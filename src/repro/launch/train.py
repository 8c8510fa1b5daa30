"""End-to-end training driver with first-class checkpoint-restart.

This is the job script of the paper's Fig. 3, as a framework CLI:

  python -m repro.launch.train --arch qwen2-0.5b --reduced --steps 200 \\
      --batch 8 --seq 128 --ckpt-dir /tmp/run1 --interval-steps 25 \\
      --walltime 300 --margin 10

Behaviour:
  * restores the latest committed checkpoint if one exists (else cold start);
  * checkpoints every --interval-steps, on trapped SIGTERM/SIGUSR1, and when
    the walltime margin is reached;
  * exits with code 85 (REQUEUE_EXIT) when interrupted mid-run so the batch
    scheduler (sched/slurmsim.py or a real Slurm wrapper) requeues it;
  * optionally attaches to an external checkpoint coordinator
    (--coordinator host:port --worker-id N) for multi-worker rounds.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
from pathlib import Path

import jax

from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.checkpoint.store import TieredStore, node_local_tier_roots
from repro.configs.base import get_config, reduced as reduce_cfg
from repro.core.cr_manager import CRManager
from repro.core.requeue import RequeueFile, WalltimeTracker, detect_node
from repro.sched.cache_registry import (ENV_PEER_ROOTS, REGISTRY_DIRNAME,
                                        CacheRegistry, parse_peer_roots)
from repro.core.signals import SignalTrap
from repro.core.worker import CkptClient, InlineCoordinator
from repro.data.pipeline import PipelineState, SyntheticTokens
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw
from repro.parallel.mesh_rules import Rules
from repro.train import step as TS
from repro.utils import tracing as TR

REQUEUE_EXIT = 85


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--ckpt-mode", default="sync", choices=["sync", "async"])
    ap.add_argument("--ckpt-incremental", action="store_true")
    ap.add_argument("--ckpt-delta", action="store_true",
                    help="content-addressed delta checkpoints (shard v3): "
                         "each save writes only the chunks whose hash "
                         "changed since the parent step, and restores "
                         "fetch only chunks the node is missing")
    ap.add_argument("--ckpt-rebase-every", type=int, default=8,
                    help="delta-chain length bound: after this many chained "
                         "delta commits the manifest re-baselines (chunk "
                         "dedup makes the rebaseline itself free)")
    ap.add_argument("--ckpt-replicas", type=int, default=1)
    ap.add_argument("--ckpt-promote", default="off",
                    choices=["off", "on_restore", "eager"],
                    help="tee restored/committed checkpoints into the "
                         "node-local tier so the next restart on this node "
                         "skips the shared filesystem")
    ap.add_argument("--ckpt-promote-tier", default="local",
                    choices=["ram", "local"])
    ap.add_argument("--local-root", default=None,
                    help="node-local tier root: mounts the local/ram tiers "
                         "under this path instead of --ckpt-dir, so promoted "
                         "caches are per-node (defaults to $REPRO_LOCAL_ROOT "
                         "as set by sched/slurmsim.py placements)")
    ap.add_argument("--peer-roots", default=None,
                    help="warm-peer cache roots as 'name=path,name=path': "
                         "a cold-node restore sources checkpoint ranges from "
                         "these peers' local tiers instead of the shared "
                         "filesystem (defaults to $REPRO_PEER_ROOTS as set "
                         "by the scheduler, then to the last requeue "
                         "record's peer_roots)")
    ap.add_argument("--restore-workers", type=int, default=0,
                    help="parallel restore read pool size (0=auto, 1=serial)")
    ap.add_argument("--hash-workers", type=int, default=0,
                    help="parallel chunk hash/CRC pool size for delta saves "
                         "(0=auto / $REPRO_HASH_WORKERS, 1=serial)")
    ap.add_argument("--ckpt-compress", type=int, default=0,
                    help="per-chunk compression level for delta chunk files "
                         "(0=off; >=1 frames each stored chunk with zstd "
                         "when available, else zlib — hashes stay over the "
                         "raw bytes, so dedup and fingerprints are "
                         "unaffected)")
    ap.add_argument("--io-batch", type=int, default=0,
                    help="ranges per batched restore-read submission "
                         "(0=auto / $REPRO_IO_BATCH, 1=per-range reads)")
    ap.add_argument("--ckpt-fingerprint", action="store_true",
                    help="delta saves stamp per-chunk 32-bit fingerprints "
                         "and use the parent step's as a dirty-chunk "
                         "pre-filter: fingerprint-equal chunks skip blake2b "
                         "(opt-in: a dirty chunk colliding on 32 bits would "
                         "be treated as clean)")
    ap.add_argument("--ckpt-predump", action="store_true",
                    help="CRIU-style pre-dump: before each interval "
                         "checkpoint, snapshot + hash + pre-write chunks in "
                         "the background so the save stall covers only "
                         "bytes dirtied in the last --ckpt-predump-lead "
                         "steps (requires --ckpt-delta)")
    ap.add_argument("--ckpt-predump-lead", type=int, default=1,
                    help="pre-dump window: a pre-dump fires at EVERY step "
                         "in the last N steps before the interval boundary "
                         "(iterative pre-copy — each lead re-hashes only "
                         "what dirtied since the lead before)")
    ap.add_argument("--ckpt-device-fp", action="store_true",
                    help="device-resident dirty detection: run the "
                         "fingerprint kernel on live device params and copy "
                         "only fp-dirty chunks host-side — clean chunks "
                         "cost zero device->host bytes (requires "
                         "--ckpt-delta; set REPRO_DEVICE_FP_IMPL to pick "
                         "the kernel impl)")
    ap.add_argument("--ckpt-calibrate", action="store_true",
                    help="measure per-tier store bandwidth/latency at "
                         "startup (cached in tier_profile.json) and apply "
                         "the profile to tier routing")
    ap.add_argument("--interval-steps", type=int, default=0)
    ap.add_argument("--walltime", type=float, default=0.0)
    ap.add_argument("--margin", type=float, default=5.0)
    ap.add_argument("--coordinator", default=None, help="host:port")
    ap.add_argument("--worker-id", type=int, default=0)
    ap.add_argument("--num-workers", type=int, default=1)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--step-sleep", type=float, default=0.0,
                    help="artificial per-step delay (benchmark pacing)")
    ap.add_argument("--trace-out", default=None,
                    help="record the program's spans and counters and write "
                         "them here as JSON when the attempt ends")
    return ap


def run_summary(state_ready_s: float, metrics_log: list, events: list) -> dict:
    """One attempt's device and timings: ``state_ready_s`` (restore or
    init, placed on device), ``first_step_s`` (compile included),
    ``step_s`` (the later steps' median), ``save_s`` per committed save,
    and the device's peak memory where the backend reports it."""
    dev = jax.devices()[0]
    steps = sorted(m["step_s"] for m in metrics_log[1:])
    return {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "state_ready_s": state_ready_s,
        "first_step_s": metrics_log[0]["step_s"] if metrics_log else None,
        "step_s": steps[len(steps) // 2] if steps else None,
        "save_s": [e["duration_s"] for e in events if "duration_s" in e],
        "peak_bytes_in_use": (dev.memory_stats() or {}).get("peak_bytes_in_use"),
    }


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    if args.ckpt_delta and args.ckpt_incremental:
        sys.exit("--ckpt-delta and --ckpt-incremental are mutually exclusive")
    if ((args.ckpt_predump or args.ckpt_fingerprint or args.ckpt_device_fp)
            and not args.ckpt_delta):
        sys.exit("--ckpt-predump/--ckpt-fingerprint/--ckpt-device-fp "
                 "require --ckpt-delta")
    # trap preemption signals from the very start: a USR1 during jit compile /
    # restore must checkpoint-and-requeue, not kill the process (default USR1
    # action is terminate) — the paper's startup-time lesson (Fig. 2) applies
    # to the C/R loop itself.
    trap = SignalTrap()
    trap.__enter__()
    if args.trace_out:
        TR.enable()
    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    oc = adamw.OptConfig(lr=args.lr, warmup_steps=10, decay_steps=max(args.steps, 2))

    mesh = make_host_mesh()
    rules = Rules(mesh)
    jitted, st_sh, batch_sh_fn = TS.make_train_step(
        cfg, mesh, oc, microbatches=args.microbatches, rules=rules, donate=False)

    # multi-node placement: the shared tier lives under --ckpt-dir for every
    # node; the node-LOCAL tiers mount under the root the scheduler handed us,
    # so a shared->local promotion warms exactly this node's cache and the
    # restore-aware scheduler can route the next requeue back here.
    local_root = args.local_root or os.environ.get("REPRO_LOCAL_ROOT")
    tier_roots = node_local_tier_roots(local_root) if local_root else None
    store = TieredStore(Path(args.ckpt_dir), tier_roots=tier_roots)
    if args.ckpt_calibrate:
        # measured tier profile (cached in tier_profile.json under the store
        # root) replaces the static tier table — restore sizing and promote
        # routing then reflect THIS machine's actual I/O planes
        from repro.checkpoint.calibrate import calibrate_tiers
        calibrate_tiers(store)
    requeue_file = RequeueFile(Path(args.ckpt_dir) / "requeue.json")
    prior = requeue_file.load()
    # peer fabric: scheduler hint first, then whatever the last attempt
    # recorded; the registry adds decentralized discovery on top
    node = detect_node()
    peers = parse_peer_roots(args.peer_roots
                             or os.environ.get(ENV_PEER_ROOTS))
    if not peers:
        peers = {n: Path(r)
                 for n, r in (prior.get("peer_roots") or {}).items()}
    registry = CacheRegistry(
        Path(args.ckpt_dir) / REGISTRY_DIRNAME)
    policy = CheckpointPolicy(replicas=args.ckpt_replicas,
                              mode=args.ckpt_mode,
                              incremental=args.ckpt_incremental,
                              delta=args.ckpt_delta,
                              rebase_every=args.ckpt_rebase_every,
                              restore_workers=args.restore_workers,
                              fingerprint=args.ckpt_fingerprint,
                              device_fp=args.ckpt_device_fp,
                              hash_workers=args.hash_workers,
                              compress=args.ckpt_compress,
                              io_batch=args.io_batch,
                              promote=args.ckpt_promote,
                              promote_tier=args.ckpt_promote_tier)
    ckpt = CheckpointManager(store, policy, worker_id=args.worker_id,
                             num_workers=args.num_workers, peer_roots=peers,
                             node=node, registry=registry)

    if args.coordinator:
        host, port = args.coordinator.rsplit(":", 1)
        client = CkptClient(host, int(port), args.worker_id)
    else:
        client = InlineCoordinator(commit_fn=ckpt.commit)

    walltime = None
    if args.walltime:
        walltime = WalltimeTracker(args.walltime, args.margin,
                                   consumed_s=prior.get("consumed_s", 0.0))

    pipe = SyntheticTokens(cfg, args.batch, args.seq, seed=args.seed)

    try:
        crm = CRManager(ckpt, client=client, signal_trap=trap, walltime=walltime,
                        requeue_file=requeue_file,
                        interval_steps=args.interval_steps or None,
                        predump=args.ckpt_predump,
                        predump_lead=args.ckpt_predump_lead,
                        cfg=cfg, rules=rules, node=node,
                        peers=peers or None)

        def init_fn():
            return TS.init_train_state(cfg, oc, jax.random.PRNGKey(args.seed))

        # template for restore: abstract state (host arrays will be placed in)
        templates = {"state": TS.abstract_train_state(cfg, oc)}
        axes = {"state": TS.state_logical_axes(cfg)}
        with TR.span("repro.train.state_ready", timed=True) as ready:
            state, meta, start_step = crm.restore_or_init(init_fn, templates, axes)
            jax.block_until_ready(state)
        state_ready_s = ready.elapsed_s
        if meta is not None and "data_state" in meta:
            pipe.restore(PipelineState.from_dict(meta["data_state"]))

        metrics_log = []
        exit_code = 0
        step = start_step
        for step in range(start_step, args.steps):
            batch = next(pipe)
            with TR.span("repro.train.step", timed=True, step=step) as timed:
                state, metrics = jitted(state, batch)
                loss = float(metrics["loss"])   # waits for the step
            step_s = timed.elapsed_s
            if args.step_sleep:
                time.sleep(args.step_sleep)
            metrics_log.append({"step": step, "loss": loss,
                                "t": time.time(), "step_s": step_s})
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step} loss {loss:.4f}", flush=True)

            extra = {"data_state": pipe.state().to_dict()}
            action = crm.step_boundary(step, lambda: state, extra_meta=extra)
            if action == "exit":
                crm.request_requeue(step, reason=crm.exit_reason() or "")
                print(f"[train] interrupted at step {step} -> requeue", flush=True)
                exit_code = REQUEUE_EXIT
                break
        else:
            # run completed: final checkpoint so eval/serving can pick it up
            crm.checkpoint_now(args.steps - 1, lambda: state, reason="final",
                               extra_meta={"data_state": pipe.state().to_dict(),
                                           "completed": True})
            print(f"[train] completed {args.steps} steps", flush=True)

        if args.metrics_out:
            Path(args.metrics_out).write_text(json.dumps(metrics_log))
        crm.close()
        print("[train] summary " + json.dumps(
            run_summary(state_ready_s, metrics_log, crm.events)), flush=True)
    finally:
        trap.__exit__(None, None, None)
        if args.trace_out:
            TR.dump(args.trace_out)
    return exit_code


if __name__ == "__main__":
    # main() traps these signals while it runs and then puts back what it
    # found.  Finding them ignored, it leaves them ignored while the finished
    # process frees its state and exits: a walltime warning landing then has
    # nothing left to save and must not kill a completed job.
    for sig in SignalTrap().signals:
        signal.signal(sig, signal.SIG_IGN)
    sys.exit(main())
