"""Serving driver: pause/migrate/resume, plus serving-fleet weight-follow
(the paper's C/R applied to inference state, and the chunk fabric applied to
weight distribution).

  python -m repro.launch.serve --arch llama3.2-1b --reduced --batch 4 \
      --prompt-len 12 --gen 24 --snapshot-at 8 --ckpt-dir /tmp/serve

Prefills a batch of synthetic prompts, generates; if --snapshot-at is set,
checkpoints the engine (KV caches + cursors) at that token, rebuilds a fresh
engine, restores, and finishes — printing whether the continuation matched an
unmigrated reference (it must, bit-for-bit).

Fleet mode (``--follow``): the checkpoint prefix holds PARAMETER checkpoints
pushed by a trainer (``CheckpointManager`` + ``registry.announce_push``).
This replica restores the latest push read-only, serves batches, and between
batches polls the push plane, fetches newer weights through the chunk
fabric, and swaps them in at generation boundaries (never mid-decode) with
staleness bounded by ``--max-lag-steps``:

  python -m repro.launch.serve --arch llama3.2-1b --reduced --follow \
      --ckpt-dir /tmp/weights --replica r0 --max-lag-steps 2 --batches 4
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.checkpoint.store import TieredStore, node_local_tier_roots
from repro.configs.base import get_config, reduced as reduce_cfg
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.sched.cache_registry import REGISTRY_DIRNAME, CacheRegistry
from repro.serve.engine import Engine
from repro.serve.weight_sync import ParamHandle, WeightSyncClient


def follow(args) -> int:
    """Serving-fleet follower: restore the latest pushed weights read-only,
    then serve batches while tracking the push plane.

    Fleet citizenship (PR 8): the follower advertises its fetched chunk
    inventory to the registry (follower cache), so the next replica pulls
    the delta from THIS process instead of the shared tier; a replica past
    ``--max-lag-steps`` DRAINS (refuses new batches, keeps polling, shows
    ``draining`` fleet-wide) and re-admits once it catches up, unless
    ``--on-stale raise`` asks for the fail-out-of-rotation behavior.
    ``--local-root`` mounts the node-local tiers under a private directory
    so many replicas of one host stay isolated (and peer-fetchable);
    ``--pipeline-uploads`` overlaps the device upload of push N with the
    fetch of push N+1."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    mesh = make_host_mesh()
    tier_roots = (node_local_tier_roots(Path(args.local_root))
                  if args.local_root else None)
    store = TieredStore(Path(args.ckpt_dir), tier_roots=tier_roots)
    registry = CacheRegistry(Path(args.ckpt_dir) / REGISTRY_DIRNAME)
    mgr = CheckpointManager(
        store,
        CheckpointPolicy(delta=args.delta, restore_workers=args.restore_workers),
        node=args.replica, registry=registry)
    template = jax.tree_util.tree_map(
        np.asarray, M.init_params(cfg, jax.random.PRNGKey(args.seed)))
    steps = mgr.steps()
    if not steps:
        print("no committed weight push found; start the publisher first",
              file=sys.stderr)
        return 1
    to_dev = (lambda t: jax.tree_util.tree_map(jnp.asarray, t))
    host, manifest = mgr.restore(template, promote=False,
                                 follower_cache=True)
    handle = ParamHandle(to_dev(host), step=manifest["step"])
    client = WeightSyncClient(mgr, handle, template, registry=registry,
                              replica=args.replica,
                              max_lag_steps=args.max_lag_steps,
                              to_native=to_dev, on_stale=args.on_stale,
                              pipeline_uploads=args.pipeline_uploads)
    eng = Engine(cfg, mesh, handle, batch=args.batch, max_seq=args.max_seq,
                 sync_client=client)
    rng = np.random.default_rng(args.seed)
    shape = ((args.batch, args.prompt_len, cfg.num_codebooks)
             if cfg.num_codebooks else (args.batch, args.prompt_len))
    print(f"replica {args.replica}: serving step {manifest['step']}")
    for b in range(args.batches):
        client.sync_once()                   # fetch off the request path
        if not eng.admit():                  # staleness gate: DRAIN, not die
            print(f"replica {args.replica}: draining at lag {client.lag()}",
                  file=sys.stderr)
            deadline = time.monotonic() + args.drain_timeout_s
            while not eng.admit():
                if time.monotonic() >= deadline:
                    print(f"replica {args.replica}: drain timed out after "
                          f"{args.drain_timeout_s:.0f}s at lag "
                          f"{client.lag()}", file=sys.stderr)
                    client.close()
                    mgr.close()
                    return 1
                time.sleep(args.poll_s)
                client.sync_once()
            print(f"replica {args.replica}: re-admitted at step "
                  f"{handle.step}")
        prompts = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, shape), jnp.int32)}
        eng.prefill(prompts)                 # boundary: staged push swaps in
        eng.generate(args.gen)
        print(f"batch {b}: served step {handle.step}, "
              f"lag {client.lag()}, swaps {handle.swap_count}, "
              f"swap_stall {handle.last_swap_s * 1e6:.0f}us")
    client.close()
    mgr.close()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--snapshot-at", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_serve")
    ap.add_argument("--seed", type=int, default=0)
    # fleet follower mode
    ap.add_argument("--follow", action="store_true",
                    help="serve as a weight-sync follower of --ckpt-dir")
    ap.add_argument("--replica", default="r0",
                    help="this replica's name in the registry fleet view")
    ap.add_argument("--max-lag-steps", type=int, default=None,
                    help="staleness bound: force a swap (then drain or "
                         "fail) past this many steps behind the push")
    ap.add_argument("--on-stale", choices=("drain", "raise"),
                    default="drain",
                    help="--follow: past --max-lag-steps, drain and "
                         "re-admit (default) or fail out of rotation")
    ap.add_argument("--drain-timeout-s", type=float, default=60.0,
                    help="--follow: give up on a drain that never "
                         "re-admits after this long")
    ap.add_argument("--poll-s", type=float, default=0.1,
                    help="--follow: push-plane poll interval while "
                         "draining")
    ap.add_argument("--pipeline-uploads", action="store_true",
                    help="--follow: overlap device upload of push N with "
                         "the fetch of push N+1")
    ap.add_argument("--local-root", default=None,
                    help="--follow: private node-local tier root for this "
                         "replica (isolates + peer-exposes its cache)")
    ap.add_argument("--batches", type=int, default=4,
                    help="--follow: request batches to serve before exit")
    ap.add_argument("--delta", action="store_true", default=True,
                    help="--follow: expect delta (chunked) weight pushes")
    ap.add_argument("--restore-workers", type=int, default=0)
    args = ap.parse_args(argv)
    configure_compile_cache()
    if args.follow:
        return follow(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    mesh = make_host_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    shape = ((args.batch, args.prompt_len, cfg.num_codebooks)
             if cfg.num_codebooks else (args.batch, args.prompt_len))
    prompts = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, shape), jnp.int32)}

    def fresh():
        return Engine(cfg, mesh, params, batch=args.batch, max_seq=args.max_seq)

    # reference (no migration)
    ref = fresh()
    ref.prefill(prompts)
    ref_tokens = ref.generate(args.gen)

    if not args.snapshot_at:
        print(f"generated {args.gen} tokens x {args.batch} requests")
        print("request 0:", np.asarray(ref_tokens[0]).ravel()[:16], "...")
        return 0

    eng = fresh()
    eng.prefill(prompts)
    first = eng.generate(args.snapshot_at)
    mgr = CheckpointManager(TieredStore(Path(args.ckpt_dir)))
    host = jax.tree_util.tree_map(np.asarray, eng.snapshot())
    mgr.save(0, host)
    mgr.commit(0)
    del eng
    print(f"snapshotted at token {args.snapshot_at}; migrating...")

    eng2 = fresh()
    restored, _ = mgr.restore(host)
    eng2.restore(jax.tree_util.tree_map(jnp.asarray, restored))
    rest = eng2.generate(args.gen - args.snapshot_at)
    got = np.concatenate([first, rest], axis=1)
    match = np.array_equal(got, ref_tokens)
    print(f"continuation {'MATCHES' if match else 'DIVERGED FROM'} the "
          f"unmigrated reference")
    return 0 if match else 1


if __name__ == "__main__":
    sys.exit(main())
