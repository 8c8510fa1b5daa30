#!/usr/bin/env python3
"""Run the training checkpoint-restart loop on a TPU and check it is bit-exact.

    python chip_smoke.py               # one chip: full-width qwen2-0.5b
    python chip_smoke.py --four-chips  # four chips: llama3.2-1b, elastic restore

One chip.  Each phase is its own process, one after another, because a chip
belongs to one process at a time; this parent never imports JAX.
  device     the devices JAX sees: anything but a TPU fails the run
  reference  ``python -m repro.launch.train`` at published widths (batch 8,
             seq 128), uninterrupted, with interval checkpoints
  preempted  the same command, sent SIGUSR1 once its first interval
             checkpoint is committed: it checkpoints and exits 85 (requeue)
  resumed    the same command again: it restores and runs to the end
  compare    the two final checkpoints are bit-identical leaf by leaf, and
             the losses are equal from the resume step on
  device-fp  a delta checkpoint with dirty detection on the device, once with
             the Pallas kernel and once with the jnp path: identical committed
             manifests (chunk hashes and fingerprints), and the kernel is in
             the lowered program

Four chips (``--four-chips``), one process driving all four: llama3.2-1b at
published widths (14.8 GB of state, more than one chip holds) trains on a
(4, 1) mesh and checkpoints; the checkpoint resumes on (4, 1) bit-exact
against the uninterrupted run, and restores onto (2, 2) with bit-identical
leaves and a finite next-step loss close to the reference's.

The timings printed on the way are readings of single runs, not a
benchmark.  The checkpoints go to a temporary directory, the phase logs to
``chiprun_out/chip_smoke/``.  The last line of a passing run is
``{"ok": true, "device": {...}}``; any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
LOG_DIR = ROOT / "chiprun_out" / "chip_smoke"

# A full-width checkpoint is 5.9 GB, and every byte written counts against
# the machine's disk, deleted or not: the phases below write seven of them.
ARCH = "qwen2-0.5b"
STEPS, INTERVAL = 8, 4              # saves: interval at step 4, final at 7
STEP_SLEEP_S = 0.5                  # the SIGUSR1 lands well before step 7
FP_STEPS = 2                        # device-fp: one save, the final at step 1
REQUEUE_EXIT = 85                   # launch/train.py's requeue exit code
PHASE_TIMEOUT_S = 900

FOUR_CHIP_ARCH = "llama3.2-1b"
FOUR_CHIP_STEPS, FOUR_CHIP_SAVE_AT = 4, 1
FOUR_CHIP_LOSS_RTOL = 1e-3          # (2, 2) next-step loss against (4, 1)

DEVICE_TAG = "[smoke] device "
SUMMARY_TAG = "[train] summary "
# JAX's own timings of the train step (train/step.py's jitted function)
STEP_TIMINGS = {
    "step_trace_s": re.compile(r"Finished tracing \+ transforming "
                               r"sharded_train_step for pjit in ([0-9.]+) sec"),
    "step_lower_s": re.compile(r"Finished jaxpr to MLIR module conversion "
                               r"jit\(sharded_train_step\) in ([0-9.]+) sec"),
    "step_compile_s": re.compile(r"Finished XLA compilation of "
                                 r"jit\(sharded_train_step\) in ([0-9.]+) sec"),
}
STEP_CACHE_HIT = "Persistent compilation cache hit for 'jit_sharded_train_step'"


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


# ---------------------------------------------------------------------------
# parent: phases as child processes
# ---------------------------------------------------------------------------

def child_env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(SRC), env.get("PYTHONPATH")) if p)
    # the compile and persistent-cache lines the readings below are read from
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler,jax._src.dispatch"
    env.update(extra)
    return env


def run_phase(name: str, argv: list, env: dict, on_poll=None) -> tuple:
    """Run one phase to its end; returns (exit code, its output).  The
    output goes to ``LOG_DIR/<name>.log``; ``on_poll(proc)`` is called
    while the child runs."""
    log = LOG_DIR / f"{name}.log"
    t0 = time.monotonic()
    with open(log, "w") as fh:
        proc = subprocess.Popen(argv, stdout=fh, stderr=subprocess.STDOUT,
                                env=env, cwd=ROOT)
        try:
            while proc.poll() is None:
                if time.monotonic() - t0 > PHASE_TIMEOUT_S:
                    fail(f"{name}: no exit within {PHASE_TIMEOUT_S} s")
                if on_poll is not None:
                    on_poll(proc)
                time.sleep(0.05)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    out = log.read_text(errors="replace")
    print(f"[smoke] {name}: exit {proc.returncode} after "
          f"{time.monotonic() - t0:.1f} s (log {log.relative_to(ROOT)})",
          flush=True)
    return proc.returncode, out


def tagged_json(out: str, tag: str) -> dict:
    lines = [ln for ln in out.splitlines() if ln.startswith(tag)]
    if not lines:
        fail(f"no '{tag.strip()}' line in the phase output")
    return json.loads(lines[-1][len(tag):])


def check_device(name: str, info: dict, count: int) -> dict:
    print(f"[smoke] {name}: platform={info['platform']} "
          f"device_kind={info['device_kind']} "
          f"device_count={info['device_count']}", flush=True)
    if info["platform"] != "tpu":
        fail(f"{name} ran on {info['platform']}, not on a TPU")
    if info["device_count"] != count:
        fail(f"{name} sees {info['device_count']} devices, wants {count}")
    return info


def child_phase(name: str, count: int, *child_args) -> dict:
    """Run one of this script's own children (``--child ...``): it must
    exit 0 on ``count`` TPUs.  Echoes its ``[smoke]`` lines and returns
    its device."""
    rc, out = run_phase(name, [sys.executable, __file__, "--child",
                               *child_args], child_env())
    if rc != 0:
        fail(f"{name} exited {rc}: {out[-4000:]}")
    device = check_device(name, tagged_json(out, DEVICE_TAG), count)
    for ln in out.splitlines():
        if ln.startswith("[smoke] ") and not ln.startswith(DEVICE_TAG):
            print(ln, flush=True)
    return device


def train_argv(ckpt_dir: Path, metrics: Path, steps: int, *extra) -> list:
    return [sys.executable, "-m", "repro.launch.train", "--arch", ARCH,
            "--steps", str(steps), "--batch", "8", "--seq", "128",
            "--ckpt-dir", str(ckpt_dir), "--metrics-out", str(metrics),
            *extra]


def train_phase(name: str, argv: list, env: dict, want_rc: int,
                on_poll=None) -> str:
    rc, out = run_phase(name, argv, env, on_poll)
    if rc != want_rc:
        print(out[-4000:], file=sys.stderr)
        fail(f"{name} exited {rc}, wants {want_rc}")
    summary = tagged_json(out, SUMMARY_TAG)
    check_device(name, summary, 1)
    jax_s = " ".join(f"{k}={[float(v) for v in rx.findall(out)]}"
                     for k, rx in STEP_TIMINGS.items())
    print(f"[smoke] {name} readings (single run, not a benchmark): "
          f"state_ready_s={summary['state_ready_s']} "
          f"first_step_s={summary['first_step_s']} {jax_s} "
          f"step_cache_hits={out.count(STEP_CACHE_HIT)} "
          f"step_s_median={summary['step_s']} save_s={summary['save_s']} "
          f"peak_bytes_in_use={summary['peak_bytes_in_use']}", flush=True)
    return out


def committed_manifests(ckpt_dir: Path) -> dict:
    """{step: manifest path} for every committed checkpoint under a
    train.py ``--ckpt-dir`` (a checkpoint exists iff its manifest does)."""
    return {int(p.parent.name.split("_")[1]): p
            for p in ckpt_dir.glob("**/step_*/MANIFEST.json")}


def losses(metrics: Path) -> dict:
    return {m["step"]: m["loss"] for m in json.loads(metrics.read_text())}


def one_chip(tmp: Path) -> dict:
    device = child_phase("device", 1, "device")
    env = child_env()

    ref_dir, pre_dir = tmp / "reference", tmp / "preempted"
    cr_args = ("--interval-steps", str(INTERVAL),
               "--step-sleep", str(STEP_SLEEP_S))
    train_phase("reference", train_argv(ref_dir, tmp / "reference.json",
                                        STEPS, *cr_args), env, 0)

    sent = []

    def preempt_after_first_interval(proc):
        if not sent and INTERVAL in committed_manifests(pre_dir):
            proc.send_signal(signal.SIGUSR1)
            sent.append(time.monotonic())

    argv = train_argv(pre_dir, tmp / "preempted.json", STEPS, *cr_args)
    train_phase("preempted", argv, env, REQUEUE_EXIT,
                preempt_after_first_interval)
    exit_step = max(committed_manifests(pre_dir))
    if not sent or not INTERVAL < exit_step < STEPS - 1:
        fail(f"preempted run checkpointed at step {exit_step}, not between "
             f"its first interval checkpoint and the end")
    print(f"[smoke] preempted: SIGUSR1 after the step-{INTERVAL} checkpoint, "
          f"requeue checkpoint at step {exit_step}", flush=True)

    out = train_phase("resumed", argv, env, 0)
    restored = f"[cr] restored checkpoint step={exit_step} -> resuming at " \
               f"{exit_step + 1}"
    if restored not in out:
        fail(f"resumed run did not log '{restored}'")

    ref_loss, res_loss = losses(tmp / "reference.json"), losses(
        tmp / "preempted.json")
    if sorted(res_loss) != list(range(exit_step + 1, STEPS)):
        fail(f"resumed run covered steps {sorted(res_loss)}")
    diff = [s for s in res_loss if res_loss[s] != ref_loss[s]]
    if diff:
        fail(f"losses differ from the reference at steps {diff}")
    print(f"[smoke] losses equal to the reference's at steps "
          f"{exit_step + 1}..{STEPS - 1}", flush=True)
    child_phase("compare", 1, "compare", str(ref_dir), str(pre_dir),
                str(STEPS - 1))
    shutil.rmtree(ref_dir)
    shutil.rmtree(pre_dir)

    manifests = {}
    ir_dir = tmp / "ir_pallas"
    for impl in ("pallas", "auto"):
        d = tmp / f"device_fp_{impl}"
        extra = {"REPRO_DEVICE_FP_IMPL": impl}
        if impl == "pallas":
            extra["JAX_DUMP_IR_TO"] = str(ir_dir)
        train_phase(f"device_fp_{impl}", train_argv(
            d, tmp / f"device_fp_{impl}.json", FP_STEPS, "--ckpt-delta",
            "--ckpt-device-fp"), child_env(**extra), 0)
        manifests[impl] = {}
        for step, path in sorted(committed_manifests(d).items()):
            m = json.loads(path.read_text())
            manifests[impl][step] = {"step": m["step"], "leaves": m["leaves"]}
        shutil.rmtree(d)
    if sorted(manifests["pallas"]) != [FP_STEPS - 1]:
        fail(f"device-fp committed steps {sorted(manifests['pallas'])}")
    if manifests["pallas"] != manifests["auto"]:
        fail("device-fp manifests differ between the Pallas and jnp paths")
    chunks = [c for m in manifests["pallas"].values() for leaf in m["leaves"]
              for c in leaf["chunks"]]
    if not chunks or any("fp" not in c for c in chunks):
        fail("device-fp manifests carry no per-chunk fingerprints")
    kernel_modules = [p.name for p in ir_dir.glob("*chunk_fingerprints*")
                      if "tpu_custom_call" in p.read_text(errors="replace")]
    if not kernel_modules:
        fail("no chunk_fingerprints program with a tpu_custom_call")
    print(f"[smoke] device-fp: pallas == auto over {len(chunks)} chunk "
          f"entries in steps {sorted(manifests['pallas'])}; kernel in "
          f"{len(kernel_modules)} lowered program(s)", flush=True)
    return device


def four_chips(tmp: Path) -> dict:
    device = child_phase("device", 4, "device")
    child_phase("four_chips", 4, "four-chips", str(tmp / "ckpt"))
    return device


# ---------------------------------------------------------------------------
# children (each imports JAX and holds the chip while it runs)
# ---------------------------------------------------------------------------

def print_device():
    import jax

    dev = jax.devices()[0]
    print(DEVICE_TAG + json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices())}), flush=True)


def flat_host(tree) -> dict:
    import numpy as np

    from repro.utils.tree import flatten_with_names

    return {k: np.asarray(v) for k, v in flatten_with_names(tree)}


def bit_mismatches(a: dict, b: dict) -> list:
    """Names of leaves whose dtype, shape or bytes differ (bytes, not
    values: NaN payloads and signed zeros count)."""
    if set(a) != set(b):
        return sorted(set(a) ^ set(b))
    return [k for k in a if a[k].dtype != b[k].dtype
            or a[k].shape != b[k].shape or a[k].tobytes() != b[k].tobytes()]


def compare_child(ref_dir: str, other_dir: str, step: int):
    from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
    from repro.checkpoint.store import TieredStore
    from repro.configs.base import get_config
    from repro.optim import adamw
    from repro.train import step as TS

    print_device()
    template = TS.abstract_train_state(get_config(ARCH), adamw.OptConfig())

    def load(d):
        mgr = CheckpointManager(TieredStore(Path(d)),
                                CheckpointPolicy(replicas=1))
        try:
            tree, _ = mgr.restore(template, step)
        finally:
            mgr.close()
        return flat_host(tree)

    a = load(ref_dir)
    b = load(other_dir)
    bad = bit_mismatches(a, b)
    if bad:
        fail(f"step-{step} checkpoints differ in leaves {bad[:8]}")
    nbytes = sum(v.nbytes for v in a.values())
    print(f"[smoke] compare: step-{step} checkpoints bit-identical, "
          f"{len(a)} leaves, {nbytes} bytes", flush=True)


def four_chip_child(ckpt_dir: str):
    """Train on (4, 1), save, resume on (4, 1) and restore onto (2, 2)."""
    import jax

    from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
    from repro.checkpoint.store import TieredStore
    from repro.configs.base import get_config
    from repro.core.virtualization import fetch_tree, place_tree
    from repro.data.pipeline import SyntheticTokens
    from repro.launch.compile_cache import configure_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw
    from repro.parallel.mesh_rules import Rules
    from repro.train import step as TS

    configure_compile_cache()
    print_device()
    cfg = get_config(FOUR_CHIP_ARCH)
    oc = adamw.OptConfig(warmup_steps=10, decay_steps=FOUR_CHIP_STEPS)
    pipe = SyntheticTokens(cfg, 8, 128, seed=0)
    axes = TS.state_logical_axes(cfg)
    mgr = CheckpointManager(TieredStore(Path(ckpt_dir)),
                            CheckpointPolicy(replicas=1))

    def on_mesh(shape):
        rules = Rules(make_mesh(shape, ("data", "model")))
        step_fn, st_sh, _ = TS.make_train_step(cfg, rules.mesh, oc,
                                               rules=rules, donate=False)
        return rules, step_fn, st_sh

    def train(step_fn, state, start, stop):
        out = {}
        for s in range(start, stop):
            t0 = time.perf_counter()
            state, m = step_fn(state, pipe.batch_at(s))
            out[s] = float(m["loss"])
            print(f"[smoke] step {s} loss {out[s]!r} "
                  f"({time.perf_counter() - t0:.3f} s)", flush=True)
        return state, out

    def restore(rules):
        t0 = time.perf_counter()
        host, _ = mgr.restore(TS.abstract_train_state(cfg, oc),
                              FOUR_CHIP_SAVE_AT)
        state = place_tree(host, axes, rules)
        jax.block_until_ready(state)
        print(f"[smoke] restore + place on {rules.mesh.devices.shape}: "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        return state

    # uninterrupted reference on (4, 1), checkpointed after FOUR_CHIP_SAVE_AT
    rules41, step41, st_sh = on_mesh((4, 1))
    state = jax.jit(lambda k: TS.init_train_state(cfg, oc, k),
                    out_shardings=st_sh)(jax.random.PRNGKey(0))
    state, ref_loss = train(step41, state, 0, FOUR_CHIP_SAVE_AT + 1)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    per_chip = max(d.memory_stats().get("peak_bytes_in_use", 0)
                   if d.memory_stats() else 0 for d in jax.devices())
    print(f"[smoke] {FOUR_CHIP_ARCH}: {nbytes} bytes of train state over "
          f"{len(jax.devices())} chips; peak bytes in use on a chip "
          f"{per_chip}", flush=True)
    t0 = time.perf_counter()
    host = fetch_tree(state)
    saved = flat_host(host)
    mgr.save(FOUR_CHIP_SAVE_AT, host)
    mgr.commit(FOUR_CHIP_SAVE_AT)
    print(f"[smoke] save of step {FOUR_CHIP_SAVE_AT}: "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    state, more = train(step41, state, FOUR_CHIP_SAVE_AT + 1, FOUR_CHIP_STEPS)
    ref_loss.update(more)
    ref_final = flat_host(fetch_tree(state))
    del state

    # resume on (4, 1): bit-exact against the uninterrupted run
    state, res_loss = train(step41, restore(rules41), FOUR_CHIP_SAVE_AT + 1,
                            FOUR_CHIP_STEPS)
    diff = [s for s in res_loss if res_loss[s] != ref_loss[s]]
    if diff:
        fail(f"(4, 1) resume: losses differ at steps {diff}")
    bad = bit_mismatches(ref_final, flat_host(fetch_tree(state)))
    if bad:
        fail(f"(4, 1) resume: final state differs in leaves {bad[:8]}")
    del state
    print(f"[smoke] (4, 1) resume: losses equal at steps {sorted(res_loss)}, "
          f"final state bit-identical ({len(ref_final)} leaves)", flush=True)

    # restore onto (2, 2): the saved leaves, resharded, then one step
    rules22, step22, _ = on_mesh((2, 2))
    state = restore(rules22)
    bad = bit_mismatches(saved, flat_host(fetch_tree(state)))
    if bad:
        fail(f"(2, 2) restore: leaves differ from the saved state {bad[:8]}")
    nxt = FOUR_CHIP_SAVE_AT + 1
    _, loss22 = train(step22, state, nxt, nxt + 1)
    a, b = loss22[nxt], ref_loss[nxt]
    if not (math.isfinite(a) and abs(a - b) <= FOUR_CHIP_LOSS_RTOL * abs(b)):
        fail(f"(2, 2) step {nxt} loss {a!r} against (4, 1) {b!r}")
    print(f"[smoke] (2, 2) restore: {len(saved)} leaves bit-identical to the "
          f"saved state; step {nxt} loss {a!r} against (4, 1) {b!r} "
          f"(rel diff {abs(a - b) / abs(b):.3e})", flush=True)
    mgr.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip elastic restore phase")
    ap.add_argument("--child", nargs="+", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        kind, *rest = args.child
        if kind == "device":
            print_device()
        elif kind == "compare":
            compare_child(rest[0], rest[1], int(rest[2]))
        elif kind == "four-chips":
            four_chip_child(rest[0])
        else:
            fail(f"unknown child {kind}")
        return 0

    if not (SRC / "repro" / "launch" / "train.py").is_file():
        fail(f"no repro sources under {SRC}: run from a checkout")
    LOG_DIR.mkdir(parents=True, exist_ok=True)
    tmp = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        free = shutil.disk_usage(tmp).free
        print(f"[smoke] checkpoints under {tmp}: {free} bytes free", flush=True)
        device = four_chips(tmp) if args.four_chips else one_chip(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
