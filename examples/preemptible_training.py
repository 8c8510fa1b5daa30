"""Preemptible training under a batch scheduler — the paper's Fig. 3 end-to-end.

    PYTHONPATH=src python examples/preemptible_training.py [--preset demo|100m]

Submits a training job to the Slurm simulator with a walltime far shorter than
the job needs.  The scheduler delivers SIGUSR1 before each limit; the job
checkpoints, exits 85, is requeued (output appended), restores, and repeats
until the run completes.  The final summary shows every attempt, the steps it
covered, and that total progress equals a single uninterrupted run.

Presets:
  demo  ~6M-param model, 120 steps  (finishes in a few minutes on 1 CPU core)
  100m  ~100M-param model, 300 steps (the full-scale deliverable; needs real
        compute — identical code path, just bigger numbers)
"""
import argparse
import json
import re
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.sched.slurmsim import JobSpec, SlurmSim  # noqa: E402

PRESETS = {
    # (extra train args, per-attempt walltime seconds)
    "demo": (["--reduced", "--steps", "120", "--batch", "4", "--seq", "64",
              "--step-sleep", "0.1"], 25.0),
    "100m": (["--steps", "300", "--batch", "8", "--seq", "512",
              "--microbatches", "2"], 1800.0),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="demo", choices=sorted(PRESETS))
    ap.add_argument("--arch", default="qwen2-0.5b")
    args = ap.parse_args()
    extra, walltime = PRESETS[args.preset]

    with tempfile.TemporaryDirectory() as d:
        ckpt = Path(d) / "ckpt"
        metrics = Path(d) / "metrics.json"
        cmd = [sys.executable, "-m", "repro.launch.train", "--arch", args.arch,
               "--ckpt-dir", str(ckpt), "--metrics-out", str(metrics),
               "--walltime", "86400", "--margin", "2", *extra]
        sim = SlurmSim(Path(d) / "slurm")
        jid = sim.submit(JobSpec(
            name="pretrain", cmd=cmd, walltime_s=walltime, signal_margin_s=4.0,
            env={"PYTHONPATH": str(ROOT / "src")},
            max_requeues=50))
        print(f"submitted job {jid} (walltime {walltime}s/attempt) — running...")
        sim.run(timeout_s=86400)
        rec = sim.job(jid)
        print(f"\njob state: {rec.state}   attempts: {rec.requeues + 1}   "
              f"exit codes: {rec.exit_codes}")
        out = (Path(d) / "slurm" / "pretrain.out").read_text()
        attempts = re.findall(r"=== launch attempt (\d+) on \S+ ===", out)
        resumes = re.findall(r"restored checkpoint step=(\d+)", out)
        print(f"scheduler launches: {attempts}")
        print(f"restore points:      {resumes}")
        if metrics.exists():
            m = json.loads(metrics.read_text())
            print(f"final step {m[-1]['step']}  final loss {m[-1]['loss']:.4f}")
        assert rec.state == "COMPLETED"
        print("OK — preempted training completed via checkpoint-requeue cycles")


if __name__ == "__main__":
    main()
