"""End-to-end Fig.-3 workflow: a real training subprocess under the slurm
simulator is preempted (walltime USR1), checkpoints, exits 85, is requeued, and
finishes with params BIT-IDENTICAL to an uninterrupted reference run."""
import json
import os
import signal
import subprocess
import sys
from pathlib import Path

import pytest

from repro.sched.slurmsim import REQUEUE_EXIT, JobSpec, SlurmSim

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _base_cmd(ckpt_dir, metrics, steps=40):
    return [sys.executable, "-m", "repro.launch.train",
            "--arch", "qwen2-0.5b", "--reduced",
            "--steps", str(steps), "--batch", "4", "--seq", "64",
            "--interval-steps", "100", "--step-sleep", "0.2",
            "--walltime", "600", "--margin", "2",
            "--ckpt-dir", str(ckpt_dir), "--metrics-out", str(metrics)]


@pytest.mark.slow
def test_preempt_requeue_bit_identical(tmp_path):
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}

    ref_dir, pre_dir = tmp_path / "ref", tmp_path / "pre"
    ref_metrics, pre_metrics = tmp_path / "ref.json", tmp_path / "pre.json"
    # 80 steps sleep 16 s: the first attempt outlasts the 17 s warning even
    # when it loads its step from the persistent compile cache
    steps = 80

    r = subprocess.run(_base_cmd(ref_dir, ref_metrics, steps), env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr

    sim = SlurmSim(tmp_path / "sim")
    jid = sim.submit(JobSpec(
        name="train", walltime_s=20.0, signal_margin_s=3.0,
        cmd=_base_cmd(pre_dir, pre_metrics, steps), env={"PYTHONPATH": SRC,
                                                  "JAX_PLATFORMS": "cpu"},
        max_requeues=10))
    sim.run(timeout_s=400)
    rec = sim.job(jid)
    assert rec.state == "COMPLETED", (rec.state, rec.exit_codes)
    assert rec.requeues >= 1, "walltime preemption never happened"
    assert REQUEUE_EXIT in rec.exit_codes

    ref = {m["step"]: m["loss"] for m in json.loads(ref_metrics.read_text())}
    pre = {m["step"]: m["loss"] for m in json.loads(pre_metrics.read_text())}
    last = max(ref)
    assert last in pre, "requeued job never reached the final step"
    assert ref[last] == pre[last], "preempted run diverged from reference"


def test_warning_signal_during_shutdown_keeps_exit_zero(tmp_path):
    """A walltime warning that lands while a finished run shuts down has
    nothing left to checkpoint: the job still exits 0, not by the signal."""
    proc = subprocess.Popen(
        _base_cmd(tmp_path / "ck", tmp_path / "m.json", steps=2),
        env={**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    out = []
    for line in proc.stdout:
        out.append(line)
        if line.startswith("[train] summary"):
            proc.send_signal(signal.SIGUSR1)
    assert proc.wait(timeout=120) == 0, "".join(out)


@pytest.mark.slow
def test_manual_preemption_scancel(tmp_path):
    """Manual C/R strategy: operator preempts (SIGTERM) mid-run; job requeues."""
    env_d = {"PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    sim = SlurmSim(tmp_path / "sim")
    jid = sim.submit(JobSpec(
        name="train", walltime_s=600.0, signal_margin_s=5.0,
        cmd=_base_cmd(tmp_path / "ck", tmp_path / "m.json", steps=25),
        env=env_d, max_requeues=3))
    import threading, time

    def preempt_later():
        time.sleep(12)
        if sim.job(jid).state == "RUNNING":
            sim.preempt(jid)

    t = threading.Thread(target=preempt_later, daemon=True)
    t.start()
    sim.run(timeout_s=300)
    rec = sim.job(jid)
    assert rec.state == "COMPLETED", (rec.state, rec.exit_codes)
    # requeue count may be 0 if the job outran the preemptor; exit codes tell
    if rec.requeues:
        assert rec.exit_codes[0] == REQUEUE_EXIT
