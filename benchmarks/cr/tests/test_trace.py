"""The reduction from a trace to busy time, idle share, kernel time and the
breakdown: on a trace made by hand, and on a small trace recorded on a TPU
v5e (three steps of qwen2-0.5b.train), against a brute-force count."""
import json

import numpy as np
import pytest

import tracered
from conftest import CR

MS = 1e6      # ns


def by_hand():
    # window 0..10 ms; a while loop 1..4 ms holding two fusions; a kernel
    # 6..7 ms; the host steps 0..5.5 and its boundary 5.5..10
    ops = [["while.3", 1 * MS, 3 * MS, ""],
           ["fusion.1", 1.5 * MS, 1 * MS, ""],
           ["fusion.2", 2.5 * MS, 1 * MS, ""],
           ["custom-call.9", 6 * MS, 1 * MS, "jit(f)/pallas_call|_fold_kernel"]]
    host = [["bench.window", 0, 10 * MS], ["bench.step", 0, 5.5 * MS],
            ["bench.step_boundary", 5.5 * MS, 4.5 * MS]]
    return {"devices": {"/device:TPU:0": ops}, "host": host, "window": [0, 10 * MS]}


def test_by_hand():
    r = tracered.reduce(by_hand())
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)
    assert r["idle_share"] == pytest.approx(0.6)
    ops = dict((n, t) for n, t in r["device_ops"])
    assert ops["fusion"] == pytest.approx(0.002)
    assert ops["while"] == pytest.approx(0.001)        # 3 ms less its 2 ms of body
    assert ops["custom-call"] == pytest.approx(0.001)
    assert r["idle_gaps"] == [["bench.step_boundary", pytest.approx(0.003)],
                              ["bench.step", pytest.approx(0.002)],
                              ["bench.step", pytest.approx(0.001)]]
    assert tracered.kernel_seconds(by_hand(), "_fold_kernel") == pytest.approx(0.001)
    assert tracered.kernel_seconds(by_hand(), "no_such_kernel") == 0.0


def test_no_device_ops_reads_no_idle_share():
    norm = by_hand()
    norm["devices"] = {}
    r = tracered.reduce(norm)
    assert r["busy_s"] == 0.0 and r["idle_share"] is None


def recorded():
    return json.loads((CR / "testdata" / "trace_qwen2_train_v5e.json").read_text())


def test_recorded_busy_against_brute_force():
    norm = recorded()
    lo, hi = norm["window"]
    (ops,) = norm["devices"].values()
    t = np.zeros(int((hi - lo) // 1000) + 1, bool)          # 1 us cells
    for _, s, d, _ in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            t[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    r = tracered.reduce(norm)
    assert r["busy_s"] == pytest.approx(t.sum() * 1e-6, rel=2e-3)
    assert 0.0 < r["idle_share"] < 0.5
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)


def test_recorded_breakdown():
    r = tracered.reduce(recorded())
    fams = [n for n, _ in r["device_ops"]]
    assert len(fams) == 10 and "fusion" in fams
    total = sum(t for _, t in r["device_ops"])
    assert total <= r["busy_s"] * 1.001                     # self times never double count
    assert len(r["idle_gaps"]) == 10
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.step", "bench.step_boundary", "host:other"}
    assert [g for _, g in r["idle_gaps"]] == sorted((g for _, g in r["idle_gaps"]), reverse=True)
