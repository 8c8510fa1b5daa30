"""The program's tracer (``repro.utils.tracing``): off it records nothing and
costs nothing but the fields it times; on, the spans of a save, a commit and
a restore nest, cross threads to their parents, and agree with the fields
and stats the program already keeps."""
import gc
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.checkpoint.store import TieredStore
from repro.utils import tracing as TR

SRC = str(Path(__file__).resolve().parents[1] / "src")

SAVE_SPANS = {"repro.ckpt.save", "repro.ckpt.save.snapshot", "repro.store.stream",
              "repro.store.publish", "repro.ckpt.save.wpart",
              "repro.store.replica_write"}
COMMIT_SPANS = {"repro.ckpt.commit", "repro.ckpt.commit.wait_writes",
                "repro.ckpt.commit.read_parts", "repro.ckpt.commit.manifest",
                "repro.ckpt.commit.gc"}
RESTORE_SPANS = {"repro.ckpt.restore", "repro.ckpt.restore.list",
                 "repro.ckpt.restore.manifest", "repro.restore.plan",
                 "repro.restore.run", "repro.restore.task",
                 "repro.ckpt.restore.assemble"}


@pytest.fixture
def tracer():
    TR.disable()
    TR.reset()
    TR.enable()
    yield TR
    TR.disable()
    TR.reset()


def tree(leaves=4, n=1 << 20, seed=0):
    rng = np.random.default_rng(seed)
    return {f"w{i}": rng.standard_normal(n, dtype=np.float32) for i in range(leaves)}


def by_name(recs, name):
    return [r for r in recs if r["name"] == name]


def one(recs, name):
    (r,) = by_name(recs, name)
    return r


def covered_share(recs, parent) -> float:
    """Share of ``parent`` covered by the union of its children on its own
    thread."""
    kids = sorted((r["t0_ns"], r["t1_ns"]) for r in recs
                  if r["parent"] == parent["id"] and r["thread"] == parent["thread"])
    total, cur = 0, None
    for a, b in kids:
        if cur is None or a > cur[1]:
            if cur is not None:
                total += cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    if cur is not None:
        total += cur[1] - cur[0]
    return total / (parent["t1_ns"] - parent["t0_ns"])


def test_off_records_nothing_and_timed_spans_still_time():
    TR.disable()
    TR.reset()
    assert TR.span("repro.x") is TR.NULL
    assert TR.span("repro.y", bytes=3) is TR.NULL
    with TR.span("repro.x") as sp:
        sp.set(bytes=1)
    TR.count("repro.n", 5)
    assert TR.current() is None
    with TR.span("repro.z", timed=True) as timed:
        sum(range(10000))
    assert timed.elapsed_s > 0
    assert TR.records() == [] and TR.counters() == {} and TR.events() == []


def test_parents_cross_the_fanout_threads_and_the_restore_pool(tracer, tmp_path):
    store = TieredStore(tmp_path, seed=0)
    m = CheckpointManager(store, CheckpointPolicy(replicas=2, restore_workers=4))
    t = tree(leaves=8, n=1 << 18)
    m.save(1, t)
    m.commit(1)
    m.restore(t)
    recs = tracer.records()
    stream = one(recs, "repro.store.stream")
    writers = by_name(recs, "repro.store.replica_write")
    assert len(writers) == 2
    assert all(w["parent"] == stream["id"] for w in writers)
    assert {w["thread"] for w in writers} == {"ckpt-fanout-0", "ckpt-fanout-1"}
    assert stream["thread"] == "MainThread"
    run = one(recs, "repro.restore.run")
    tasks = by_name(recs, "repro.restore.task")
    assert tasks and all(t["parent"] == run["id"] for t in tasks)
    assert all(t["thread"].startswith("ckpt-restore") for t in tasks)


@pytest.mark.parametrize("on", [True, False])
def test_inline_checkpoint_duration_is_its_span(tracer, on):
    import time

    from repro.core.worker import InlineCoordinator
    if not on:
        TR.disable()
    saved = []
    co = InlineCoordinator(commit_fn=lambda step, num_workers: {"step": step})
    co.request("interval")
    rec = co.service(3, lambda step: (saved.append(step), time.sleep(0.01)))
    assert saved == [3] and rec["manifest_step"] == 3
    assert rec["duration_s"] >= 0.01
    if on:
        sp = one(tracer.records(), "repro.cr.checkpoint")
        assert rec["duration_s"] == (sp["t1_ns"] - sp["t0_ns"]) / 1e9
        assert sp["attrs"]["reason"] == "interval"
    else:
        assert TR.records() == []


def test_pool_tasks_take_the_submitting_span_as_parent(tracer):
    from repro.checkpoint.async_writer import WorkPool
    pool = WorkPool(max_inflight=2, workers=1, name="ckpt-test")
    try:
        with TR.span("repro.test.submit") as sp:
            pool.submit(lambda: None)
            pool.wait()
    finally:
        pool.close()
    task = one(tracer.records(), "repro.pool.task")
    assert task["parent"] == sp.id and task["attrs"]["pool"] == "ckpt-test"


def test_save_commit_restore_spans_counters_and_stats(tracer, tmp_path):
    store = TieredStore(tmp_path, seed=0)
    m = CheckpointManager(store, CheckpointPolicy(replicas=2))
    t = tree()
    m.save(7, t)
    m.commit(7)
    got, _ = m.restore(t)
    assert all(np.array_equal(got[k], t[k]) for k in t)
    recs = tracer.records()
    names = {r["name"] for r in recs}
    assert SAVE_SPANS | COMMIT_SPANS | RESTORE_SPANS <= names

    # the stream wrote the shard once per replica; every store write counted
    shard = next(p for p in tmp_path.rglob("shard_w00000.bin"))
    stream_bytes = [e["value"] for e in tracer.events()
                    if e["name"] == "repro.store.bytes_written"
                    and one(recs, "repro.store.stream")["t0_ns"] <= e["t_ns"]
                    <= one(recs, "repro.store.stream")["t1_ns"]]
    assert stream_bytes == [shard.stat().st_size * 2]
    on_disk = sum(p.stat().st_size for p in tmp_path.rglob("*") if p.is_file())
    assert tracer.counters()["repro.store.bytes_written{tier=shared}"] == on_disk
    assert one(recs, "repro.store.stream")["attrs"]["bytes"] == shard.stat().st_size * 2
    assert "repro.store.fanout_wait_s" in tracer.counters()

    # the restore's span carries the stats once, as the manager keeps them
    restore = one(recs, "repro.ckpt.restore")
    st = m.last_restore_stats
    for k in ("bytes_read", "bytes_by_tier", "tasks", "replica_fallbacks", "mode"):
        assert restore["attrs"][k] == st[k], k
    assert restore["attrs"]["bytes_read"] == sum(x.nbytes for x in t.values())
    pread = [e for e in tracer.events() if e["name"] == "repro.restore.pread_s"]
    assert len(pread) == st["tasks"] and all(e["value"] > 0 for e in pread)
    for top in ("repro.ckpt.save", "repro.ckpt.commit", "repro.ckpt.restore"):
        attrs = one(recs, top)["attrs"]
        assert attrs["cpu_user_s"] >= 0 and attrs["cpu_sys_s"] >= 0

    # children cover their parents
    assert covered_share(recs, one(recs, "repro.ckpt.save")) >= 0.95
    assert covered_share(recs, restore) >= 0.95


def test_delta_fields_are_their_spans(tracer, tmp_path):
    store = TieredStore(tmp_path, seed=0)
    m = CheckpointManager(store, CheckpointPolicy(delta=True, replicas=2))
    t = tree(leaves=3, n=1 << 18)
    parts = []
    for step in (1, 2):
        t["w0"][:1000] += 1.0
        parts.append(m.save(step, t))
        m.commit(step)
    recs = tracer.records()
    for field, name in (("fp_s", "repro.ckpt.delta.fp"), ("hash_s", "repro.ckpt.delta.hash"),
                        ("diff_s", "repro.ckpt.delta.diff"),
                        ("write_s", "repro.ckpt.delta.write")):
        spans = by_name(recs, name)
        assert len(spans) == 2, name
        for part, sp in zip(parts, spans):
            assert part["delta"][field] == (sp["t1_ns"] - sp["t0_ns"]) / 1e9, field
    snaps = by_name(recs, "repro.ckpt.save.snapshot")
    for part, sp in zip(parts, snaps):
        assert part["snapshot_s"] == (sp["t1_ns"] - sp["t0_ns"]) / 1e9
    saves = by_name(recs, "repro.ckpt.save")
    for part, sp in zip(parts, saves):
        assert 0 < part["delta"]["stall_s"] <= (sp["t1_ns"] - sp["t0_ns"]) / 1e9


def test_delta_fields_keep_their_values_with_the_tracer_off(tmp_path):
    TR.disable()
    store = TieredStore(tmp_path, seed=0)
    m = CheckpointManager(store, CheckpointPolicy(delta=True))
    d = m.save(1, tree(leaves=2, n=1 << 16))["delta"]
    assert all(d[k] > 0 for k in ("hash_s", "diff_s", "write_s", "stall_s"))
    assert TR.records() == []


def test_jit_counts_a_cache_miss_then_a_hit(tracer, tmp_path, monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")}
    compilation_cache.reset_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    x = np.arange(8, dtype=np.float32)
    try:
        def counts():
            c = tracer.counters()
            return c.get("repro.jit.cache_misses", 0), c.get("repro.jit.cache_hits", 0)

        jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
        assert counts() == (1, 0)
        jax.clear_caches()
        jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
        assert counts() == (1, 1)
        c = tracer.counters()
        assert c["repro.jit.cache_load_s"] > 0 and c["repro.jit.trace_s"] > 0
        assert c["repro.jit.lower_s"] > 0 and c["repro.jit.compile_s"] > 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_a_collection_is_a_gc_span(tracer):
    with TR.span("repro.test.outer") as outer:
        gc.collect()
    spans = by_name(tracer.records(), "repro.host.gc")
    mine = [s for s in spans if s["attrs"]["generation"] == 2]
    assert mine and mine[-1]["parent"] == outer.id
    assert "collected" in mine[-1]["attrs"]
    TR.disable()
    gc.collect()
    assert len(by_name(tracer.records(), "repro.host.gc")) == len(spans)


def test_the_tracer_follows_the_profiler(tmp_path):
    import jax
    TR.disable()
    TR.reset()
    try:
        TR.follow_profiler()
        assert not TR.enabled()
        jax.profiler.start_trace(str(tmp_path))
        try:
            TR.follow_profiler()
            assert TR.enabled()
            with TR.span("repro.test.profiled"):
                pass
        finally:
            jax.profiler.stop_trace()
        TR.follow_profiler()
        assert not TR.enabled()
        assert [r["name"] for r in TR.records()] == ["repro.test.profiled"]
        TR.enable()                      # on by hand: the profiler leaves it on
        TR.follow_profiler()
        assert TR.enabled()
    finally:
        TR.disable()
        TR.reset()


def test_records_are_bounded(tracer, monkeypatch):
    monkeypatch.setattr(TR, "MAX_RECORDS", 3)
    for _ in range(5):
        with TR.span("repro.test.many"):
            pass
    assert len(tracer.records()) == 3
    assert tracer.counters()[TR.DROPPED] == 2


def test_train_trace_out_dumps_spans_and_counters(tmp_path):
    out = tmp_path / "trace.json"
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-0.5b",
           "--reduced", "--steps", "3", "--batch", "2", "--seq", "16",
           "--interval-steps", "1", "--ckpt-dir", str(tmp_path / "ck"),
           "--trace-out", str(out)]
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(out.read_text())
    names = {s["name"] for s in got["spans"]}
    assert {"repro.train.state_ready", "repro.train.step", "repro.cr.restore_or_init",
            "repro.cr.step_boundary", "repro.fetch_tree", "repro.ckpt.save",
            "repro.ckpt.commit"} <= names
    steps = [s for s in got["spans"] if s["name"] == "repro.train.step"]
    assert len(steps) == 3
    saving = [s for s in got["spans"]
              if s["name"] == "repro.cr.step_boundary" and s["attrs"]["save"]]
    assert len(saving) == 2                 # steps 1 and 2 (step 0 never saves)
    assert got["counters"]["repro.store.bytes_written{tier=shared}"] > 0
    assert set(got["clock"]) == {"perf_counter_ns", "time_ns"}
    summary = json.loads(next(ln for ln in r.stdout.splitlines()
                              if ln.startswith("[train] summary "))[len("[train] summary "):])
    ready = next(s for s in got["spans"] if s["name"] == "repro.train.state_ready")
    assert summary["state_ready_s"] == (ready["t1_ns"] - ready["t0_ns"]) / 1e9
