"""The readers of the program's own spans and counters: on a context made
by hand, on a program without a tracer, and in a traced run of each cell
at its tiny size."""
import time

import pytest

import harness
import progspans as P

S = 1e9        # ns per second


class Bench:
    """The harness's spans object, as the readers see it."""

    def __init__(self, records):
        self.records = records


def span(name, i, t0, t1, parent=None, thread="MainThread", **attrs):
    return {"name": name, "id": i, "parent": parent, "thread": thread,
            "t0_ns": t0 * S, "t1_ns": t1 * S, "attrs": attrs}


def event(name, t, value):
    return {"name": name, "t_ns": t * S, "value": value, "labels": {}}


def ctx_for(spans, events, bench=(), saves=(), resumes=(), lo=10.0, hi=100.0):
    return {"t_open": lo, "t_close": hi, "program_spans": list(spans),
            "program_events": list(events), "spans": Bench(list(bench)),
            "saves": list(saves), "resumes": list(resumes)}


def read(metric, ctx):
    return harness.Cell.reader(metric).read(ctx)


def save_ctx():
    # one save 20..30 s (its commit 29..30), a save before the window opens
    spans = [span("repro.ckpt.save", 1, 20.0, 28.0, cpu_user_s=3.0, cpu_sys_s=5.0),
             span("repro.store.stream", 2, 20.5, 27.5, parent=1),
             span("repro.ckpt.commit", 3, 28.0, 28.5),
             span("repro.ckpt.save", 4, 1.0, 3.0, cpu_user_s=9.0, cpu_sys_s=9.0),
             span("repro.ckpt.commit", 5, 3.0, 9.0)]
    events = [event("repro.store.fanout_wait_s", 27.5, 2.5),
              event("repro.store.fanout_wait_s", 3.0, 7.0)]
    return ctx_for(spans, events, saves=[{"stall_s": 8.6, "delta": None}])


def test_save_readers_by_hand():
    ctx = save_ctx()
    assert read("save_fanout_wait_s.full", ctx) == pytest.approx(2.5)
    assert read("save_host_cpu_share.full", ctx) == pytest.approx(100.0)
    assert read("ckpt_commit_s", ctx) == pytest.approx(0.5)
    ctx["saves"] = [{"stall_s": 1.0, "delta": {"hash_s": 1.0}}]
    assert read("save_fanout_wait_s.full", ctx) is None           # a delta save
    assert read("save_host_cpu_share.full", ctx) is None


def resume_ctx():
    spans = [span("repro.ckpt.restore", 1, 20.0, 24.0, cpu_user_s=2.0, cpu_sys_s=4.0),
             span("repro.restore.run", 2, 20.5, 23.5, parent=1),
             span("repro.restore.task", 3, 20.5, 23.5, parent=2, thread="ckpt-restore_0"),
             span("repro.restore.task", 4, 20.5, 22.5, parent=2, thread="ckpt-restore_1"),
             span("repro.ckpt.restore", 5, 40.0, 42.0, cpu_user_s=1.0, cpu_sys_s=1.0)]
    events = [event("repro.restore.pread_s", 23.5, 2.0),
              event("repro.restore.pread_s", 22.5, 1.5),
              # first steps 25..27 and 45..46; a nested trace (26.0..26.4
              # inside 25.9..26.5) counts once; the digest's loads at 28 do not
              event("repro.jit.trace_s", 26.4, 0.4), event("repro.jit.trace_s", 26.5, 0.6),
              event("repro.jit.lower_s", 26.8, 0.2),
              event("repro.jit.cache_load_s", 26.9, 0.1),
              event("repro.jit.cache_load_s", 28.0, 5.0),
              event("repro.jit.cache_misses", 26.95, 1),
              event("repro.jit.trace_s", 45.5, 0.2),
              event("repro.jit.cache_load_s", 45.9, 0.3)]
    bench = [("bench.first_step", 25.0, 27.0), ("bench.first_step", 45.0, 46.0),
             ("bench.first_step", 1.0, 2.0)]
    return ctx_for(spans, events, bench, resumes=[{}, {}])


def test_resume_readers_by_hand():
    ctx = resume_ctx()
    assert read("restore_io_share", ctx) == pytest.approx(100.0 * 3.5 / 5.0)
    assert read("restore_host_cpu_share", ctx) == pytest.approx((150.0 + 100.0) / 2)
    assert read("first_step_trace_lower_s", ctx) == pytest.approx((0.8 + 0.2) / 2)
    assert read("first_step_cache_load_s", ctx) == pytest.approx((0.1 + 0.3) / 2)
    assert read("first_step_cache_misses", ctx) == pytest.approx(0.5)


def test_gc_reader_clips_to_the_window():
    spans = [span("repro.host.gc", 1, 9.5, 10.5), span("repro.host.gc", 2, 50.0, 50.25),
             span("repro.host.gc", 3, 99.0, 101.0, thread="ckpt-fanout-0"),
             span("repro.host.gc", 4, 200.0, 201.0)]
    assert read("host_gc_s.train", ctx_for(spans, [])) == pytest.approx(0.5 + 0.25 + 1.0)
    assert read("host_gc_s.train", ctx_for([span("repro.other", 1, 20, 21)], [])) == 0.0


def test_self_time_less_its_children():
    src = ([span("repro.cr.step_boundary", 1, 0.0, 10.0),
            span("repro.fetch_tree", 2, 0.5, 3.0, parent=1),
            span("repro.ckpt.save", 3, 3.0, 9.0, parent=1),
            span("repro.store.replica_write", 4, 3.0, 9.5, parent=1, thread="ckpt-fanout-0")],
           [])
    sp = P.spans(src, "repro.cr.step_boundary", 0.0, 10.0)[0]
    assert P.self_s(src, sp) == pytest.approx(1.5)


NEW = {"qwen2-0.5b.save-full": ["save_fanout_wait_s.full", "save_host_cpu_share.full",
                                "ckpt_commit_s"],
       "qwen2-0.5b.resume": ["restore_io_share", "restore_host_cpu_share",
                             "first_step_trace_lower_s", "first_step_cache_load_s",
                             "first_step_cache_misses"],
       "qwen2-0.5b.train": ["host_gc_s.train"]}


def test_without_the_programs_spans_readers_find_nothing(monkeypatch):
    from repro.utils import tracing
    monkeypatch.setattr(tracing, "records", lambda: [])
    ctx = resume_ctx()
    del ctx["program_spans"], ctx["program_events"]
    ctx["saves"] = save_ctx()["saves"]
    for names in NEW.values():
        for m in names:
            assert read(m, ctx) is None, m


@pytest.fixture
def tracer_reset():
    from repro.utils import tracing
    yield
    tracing.disable()
    tracing.reset()


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_tiny_run_reports_every_new_metric(cell, tiny_root, tracer_reset):
    def interpret(tr):
        tr.ckpt.device_fp_impl = "pallas_interpret"

    out = harness.run(cell, 2 ** 31 + 11, 1.0, True, t_process=time.perf_counter(),
                      allow_cpu=True, hook=interpret, root=tiny_root)
    assert out["correct"], out["checks"]
    for m in NEW[cell]:
        assert out["metrics"].get(m, {}).get("value") is not None, (m, out["metrics"])
