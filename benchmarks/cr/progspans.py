"""The program's own spans and counters, read inside the window.

The program keeps one tracer (``src/repro/utils/tracing.py``).  It records
while a ``jax.profiler`` trace is being collected, so a ``--trace 1`` run
holds the program's ``repro.*`` spans and counter increments of the window,
on ``time.perf_counter``'s clock, as the harness's ``t_open`` and
``t_close`` are.  A context may bring them itself as ``program_spans`` and
``program_events`` (the tracer's ``records()`` and ``events()``); otherwise
they are taken from the tracer in this process.  Where the program has no
tracer, or it recorded nothing, ``source`` returns None and so does every
reader built on it.
"""
from __future__ import annotations


def source(ctx):
    """``(spans, events)`` of the program, or None."""
    if "program_spans" in ctx:
        return ctx["program_spans"], ctx.get("program_events", [])
    try:
        from repro.utils import tracing
    except ImportError:
        return None
    spans = tracing.records()
    return (spans, tracing.events()) if spans else None


def window(ctx) -> tuple:
    return ctx["t_open"], ctx["t_close"]


def spans(src, name: str, lo: float, hi: float) -> list:
    """Spans named ``name`` that lie inside [lo, hi] (seconds), with their
    start, end and duration in seconds added as ``t0``, ``t1``, ``s``."""
    out = []
    for r in src[0]:
        if r["name"] != name:
            continue
        t0, t1 = r["t0_ns"] / 1e9, r["t1_ns"] / 1e9
        if t0 >= lo and t1 <= hi:
            out.append({**r, "t0": t0, "t1": t1, "s": t1 - t0})
    return out


def events(src, name: str, intervals) -> list:
    """Values of the increments of counter ``name`` that fall inside any of
    ``intervals`` ([(lo, hi)] in seconds), with the time each was taken."""
    out = []
    for e in src[1]:
        if e["name"] != name:
            continue
        t = e["t_ns"] / 1e9
        if any(lo <= t <= hi for lo, hi in intervals):
            out.append((t, e["value"]))
    return out


def union_s(intervals) -> float:
    """Seconds covered by the union of [(start, end)] intervals."""
    total, cur = 0.0, None
    for a, b in sorted(intervals):
        if cur is None or a > cur[1]:
            if cur is not None:
                total += cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    if cur is not None:
        total += cur[1] - cur[0]
    return total


def self_s(src, span: dict) -> float:
    """A span's seconds less those its children on its own thread cover."""
    kids = [(r["t0_ns"] / 1e9, r["t1_ns"] / 1e9) for r in src[0]
            if r["parent"] == span["id"] and r["thread"] == span["thread"]]
    return span["s"] - union_s(kids)


def cpu_share(sp: dict) -> float:
    """Process CPU seconds (user and system, every thread) over the span's
    wall seconds, in % (one busy core reads 100)."""
    a = sp["attrs"]
    return 100.0 * (a["cpu_user_s"] + a["cpu_sys_s"]) / sp["s"]


def bench_intervals(ctx, name: str) -> list:
    """The harness's own spans named ``name`` inside the window, as
    [(start, end)] in seconds."""
    lo, hi = window(ctx)
    return [(t0, t1) for n, t0, t1 in ctx["spans"].records
            if n == name and t0 >= lo and t1 <= hi]


def per_first_step(ctx, counter: str):
    """Sum of counter ``counter`` inside each resume's first step (the
    harness's ``bench.first_step`` span), averaged over the resumes."""
    src = source(ctx)
    steps = bench_intervals(ctx, "bench.first_step")
    if src is None or not steps:
        return None
    return sum(v for _, v in events(src, counter, steps)) / len(steps)
