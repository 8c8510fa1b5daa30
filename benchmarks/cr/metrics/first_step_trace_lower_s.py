"""Seconds JAX spent tracing and lowering inside each resume's first step,
per resume: the program's ``repro.jit.trace_s`` and ``repro.jit.lower_s``
counters inside the ``bench.first_step`` span, nested traces counted
once."""
import progspans as P


def read(ctx):
    src = P.source(ctx)
    steps = P.bench_intervals(ctx, "bench.first_step")
    if src is None or not steps:
        return None
    busy = [(t - v, t) for name in ("repro.jit.trace_s", "repro.jit.lower_s")
            for t, v in P.events(src, name, steps)]
    return P.union_s(busy) / len(steps)
