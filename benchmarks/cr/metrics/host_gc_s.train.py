"""Seconds of Python garbage collection in the window, on any thread: the
program's ``repro.host.gc`` spans, clipped to the window."""
import progspans as P


def read(ctx):
    src = P.source(ctx)
    if src is None:
        return None
    lo, hi = P.window(ctx)
    return sum(max(0.0, min(r["t1_ns"] / 1e9, hi) - max(r["t0_ns"] / 1e9, lo))
               for r in src[0] if r["name"] == "repro.host.gc")
