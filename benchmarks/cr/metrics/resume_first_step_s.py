"""Seconds of the first step on restored state, from the call into the
jitted step (traced again, loaded from the persistent compile cache) until
its loss is on the host: the ``bench.first_step`` span, per resume."""


def read(ctx):
    d = ctx["spans"].durations("bench.first_step", ctx["t_open"], ctx["t_close"])
    return sum(d) / len(d) if d else None
