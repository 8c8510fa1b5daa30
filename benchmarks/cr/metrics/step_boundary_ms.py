"""Milliseconds of ``CRManager.step_boundary`` with no save due (signal,
coordinator and interval checks): the mean ``bench.step_boundary`` span
over the window's steps."""


def read(ctx):
    if ctx["saves"]:
        return None
    d = ctx["spans"].durations("bench.step_boundary", ctx["t_open"], ctx["t_close"])
    return 1000.0 * sum(d) / len(d) if d else None
