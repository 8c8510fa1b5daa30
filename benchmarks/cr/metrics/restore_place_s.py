"""Seconds of ``core/virtualization.place_tree`` (host-to-device placement
of the restored state) per resume in the window: the ``bench.place_tree``
span."""


def read(ctx):
    d = ctx["spans"].durations("bench.place_tree", ctx["t_open"], ctx["t_close"])
    return sum(d) / len(d) if d else None
