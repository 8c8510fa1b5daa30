"""Seconds of the device-to-host fetch of the state in a full save: the
``bench.fetch_tree`` span around ``core/virtualization.fetch_tree``,
averaged over the window's saves."""


def read(ctx):
    d = ctx["spans"].durations("bench.fetch_tree", ctx["t_open"], ctx["t_close"])
    return sum(d) / len(d) if d else None
