"""Share of the window in which no operation ran on the device, from the
trace (1 - busy union / window), in %."""


def read(ctx):
    share = ctx["reduced"]["idle_share"]
    return None if share is None else 100.0 * share
