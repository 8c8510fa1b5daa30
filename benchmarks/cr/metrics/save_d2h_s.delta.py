"""Seconds of the ranged device-to-host reads of a device-fingerprinted
delta save: the manager's own ``d2h_s`` counter in ``part["delta"]``,
averaged over the window's saves."""


def read(ctx):
    d = [s["delta"]["d2h_s"] for s in ctx["saves"] if s.get("delta")]
    return sum(d) / len(d) if d else None
