"""Seconds of chunk hashing and chunk writes of a delta save: the
manager's ``hash_s`` plus ``write_s`` counters in ``part["delta"]``,
averaged over the window's saves."""


def read(ctx):
    d = [s["delta"]["hash_s"] + s["delta"]["write_s"] for s in ctx["saves"] if s.get("delta")]
    return sum(d) / len(d) if d else None
