"""Seconds the full save's serializer blocked handing chunks to the
replica writer threads (a full queue: the disks' share of the stream): the
program's ``repro.store.fanout_wait_s`` counter, per save in the window."""
import progspans as P


def read(ctx):
    src = P.source(ctx)
    if src is None or not ctx["saves"] or ctx["saves"][0].get("delta"):
        return None
    got = P.events(src, "repro.store.fanout_wait_s", [P.window(ctx)])
    return sum(v for _, v in got) / len(ctx["saves"]) if got else None
