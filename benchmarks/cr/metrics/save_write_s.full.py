"""Seconds of ``CheckpointManager.save`` on the fetched host tree in a full
save (v2 shard serialization, CRC and the store's write): the
``bench.ckpt_save`` span, averaged over the window's saves."""


def read(ctx):
    if not ctx["saves"] or ctx["saves"][0].get("delta"):
        return None
    d = ctx["spans"].durations("bench.ckpt_save", ctx["t_open"], ctx["t_close"])
    return sum(d) / len(d) if d else None
