"""Seconds of ``CheckpointManager.restore`` (restore engine and store reads,
CRC checks) per resume in the window: the ``bench.ckpt_restore`` span."""


def read(ctx):
    d = ctx["spans"].durations("bench.ckpt_restore", ctx["t_open"], ctx["t_close"])
    return sum(d) / len(d) if d else None
