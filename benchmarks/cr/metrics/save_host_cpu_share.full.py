"""The process's CPU seconds (user and system, all threads) over the wall
seconds of ``CheckpointManager.save`` in a full save, in % (one busy core
reads 100): the program's ``repro.ckpt.save`` span, averaged over the
window's saves."""
import progspans as P


def read(ctx):
    src = P.source(ctx)
    if src is None or not ctx["saves"] or ctx["saves"][0].get("delta"):
        return None
    got = P.spans(src, "repro.ckpt.save", *P.window(ctx))
    return sum(P.cpu_share(s) for s in got) / len(got) if got else None
