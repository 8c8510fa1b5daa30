"""Seconds of ``CheckpointManager.commit`` (wait for the writes, read the
worker parts, write the manifest, GC): the program's ``repro.ckpt.commit``
span, averaged over the window's saves."""
import progspans as P


def read(ctx):
    src = P.source(ctx)
    if src is None or not ctx["saves"]:
        return None
    got = P.spans(src, "repro.ckpt.commit", *P.window(ctx))
    return sum(s["s"] for s in got) / len(got) if got else None
