"""The process's CPU seconds (user and system, all threads) over the wall
seconds of ``CheckpointManager.restore``, in % (one busy core reads 100):
the program's ``repro.ckpt.restore`` span, averaged over the window's
resumes."""
import progspans as P


def read(ctx):
    src = P.source(ctx)
    if src is None or not ctx["resumes"]:
        return None
    got = P.spans(src, "repro.ckpt.restore", *P.window(ctx))
    return sum(P.cpu_share(s) for s in got) / len(got) if got else None
