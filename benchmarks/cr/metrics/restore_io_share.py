"""Share of the restore pool's task thread-seconds spent inside the store's
reads (``pread``/``pread_batch``), in %; the rest is CRC checks and copies:
the program's ``repro.restore.pread_s`` counter over its
``repro.restore.task`` spans in the window."""
import progspans as P


def read(ctx):
    src = P.source(ctx)
    if src is None or not ctx["resumes"]:
        return None
    tasks = P.spans(src, "repro.restore.task", *P.window(ctx))
    task_s = sum(s["s"] for s in tasks)
    if task_s <= 0:
        return None
    io = P.events(src, "repro.restore.pread_s", [P.window(ctx)])
    return 100.0 * sum(v for _, v in io) / task_s
