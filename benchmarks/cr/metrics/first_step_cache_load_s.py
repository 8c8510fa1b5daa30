"""Seconds spent loading compiled programs from the persistent compile
cache inside each resume's first step, per resume: the program's
``repro.jit.cache_load_s`` counter inside the ``bench.first_step`` span."""
import progspans as P


def read(ctx):
    return P.per_first_step(ctx, "repro.jit.cache_load_s")
