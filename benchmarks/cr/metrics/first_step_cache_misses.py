"""Programs compiled and written to the persistent compile cache inside
each resume's first step (each a compile the cache did not spare), per
resume: the program's ``repro.jit.cache_misses`` counter inside the
``bench.first_step`` span."""
import progspans as P


def read(ctx):
    return P.per_first_step(ctx, "repro.jit.cache_misses")
