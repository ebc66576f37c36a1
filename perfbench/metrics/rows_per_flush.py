"""rows_per_flush: real (not sentinel) rows per admission-queue flush, the
mean of FlushRecord.n_real."""
from perfbench.stats import mean


def read(run):
    return mean(run.flush_rows)
