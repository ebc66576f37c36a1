"""flush_ms.p50: host clock around each search_batch a flush makes, which
ends in block_until_ready, median."""
from perfbench.stats import percentile


def read(run):
    return percentile(run.search_ms, 50)
