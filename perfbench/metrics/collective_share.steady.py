"""collective_share.steady: the share of device busy time, mean over the
cell's chips, spent in cross-chip collective ops (the k-merge's
all-gather). A collective lasts until its slowest peer arrives, so this
reads the merge's own cost and the imbalance between shards together."""


def read(run):
    t = run.trace
    if t is None or t.collective_s is None or t.busy_s <= 0:
        return None
    return 100.0 * t.collective_s / t.busy_s
