"""generator_lag_ms.p95: how late the open loop submitted its requests: the
submit instant minus the due instant, 95th percentile."""
from perfbench.stats import percentile


def read(run):
    return percentile(run.lags_ms, 95)
