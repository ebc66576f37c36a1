"""latency_p50_ms: median over every request of the window, from the
instant it was due to be sent to the poll that returned its answer."""
from perfbench.stats import percentile


def read(run):
    return percentile(run.latencies_ms, 50)
