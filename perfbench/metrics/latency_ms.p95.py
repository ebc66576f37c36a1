"""latency_ms.p95: 95th percentile over every request of the window, from
the instant it was due to be sent to the poll that returned its answer. One
stall of the host or of the TPU runtime moves it by a third or more in a
window of some 200 requests, so it stands beside ``latency_p50_ms``."""
from perfbench.stats import percentile


def read(run):
    return percentile(run.latencies_ms, 95)
