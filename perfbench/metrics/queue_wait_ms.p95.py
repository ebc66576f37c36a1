"""queue_wait_ms.p95: Completion.wait_ms, from admission to the start of the
flush that served the request, 95th percentile."""
from perfbench.stats import percentile


def read(run):
    return percentile(run.waits_ms, 95)
