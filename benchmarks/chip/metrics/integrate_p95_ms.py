"""integrate_p95_ms: the 95th percentile of the latency of every call
in the window, dispatch to `block_until_ready`."""
from harness import quantile


def read(ctx):
    return 1e3 * quantile(ctx["window"]["latencies_s"], 95)
