"""99th percentile of the latencies of all requests sent in the window."""
from bench.stats import percentile


def read(ctx):
    return percentile(ctx.latencies_ms(), 99)
