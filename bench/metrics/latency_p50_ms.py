"""Median latency of all requests sent in the window, send to result; a
failed request counts as missing."""
from bench.stats import percentile


def read(ctx):
    return percentile(ctx.latencies_ms(), 50)
