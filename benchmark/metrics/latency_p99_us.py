"""latency_p99_us: the 99th percentile over every frame of the window
(PERF.md §2)."""
from readers import latencies_us, percentile


def read(ctx):
    return percentile(latencies_us(ctx), 99)
