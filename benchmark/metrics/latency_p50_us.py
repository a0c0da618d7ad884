"""latency_p50_us: the median over every frame of the window (PERF.md §2)."""
from readers import latencies_us, percentile


def read(ctx):
    return percentile(latencies_us(ctx), 50)
