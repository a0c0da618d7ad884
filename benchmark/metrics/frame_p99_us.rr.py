"""frame_p99_us.rr: the 99th percentile over every frame of the window,
as ``latency_p99_us`` reads it, in a cell where that tail is too
unsteady to bound end to end (PERF.md §2, §3)."""
from readers import latencies_us, percentile


def read(ctx):
    return percentile(latencies_us(ctx), 99)
