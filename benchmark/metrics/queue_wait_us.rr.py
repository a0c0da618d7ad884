"""queue_wait_us.rr: see PERF.md §3."""
from readers import stage_mean_us


def read(ctx):
    return stage_mean_us(ctx, "queue-wait")
