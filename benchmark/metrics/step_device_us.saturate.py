"""step_device_us.saturate: see PERF.md §3."""
from readers import step_device_us


def read(ctx):
    return step_device_us(ctx)
