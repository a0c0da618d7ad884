"""fused_step_roofline: see PERF.md §3."""
from readers import step_roofline


def read(ctx):
    return step_roofline(ctx)
