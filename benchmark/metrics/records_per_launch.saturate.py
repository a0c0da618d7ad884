"""records_per_launch.saturate: see PERF.md §3."""
from readers import per_launch


def read(ctx):
    return per_launch(ctx["stats"])
