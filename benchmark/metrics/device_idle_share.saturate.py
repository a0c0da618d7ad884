"""device_idle_share.saturate: see PERF.md §3."""
from readers import idle_share


def read(ctx):
    return idle_share(ctx)
