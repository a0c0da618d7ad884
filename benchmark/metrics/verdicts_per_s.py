"""verdicts_per_s: records whose ticket resolved inside the window, over
the window (PERF.md §2)."""
from readers import answered


def read(ctx):
    t0, t_end, seconds = ctx["window"]
    return sum(f[3] for f in answered(ctx)
               if t0 <= f[1] and f[2] <= t_end) / seconds
