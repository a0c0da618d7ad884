"""What the metric readers (``metrics/<metric>.py``) share.  Each
returns None where it finds nothing to read, never 0 for a share.

End-to-end readers take the window ``(t0, t_end, seconds)`` and the
frames, each ``(sent or due, submitted, resolved, records, error)`` on
the host clock; per-layer readers take the traced run's reduction
(``trace_reduce.py``), the dispatcher's counters and the stage spans."""

import numpy as np

import cost


def answered(ctx):
    """Frames answered without error."""
    return [f for f in ctx["frames"] if f[4] is None and not np.isnan(f[2])]


def latencies_us(ctx):
    """Each frame of the window: from when it was due (open loop) or
    sent (closed loop) to its ticket's resolution."""
    t0, t_end, _s = ctx["window"]
    return [(f[2] - f[0]) * 1e6 for f in answered(ctx) if t0 <= f[0] < t_end]


def percentile(values, q):
    return float(np.percentile(np.asarray(values), q)) if values else None


def per_launch(stats):
    s0, s1 = stats
    batches = s1["batches"] - s0["batches"]
    return (s1["items"] - s0["items"]) / batches if batches else None


def step_seconds(ctx):
    """Mean device seconds of the fused step per launch: the XLA module
    with the most device time in the traced window."""
    step = ctx["trace"]["step"]
    if not step or not step["launches"]:
        return None
    return step["seconds"] / step["launches"]


def step_device_us(ctx):
    s = step_seconds(ctx)
    return None if s is None else s * 1e6


def idle_share(ctx):
    t = ctx["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def step_roofline(ctx):
    """The least time the launch's records need at the chip's memory
    bandwidth (``cost.py``), over the step's device time, in %."""
    s = step_seconds(ctx)
    records = per_launch(ctx["trace_stats"]) if ctx["trace_stats"] \
        else None
    if s is None or not records:
        return None
    peaks = ctx["peaks"][ctx["device_kind"]]   # unknown device: error
    least = records * cost.bytes_per_record() / peaks["hbm_bytes_per_s"]
    return 100.0 * least / s


def stage_mean_us(ctx, stage, family="serving-verdict"):
    s0, s1 = ctx["stages"]
    a = s0.get(family, {}).get(stage, {"count": 0, "total-s": 0.0})
    b = s1.get(family, {}).get(stage)
    if not b or b["count"] == a["count"]:
        return None
    return 1e6 * (b["total-s"] - a["total-s"]) / (b["count"] - a["count"])
