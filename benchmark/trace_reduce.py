"""From a profiler trace to the numbers the per-layer readers take:
device busy time, per-module device time, the top device operations,
and the idle gaps labelled by the benchmark's own host spans.

A module is a jitted function: its programs (buckets, variants) are
summed under the name before their fingerprint, and the fused step is
the one with the most device time.  The window is the host span ``bench.window`` that ``run.py`` opens
around the traced part of a run.  A device is a plane named
``/device:<kind>:<n>`` other than the CPU's; its busy time is the union
of its ``XLA Ops`` events (its ``XLA Modules`` events where it has no
op line) inside the window.
"""

from __future__ import annotations

import glob
import os

OPS, MODULES = "XLA Ops", "XLA Modules"
NAME = 120   # an op's name is its whole HLO line: keep its head


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def reduce_planes(planes, top=10):
    """``planes``: iterable of (plane name, [(line name, [(event name,
    start_ns, duration_ns)])]).  Returns the reduction dict."""
    host_spans = []
    devices = {}
    window = None
    for pname, lines in planes:
        is_device = pname.startswith("/device:") and \
            not pname.startswith("/device:CPU")
        for lname, events in lines:
            for name, start, dur in events:
                if not is_device:
                    if name == "bench.window":
                        window = (start, start + dur)
                    elif name.startswith("bench."):
                        host_spans.append((start, start + dur, name))
                    continue
                devices.setdefault(pname, {}).setdefault(
                    lname, []).append((name, start, start + dur))
    if window is None:
        raise ValueError("the trace has no bench.window span")
    lo, hi = window
    busy_total = 0.0
    used = 0
    modules, ops, gaps = {}, {}, []
    for pname, lines in devices.items():
        src = lines.get(OPS) or lines.get(MODULES) or []
        busy = _union(_clip([(a, b) for _n, a, b in src], lo, hi))
        if not busy:
            continue
        used += 1
        busy_total += sum(b - a for a, b in busy) / 1e9
        for name, a, b in lines.get(MODULES, []):
            if lo <= a < hi:
                # one jitted function's programs (its buckets and
                # variants) share the name before the fingerprint
                name = name.split("(")[0]
                c, t = modules.get(name, (0, 0.0))
                modules[name] = (c + 1, t + (b - a) / 1e9)
        for name, a, b in lines.get(OPS, []):
            if lo <= a < hi:
                ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    if not used:
        raise ValueError("no device operation ran in the traced window")

    def label(a, b):
        best, cover = "none", 0
        for s, e, name in host_spans:
            ov = min(b, e) - max(a, s)
            if ov > cover:
                best, cover = name, ov
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    step = max(modules.items(), key=lambda kv: kv[1][1]) if modules \
        else None
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / used,
        "devices": used,
        "modules": modules,
        "step": None if step is None else
        {"name": step[0], "launches": step[1][0], "seconds": step[1][1]},
        "breakdown": {
            "device_ops": [[n[:NAME], s] for n, s in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[label(a, b), (b - a) / 1e9]
                          for a, b in gaps[:top]]},
    }


def read_xplane(path):
    """(plane, [(line, [(event, start_ns, duration_ns)])]) from one
    ``.xplane.pb`` file, through JAX's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                 for e in ln.events])
                      for ln in p.lines])
            for p in pd.planes]


def reduce_dir(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    return reduce_planes(read_xplane(sorted(files)[-1]))
