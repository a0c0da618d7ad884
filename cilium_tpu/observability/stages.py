"""Host-timed pipeline stage slices and blocking boundaries.

The fused jitted step is opaque from the host, but the host-side
pipeline around it is where stalls actually surface: waiting on the
engine lock, building/padding the batch, the (async) dispatch call,
and the device->host sync that blocks on real compute.  Each slice is
timed where it runs — engine ``process()``/``process6()``, the verdict
service's drain/pack/dispatch/sync loop — into one labeled histogram
plus a cheap running summary served by ``pipeline_report()`` and
``/debug/pipeline`` (the Taurus stage-level-timing discipline: built
in, not bolted on).

``stage(family, name, **meta)`` times a slice the same way and also
opens a ``jax.profiler.TraceAnnotation`` named ``<family>.<name>``
around it, so a profile shows the host slice on the same clock as the
device ops it launched; ``meta`` (e.g. ``launch=n``) rides along as the
event's stats.  With the profiler off the annotation costs well under
a microsecond; callers with telemetry off pass :data:`NO_SPAN` instead.

Garbage collections are timed too, by generation (family ``runtime``,
stages ``gc-gen0/1/2``), from a ``gc.callbacks`` hook.  A collection can
start while any thread holds this module's lock, so the hook never
takes it: it adds into per-generation totals of its own that
``pipeline_report()`` merges in.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Dict

from ..utils.metrics import registry

PIPELINE_STAGE_SECONDS = registry.histogram(
    "pipeline_stage_seconds",
    "Host-observed pipeline stage slices by family and stage "
    "(lock-wait, dispatch, sync, ...)",
    buckets=(1e-6, 1e-5, 1e-4, 5e-4, 1e-3, 5e-3, .01, .05, .1, .5,
             1, 5))


def _summary(count: int, total: float, lo: float, hi: float) -> Dict:
    return {"count": count,
            "total-s": round(total, 6),
            "mean-us": round(total / count * 1e6, 2) if count else 0.0,
            "min-us": round(lo * 1e6, 2) if count else 0.0,
            "max-us": round(hi * 1e6, 2)}


class _StageStat:
    __slots__ = ("count", "total", "min", "max")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total += seconds
        if seconds < self.min:
            self.min = seconds
        if seconds > self.max:
            self.max = seconds

    def to_dict(self) -> Dict:
        return _summary(self.count, self.total, self.min, self.max)


_lock = threading.Lock()
_stats: Dict[str, Dict[str, _StageStat]] = {}

# blocking boundaries: stages whose wall time is device compute the
# host waited out, not host work — pipeline_report flags them so an
# operator reads "sync is 90% of the budget" as device-bound, not as
# a host regression.  "complete" is the serving dispatcher's ticket
# resolution (datapath/serving.py) — the ONE whitelisted sync on the
# latency-tier path, always one batch behind the launch front — and
# "d2h" the device->host copy inside it.
BLOCKING_STAGES = frozenset({"sync", "block", "device-sync",
                             "complete", "d2h"})


def record_stage(family: str, stage: str, seconds: float) -> None:
    """Account one stage slice (hot path: one dict walk + histogram
    observe)."""
    PIPELINE_STAGE_SECONDS.observe(
        seconds, labels={"family": family, "stage": stage})
    with _lock:
        fam = _stats.get(family)
        if fam is None:
            fam = _stats[family] = {}
        st = fam.get(stage)
        if st is None:
            st = fam[stage] = _StageStat()
        st.add(seconds)


# jax.profiler.TraceAnnotation, resolved on first use: this package
# stays importable without JAX (kvstore and proxy processes load it)
_annotation = None

# what callers with telemetry off enter instead of a span
NO_SPAN = contextlib.nullcontext()


class _Span:
    __slots__ = ("family", "name", "meta", "_note", "_t0")

    def __init__(self, family: str, name: str, meta: Dict):
        self.family = family
        self.name = name
        self.meta = meta

    def __enter__(self):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        # a TraceAnnotation starts when it is made: make it here
        self._note = _annotation(f"{self.family}.{self.name}",
                                 **self.meta)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self._t0
        self._note.__exit__(None, None, None)
        record_stage(self.family, self.name, seconds)
        return False


def stage(family: str, name: str, **meta) -> _Span:
    """``with stage("serving-verdict", "pack", launch=n): ...`` — one
    slice, recorded as :func:`record_stage` records it and annotated on
    the profiler's timeline as ``<family>.<name>`` with ``meta``."""
    return _Span(family, name, meta)


# ------------------------------------------------------------------ gc

_GC_STAGES = ("gc-gen0", "gc-gen1", "gc-gen2")
_GC_NOTES = tuple(f"runtime.{s}" for s in _GC_STAGES)
_gc_count = [0, 0, 0]
_gc_total = [0.0, 0.0, 0.0]
_gc_min = [float("inf")] * 3
_gc_max = [0.0, 0.0, 0.0]
# the open collection: [start stamp, its annotation or None]; the
# interpreter runs one collection at a time
_gc_open = [0.0, None]


def _on_gc(phase: str, info: Dict) -> None:
    """``gc.callbacks`` hook.  Takes no lock and calls nothing that can
    wait on one; objects it makes cannot start a nested collection (the
    interpreter runs callbacks inside the collection)."""
    g = info["generation"]
    if phase == "start":
        ann = _annotation
        if ann is not None and ann.is_enabled():
            _gc_open[1] = ann(_GC_NOTES[g])
        _gc_open[0] = time.perf_counter()
        return
    seconds = time.perf_counter() - _gc_open[0]
    note = _gc_open[1]
    if note is not None:
        _gc_open[1] = None
        note.__exit__(None, None, None)
    _gc_count[g] += 1
    _gc_total[g] += seconds
    if seconds < _gc_min[g]:
        _gc_min[g] = seconds
    if seconds > _gc_max[g]:
        _gc_max[g] = seconds


gc.callbacks.append(_on_gc)


def pipeline_report() -> Dict:
    """Per-family stage breakdown with share-of-family percentages."""
    with _lock:
        snap = {fam: {stage: st.to_dict()
                      for stage, st in stages.items()}
                for fam, stages in _stats.items()}
    snap["runtime"] = {
        name: _summary(_gc_count[g], _gc_total[g], _gc_min[g],
                       _gc_max[g])
        for g, name in enumerate(_GC_STAGES)}
    for fam, stages in snap.items():
        fam_total = sum(s["total-s"] for s in stages.values()) or 1.0
        for stage, s in stages.items():
            s["share-pct"] = round(s["total-s"] / fam_total * 100, 2)
            s["blocking-boundary"] = stage in BLOCKING_STAGES
    return snap


def reset() -> None:
    with _lock:
        _stats.clear()
    for g in range(3):
        _gc_count[g] = 0
        _gc_total[g] = 0.0
        _gc_min[g] = float("inf")
        _gc_max[g] = 0.0
