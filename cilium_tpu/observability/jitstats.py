"""JIT/compile telemetry: every compile JAX reports, by jitted function.

XLA compiles lazily: ``jax.jit`` returns instantly and the first call
per (program, input geometry) pays tracing + compilation synchronously
before dispatch.  The engine rebuilds its jitted steps on every
geometry change, so "how much wall time does this agent spend
compiling, and how often does a batch hit a cold program?" is a real
operational question (the Taurus lesson: stage-level timing must be
built into the pipeline, not bolted on).

The count comes from JAX itself: each ``/jax/core/compile/
backend_compile_duration`` event (one per program compiled, or loaded
from the persistent compile cache) counts under the jitted function's
name and is also recorded as the stage slice ``jit.compile``;
``/jax/compilation_cache/cache_hits`` counts the loads.  Nothing runs
per dispatch.  Live device bytes are a gauge fed by the table owners
(engine rebuilds, the DeviceTableManager).
"""

from __future__ import annotations

import threading
from typing import Dict

from ..utils.metrics import registry
from .stages import record_stage

COMPILE_COUNT = registry.counter(
    "jit_compile_total",
    "Jitted-program compilations (first call per program x geometry) "
    "by entry point")
COMPILE_SECONDS = registry.histogram(
    "jit_compile_seconds",
    "Wall time of compiles (lowering + XLA compile or persistent-cache "
    "load) by entry point",
    buckets=(.01, .05, .1, .25, .5, 1, 2.5, 5, 10, 30, 60, 120))
JIT_CACHE_EVENTS = registry.counter(
    "jit_cache_events_total",
    "Compiles by outcome: a persistent compile-cache hit, or a miss "
    "that ran the XLA compiler")
DEVICE_BYTES = registry.gauge(
    "device_table_bytes",
    "Live device-resident table bytes by owner")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class JitTelemetry:
    """Process-wide compile accounting, fed by JAX's monitoring events
    once :meth:`attach` has registered the listeners."""

    def __init__(self):
        self.enabled = True
        self._lock = threading.Lock()
        self._attached = False
        self._compiles: Dict[str, int] = {}
        self._compile_seconds: Dict[str, float] = {}
        self._hits = 0
        # a cache-hit event arrives inside the compile it belongs to,
        # on the compiling thread
        self._tls = threading.local()

    def attach(self) -> None:
        """Listen to JAX's compile events (idempotent)."""
        with self._lock:
            if self._attached:
                return
            self._attached = True
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self.on_duration)
        mon.register_event_listener(self.on_event)

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        if event != COMPILE_EVENT or not self.enabled:
            return
        entry = str(kw.get("fun_name", "unknown"))
        hit = getattr(self._tls, "hit", False)
        self._tls.hit = False
        with self._lock:
            self._compiles[entry] = self._compiles.get(entry, 0) + 1
            self._compile_seconds[entry] = \
                self._compile_seconds.get(entry, 0.0) + seconds
            self._hits += hit
        COMPILE_COUNT.inc(labels={"entry": entry})
        COMPILE_SECONDS.observe(seconds, labels={"entry": entry})
        JIT_CACHE_EVENTS.inc(labels={"event": "hit" if hit else "miss"})
        record_stage("jit", "compile", seconds)

    def on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._tls.hit = True

    def set_device_bytes(self, owner: str, nbytes: int) -> None:
        if self.enabled:
            DEVICE_BYTES.set(float(nbytes), labels={"owner": owner})

    def report(self) -> Dict:
        with self._lock:
            compiles = sum(self._compiles.values())
            out = {
                "compiles": dict(self._compiles),
                "compile-seconds": {k: round(v, 6) for k, v in
                                    self._compile_seconds.items()},
                "cache-hits": self._hits,
                "cache-misses": compiles - self._hits,
            }
        with DEVICE_BYTES._lock:
            per_owner = {"/".join(v for _k, v in key): val
                         for key, val in DEVICE_BYTES._values.items()}
        out["device-bytes"] = per_owner
        out["device-bytes-total"] = sum(per_owner.values())
        return out

    def reset(self) -> None:
        with self._lock:
            self._compiles.clear()
            self._compile_seconds.clear()
            self._hits = 0


jit_telemetry = JitTelemetry()
