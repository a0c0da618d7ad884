"""The latency-tier serving path: continuous micro-batching with an
async, double-buffered dispatch core.

BENCH_FULL_20260804_143713 made the problem concrete: the jitted
pipeline is throughput-shaped only — device round-trip p99 at batch
256 was 2.46 ms while the host verdict cache answers in 21 µs, because
every caller paid a synchronous pack -> H2D -> compute -> D2H round
trip per dispatch, serialized on the engine lock.  This module is the
fix, the hXDP argument applied to the verdict engine: hide per-packet
latency by keeping the pipeline full instead of waiting out each
dispatch.

Three mechanisms, one dispatcher thread:

* **Continuous micro-batching** — every submitter (verdict-service
  connections, L7 proxies, direct engine callers) enqueues frames into
  one shared :class:`VerdictDispatcher`; concurrent endpoints coalesce
  into ONE device launch instead of serializing pack+dispatch+sync on
  the engine lock.  Tickets preserve per-submitter ordering and map
  results back to exactly the submitted frames.
* **Async double-buffered dispatch** — JAX dispatch is asynchronous,
  so the dispatcher launches batch N and immediately packs batch N+1
  while N's device walk runs; the device->host sync happens once per
  batch in the *complete* stage, one batch behind the launch front.
  Up to ``depth`` batches stay in flight (the l7/http.py
  ``check_pipelined`` pattern, promoted to the verdict engine).
* **Persistent packed staging** — packing writes into preallocated
  per-bucket [10, rows] field matrices (rotated ``depth+1`` deep so an
  in-flight batch never shares memory with the one being packed; the
  CPU backend zero-copies host arrays), dispatched through
  ``Datapath.process_packed`` as ONE host->device transfer per batch
  instead of ten per-field uploads; steady-state dispatch does no
  per-batch allocation, and the table state is already device-resident
  (CT/counters are donated through the jitted step).

Failure semantics extend ``l7/parser.VerdictBatcher``'s guarantee to
the shared tier: a dispatch (or completion) that raises fails closed —
every frame in exactly that batch resolves to a deny verdict with the
error attached to its ticket; other batches are untouched.  With a
``DeviceSupervisor`` attached (datapath/supervisor.py), device faults
degrade further instead: the batch is served **fail-static from the
host oracle** (established flows keep their verdicts, new flows get
the configured degraded-mode policy) and the breaker-gated recovery
path brings the device lane back — the survivable-serving tier.

Overload protection (admission control): the pending queue is
weight-bounded (``max_pending``); work that would overflow it is shed
fail-closed at submit time, tickets may carry a deadline and expire
unserved work is shed at drain time — both with distinct
``serving_shed_total{reason}`` accounting — and a hysteresis watermark
pair flips the ``dataplane_overloaded`` gauge so callers
(verdict_service, VerdictBatcher) push back instead of queuing.

Sync-point discipline: the ONLY device synchronization on this path is
the ticket-completion transfer in ``_finalize`` (flagged as a blocking
boundary in ``pipeline_stage_seconds{stage="complete"}``); the lint in
tests/test_sync_lint.py holds the hot modules to that.

Telemetry (``observability/stages.py``), per launch: ``queue-wait``
(the oldest frame's wait), ``dispatch`` (with ``pack`` inside it),
``complete`` and, inside it, ``handoff`` (the two thread hops to and
from the supervisor's watchdog worker), ``d2h`` (the device->host copy,
on that worker) and ``resolve`` (tickets, their callbacks, SLO and
verdict-outcome accounting).  Each span carries the launch number
(``launch=n``, the lane's ``batches`` count), as does every ticket of
the launch (``Ticket.launch``).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..observability.events import (EVENT_SERVING_OVERLOAD,
                                    recorder as flight_recorder)
from ..observability.slo import slo_tracker
from ..observability.stages import NO_SPAN, record_stage
from ..observability.stages import stage as span
from ..utils.bucketing import bucket_size
from ..utils.metrics import (DATAPLANE_OVERLOADED, count_policy_verdicts,
                             registry)
from .events import DROP_POLICY
# the packed staging row order, unpacked by full_datapath_step_packed
# inside the fused program; the names also match the
# PacketRing.pop_batch SoA dict keys
from .pipeline import PACKED_FIELDS

SERVING_BATCHES = registry.counter(
    "serving_batches_total",
    "Device launches issued by the continuous micro-batching "
    "dispatcher, by lane")
SERVING_FRAMES = registry.counter(
    "serving_frames_total",
    "Frames (submissions) coalesced through the serving dispatcher, "
    "by lane")
SERVING_SHED = registry.counter(
    "serving_shed_total",
    "Frames shed fail-closed by serving admission control, by lane "
    "and reason (overflow / deadline / closed)")


class ShedError(RuntimeError):
    """The frame was shed by admission control (queue overflow or an
    expired ticket deadline) — fail-closed, never dispatched."""

    def __init__(self, reason: str):
        super().__init__(f"shed by admission control: {reason}")
        self.reason = reason


class Ticket:
    """One submission's future: resolved by the dispatcher thread with
    the per-frame results (or, on a failed batch, the fail-closed deny
    results plus the error that caused them)."""

    __slots__ = ("_event", "value", "error", "submitted_at",
                 "deadline", "launch", "_callbacks", "_cb_lock")

    def __init__(self, deadline: Optional[float] = None):
        self._event = threading.Event()
        self.value = None
        self.error: Optional[BaseException] = None
        self.submitted_at = time.perf_counter()
        # the launch that answered it (the lane's batch number; None
        # when it never reached the device)
        self.launch: Optional[int] = None
        # absolute monotonic deadline: unserved work older than this
        # is shed at drain time (admission control), never dispatched
        self.deadline = None if deadline is None else \
            time.monotonic() + deadline
        self._callbacks: List[Callable] = []
        self._cb_lock = threading.Lock()

    def resolve(self, value, error: Optional[BaseException] = None
                ) -> None:
        self.value = value
        self.error = error
        # set-then-drain under the callback lock: a concurrent
        # add_done_callback either sees the event and runs its
        # callback itself, or lands in the list we drain here —
        # never neither
        with self._cb_lock:
            self._event.set()
            cbs, self._callbacks = self._callbacks, []
        for cb in cbs:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — a bad callback must
                pass           # not poison the dispatcher thread

    def add_done_callback(self, cb: Callable) -> None:
        """Run ``cb(ticket)`` on resolution (immediately if already
        resolved) — the asyncio bridge used by VerdictBatcher."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(cb)
                return
        cb(self)

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        """Block until resolved.  Fail-closed contract: a failed batch
        still RETURNS (the deny results) — callers that must
        distinguish inspect ``.error`` afterwards."""
        if not self._event.wait(timeout):
            raise TimeoutError("serving ticket not resolved in time")
        return self.value


class ContinuousDispatcher:
    """Generic continuous micro-batching core (one dispatcher thread).

    ``launch(items, total)`` must dispatch the batch WITHOUT device
    synchronization and return an in-flight handle; ``finalize(handle,
    weights)`` performs the one blocking transfer and returns one
    result per item.  ``deny(item)`` builds the fail-closed result for
    one item.  ``weight(item)`` sizes items against ``max_batch``.

    The loop keeps up to ``depth`` launches in flight: while batch N
    computes on device, batch N+1 is drained+packed+launched — the
    double buffer.  Completion happens one batch behind the launch
    front, so the steady-state dispatch loop never blocks on device
    compute between launches.
    """

    def __init__(self, launch: Callable, finalize: Callable,
                 deny: Callable, *, max_batch: int = 1 << 15,
                 depth: int = 2, window: float = 0.0,
                 weight: Callable = lambda item: 1,
                 lane: str = "serving",
                 telemetry: Callable[[], bool] = lambda: True,
                 max_pending: Optional[int] = None,
                 default_deadline: Optional[float] = None,
                 overload_high: float = 0.75,
                 overload_low: float = 0.25,
                 supervisor=None):
        self._launch = launch
        self._finalize = finalize
        self._deny = deny
        self.max_batch = max_batch
        self.depth = max(1, depth)
        self.window = window
        self._weight = weight
        self.lane = lane
        self.family = f"serving-{lane}"
        self._telemetry = telemetry
        self._cond = threading.Condition()
        self._pending: "deque[Tuple[object, Ticket]]" = deque()
        # (handle, batch, weights, launch number)
        self._inflight: "deque[Tuple[object, list, list, int]]" = deque()
        self._closed = False
        # ---- admission control: weight-bounded pending queue with a
        # hysteresis overload watermark pair (None = unbounded, the
        # pre-supervision behavior)
        self.max_pending = max_pending
        self.default_deadline = default_deadline
        self._pending_weight = 0
        self._high_mark = None if max_pending is None else \
            max(1, int(max_pending * overload_high))
        self._low_mark = None if max_pending is None else \
            max(0, int(max_pending * overload_low))
        self.overloaded = False
        # ---- device-fault supervision (datapath/supervisor.py):
        # classify faults, fail static from the host oracle, recover
        self.supervisor = supervisor
        # serving SLO tier (observability/slo.py): resolved tickets
        # observe submit->finalize latency against the lane objective
        # (the admission deadline when one is set); launches sample
        # queue depth into the flight ring
        self._shard = getattr(supervisor, "shard", None)
        # observability: how well the batching is working
        self.batches = 0
        self._launch_no = 0   # the launch being issued (batches + 1)
        self.frames = 0
        self.items_total = 0
        self.max_batch_seen = 0
        self.errors = 0
        self.static_batches = 0
        self.shed: Dict[str, int] = {}
        self.max_pending_seen = 0
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"serving-{lane}")
        self._thread.start()

    # ------------------------------------------------------------ submit

    def _shed(self, item, ticket: Ticket, reason: str) -> Ticket:
        """Fail the item closed at admission time."""
        self.shed[reason] = self.shed.get(reason, 0) + 1
        SERVING_SHED.inc(labels={"lane": self.lane, "reason": reason})
        ticket.resolve(self._deny(item), ShedError(reason))
        return ticket

    def _set_overloaded_locked(self, value: bool) -> None:
        if value != self.overloaded:
            self.overloaded = value
            DATAPLANE_OVERLOADED.set(1.0 if value else 0.0,
                                     labels={"lane": self.lane})
            # watermark crossings are incident-timeline transitions
            flight_recorder.record(
                EVENT_SERVING_OVERLOAD, shard=self._shard,
                lane=self.lane, state="on" if value else "off",
                pending=self._pending_weight)

    def submit(self, item, deadline: Optional[float] = None) -> Ticket:
        """Queue one item from any thread; returns its Ticket.

        ``deadline`` (seconds from now; falls back to the lane's
        ``default_deadline``) bounds how long the item may wait
        unserved: expired work is shed fail-closed, never dispatched.
        A full pending queue sheds immediately (reason "overflow")."""
        if deadline is None:
            deadline = self.default_deadline
        ticket = Ticket(deadline=deadline)
        w = self._weight(item)
        with self._cond:
            if self._closed:
                ticket.resolve(self._deny(item),
                               RuntimeError("dispatcher closed"))
                return ticket
            if self.max_pending is not None and \
                    self._pending_weight + w > self.max_pending:
                return self._shed(item, ticket, "overflow")
            self._pending.append((item, ticket))
            self._pending_weight += w
            if self._pending_weight > self.max_pending_seen:
                self.max_pending_seen = self._pending_weight
            if self._high_mark is not None and \
                    self._pending_weight >= self._high_mark:
                self._set_overloaded_locked(True)
            self._cond.notify()
        return ticket

    # ----------------------------------------------------- dispatcher loop

    def _take_batch(self, wait: bool):
        """Drain up to ``max_batch`` worth of pending items.  With
        ``wait`` (nothing in flight), blocks for work; a nonzero
        collection ``window`` then lets concurrent submitters pile in
        before the first drain — the VerdictBatcher micro-batch
        window, only paid from idle (a busy pipeline coalesces
        naturally while batches compute)."""
        with self._cond:
            if wait:
                while not self._pending and not self._closed:
                    self._cond.wait()
        if wait and self.window > 0 and not self._closed:
            time.sleep(self.window)
        batch: List[Tuple[object, Ticket]] = []
        expired: List[Tuple[object, Ticket]] = []
        total = 0
        now = time.monotonic()
        with self._cond:
            while self._pending:
                w = self._weight(self._pending[0][0])
                head_deadline = self._pending[0][1].deadline
                if head_deadline is not None and head_deadline <= now:
                    # deadline-aware admission: expired work is shed
                    # fail-closed, never dispatched — a stale verdict
                    # answers nothing and only delays live traffic
                    expired.append(self._pending.popleft())
                    self._pending_weight -= w
                    continue
                if batch and total + w > self.max_batch:
                    break
                item, ticket = self._pending.popleft()
                self._pending_weight -= w
                batch.append((item, ticket))
                total += w
            if self._low_mark is not None and self.overloaded and \
                    self._pending_weight <= self._low_mark:
                self._set_overloaded_locked(False)
        for item, ticket in expired:
            self._shed(item, ticket, "deadline")
        return batch, total

    def _run(self) -> None:
        while True:
            idle = not self._inflight
            with self._cond:
                if self._closed and not self._pending:
                    break
            batch, total = self._take_batch(wait=idle)
            if batch:
                self._launch_batch(batch, total)
            # double buffer: complete the oldest launch only once the
            # pipeline is full (or nothing new arrived) — packing the
            # next batch above overlapped this one's device walk
            if self._inflight and (len(self._inflight) >= self.depth
                                   or not batch):
                self._complete_oldest()
        # shutdown: drain in-flight work, then fail any stragglers
        while self._inflight:
            self._complete_oldest()
        with self._cond:
            leftovers = list(self._pending)
            self._pending.clear()
            self._pending_weight = 0
            if self._low_mark is not None:
                self._set_overloaded_locked(False)
        for item, ticket in leftovers:
            ticket.resolve(self._deny(item),
                           RuntimeError("dispatcher closed"))

    def _launch_batch(self, batch, total: int) -> None:
        telem = self._telemetry()
        n = self._launch_no = self.batches + 1
        t0 = time.perf_counter() if telem else 0.0
        with span(self.family, "dispatch", launch=n) if telem \
                else NO_SPAN:
            handle = self._issue(batch, total)
        if handle is None:
            return
        if telem:
            record_stage(self.family, "queue-wait",
                         t0 - batch[0][1].submitted_at)
        # SLO flight sample: queue state as of this launch (racy reads
        # are fine — observability, not control flow)
        slo_tracker.sample_queue(self.lane, queued=len(self._pending),
                                 inflight=len(self._inflight),
                                 pending_weight=self._pending_weight,
                                 shard=self._shard)
        self._inflight.append(
            (handle, batch, [self._weight(item) for item, _t in batch],
             n))
        self.batches += 1
        self.frames += len(batch)
        self.items_total += total
        self.max_batch_seen = max(self.max_batch_seen, total)
        SERVING_BATCHES.inc(labels={"lane": self.lane})
        SERVING_FRAMES.inc(len(batch), labels={"lane": self.lane})

    def _issue(self, batch, total: int):
        """Launch one batch: its in-flight handle, or None when the
        batch was answered otherwise (fail-static or fail-closed)."""
        items = [item for item, _t in batch]
        if self.supervisor is not None:
            on_device, payload = self.supervisor.launch(
                self._launch, items, total)
            if not on_device:
                self._resolve_static(batch, payload)
                return None
            return payload
        try:
            return self._launch(items, total)
        except Exception as e:  # noqa: BLE001 — fail closed: deny
            self._fail(batch, e)   # exactly this batch's frames
            return None

    def _complete_oldest(self) -> None:
        handle, batch, weights, n = self._inflight.popleft()
        telem = self._telemetry()
        # the one blocking boundary on this path: host waits out device
        # compute for the batch launched one step earlier
        with span(self.family, "complete", launch=n) if telem \
                else NO_SPAN:
            results = self._collect(handle, batch, weights)
        if results is None:
            return
        handoff = self.supervisor.handoff_s \
            if telem and self.supervisor is not None else None
        if handoff is not None:
            record_stage(self.family, "handoff", handoff)
        with span(self.family, "resolve", launch=n) if telem \
                else NO_SPAN:
            if telem:
                self._account(results)
            for (item, ticket), res in zip(batch, results):
                ticket.launch = n
                ticket.resolve(res)
            self._observe_slo(batch)

    def _collect(self, handle, batch, weights):
        """One launch's per-item results, or None when the batch was
        answered otherwise (fail-static or fail-closed)."""
        if self.supervisor is not None:
            ok, payload = self.supervisor.finalize(
                self._finalize, handle, weights,
                [item for item, _t in batch])
            if not ok:
                self._resolve_static(batch, payload)
                return None
            return payload
        try:
            return self._finalize(handle, weights)
        except Exception as e:  # noqa: BLE001 — fail closed: deny
            self._fail(batch, e)   # exactly this batch's frames
            return None

    def _account(self, results) -> None:
        """Telemetry over one device launch's results, before its
        tickets resolve (nothing here; lanes with countable results
        override it)."""

    def _observe_slo(self, batch) -> None:
        """Feed resolved tickets into the serving SLO tier: one
        submit->finalize latency observation per frame, judged against
        the lane's objective (its admission deadline when set)."""
        now = time.perf_counter()
        for _item, ticket in batch:
            slo_tracker.observe(self.lane,
                                now - ticket.submitted_at,
                                shard=self._shard,
                                objective_s=self.default_deadline)

    def _fail(self, batch, error: BaseException) -> None:
        self.errors += 1
        for item, ticket in batch:
            ticket.resolve(self._deny(item), error)
        self._observe_slo(batch)

    def _resolve_static(self, batch, payload) -> None:
        """Resolve one batch with the supervisor's fail-static answer
        (results carry NO error: they are real last-known-good
        verdicts, not denials); an unusable oracle falls back to the
        fail-closed deny contract."""
        results, error = payload
        if results is None:
            self._fail(batch, error or
                       RuntimeError("dataplane degraded"))
            return
        self.static_batches += 1
        self.frames += len(batch)
        for (item, ticket), res in zip(batch, results):
            ticket.resolve(res)
        self._observe_slo(batch)

    # ---------------------------------------------------------- lifecycle

    def stats(self) -> Dict:
        with self._cond:
            queued = len(self._pending)
            pending_weight = self._pending_weight
        out = {"lane": self.lane, "batches": self.batches,
               "frames": self.frames, "items": self.items_total,
               "max_batch": self.max_batch_seen,
               "errors": self.errors, "queued": queued,
               "inflight": len(self._inflight),
               "mean_batch": round(
                   self.items_total / self.batches, 2)
               if self.batches else 0.0,
               # admission control + supervision
               "shed": dict(self.shed),
               "overloaded": self.overloaded,
               "pending-weight": pending_weight,
               "max-pending-seen": self.max_pending_seen,
               "static-batches": self.static_batches}
        if self.supervisor is not None:
            out["supervisor"] = self.supervisor.stats()
        return out

    def close(self, timeout: float = 5.0) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout)


class _LaunchResults(list):
    """One launch's per-frame (verdict, identity) slices, with the
    launch's whole host verdict array they were cut from."""

    __slots__ = ("verdicts",)


class VerdictDispatcher(ContinuousDispatcher):
    """The engine-backed lane: SoA packet-record chunks in, (verdict,
    identity) int32 arrays out, one ``Datapath.process_packed`` launch
    per coalesced batch.

    Padding keeps the verdict-service invariant: batches round up to
    the shared power-of-two bucket (utils/bucketing.bucket_size) and
    pad rows duplicate row 0, so padding can never mint new conntrack
    keys; pad results are sliced off before tickets resolve.
    """

    def __init__(self, datapath, *, max_batch: int = 1 << 15,
                 min_rows: int = 16, depth: int = 2,
                 window: float = 0.0, lane: str = "verdict",
                 max_pending: Optional[int] = None,
                 default_deadline: Optional[float] = None,
                 supervisor=None):
        self._datapath = datapath
        self._min_rows = min_rows
        # staging rings: (bucket rows) -> list of depth+1 packed
        # [10, rows] matrices (pipeline.PACKED_FIELDS row order — ONE
        # H2D per launch); rotation guarantees the matrix being packed
        # is never one of the <=depth still referenced by in-flight
        # launches
        self._staging: Dict[int, List[np.ndarray]] = {}
        self._staging_tick: Dict[int, int] = {}
        # the L7 payload lane's staging twin ([rows, W] matrices, same
        # rotation), allocated only when the engine has fast verdicts
        # on; rows without a submitted payload stay -1 (absent ->
        # redirect-to-proxy, the pre-fast behavior)
        self._pl_staging: Dict[int, List[np.ndarray]] = {}
        self._pl_tick: Dict[int, int] = {}
        super().__init__(self._launch_records, self._finalize_records,
                         self._deny_records, max_batch=max_batch,
                         depth=depth, window=window,
                         weight=lambda chunk: chunk[1], lane=lane,
                         telemetry=lambda: getattr(
                             datapath, "telemetry_enabled", False),
                         max_pending=max_pending,
                         default_deadline=default_deadline,
                         supervisor=supervisor)

    def submit_records(self, soa: Dict[str, np.ndarray], n: int,
                       deadline: Optional[float] = None,
                       payload: Optional[np.ndarray] = None) -> Ticket:
        """Queue ``n`` records given as the PacketRing SoA dict (int32
        arrays, caller-owned — they are read once at pack time on the
        dispatcher thread, so hand over fresh arrays, not ring-backed
        views).  ``payload`` is the optional [n, W] int32 L7 payload
        block (l7/fast.encode_payloads) riding with the records into
        the fused fast-verdict stage; None = every L7 rule redirects
        for these records."""
        return self.submit((soa, int(n), payload), deadline=deadline)

    # ------------------------------------------------------------- pack

    def _stage_for(self, rows: int) -> np.ndarray:
        ring = self._staging.get(rows)
        if ring is None:
            ring = self._staging[rows] = [
                np.empty((len(PACKED_FIELDS), rows), np.int32)
                for _ in range(self.depth + 1)]
            self._staging_tick[rows] = 0
        tick = self._staging_tick[rows]
        self._staging_tick[rows] = tick + 1
        return ring[tick % len(ring)]

    def _pl_stage_for(self, rows: int, width: int) -> np.ndarray:
        ring = self._pl_staging.get(rows)
        if ring is None or ring[0].shape[1] != width:
            ring = self._pl_staging[rows] = [
                np.empty((rows, width), np.int32)
                for _ in range(self.depth + 1)]
            self._pl_tick[rows] = 0
        tick = self._pl_tick[rows]
        self._pl_tick[rows] = tick + 1
        return ring[tick % len(ring)]

    def _launch_records(self, items, total: int):
        n = self._launch_no
        with span(self.family, "pack", launch=n) if self._telemetry() \
                else NO_SPAN:
            stage, pstage = self._pack(items, total)
        verdict, _event, identity, _nat = \
            self._datapath.process_packed(stage, payload=pstage)
        return verdict, identity, n

    def _pack(self, items, total: int):
        """The launch's [10, rows] field matrix (and [rows, W] payload
        matrix when the engine has L7 fast verdicts on)."""
        rows = bucket_size(total, self._min_rows)
        stage = self._stage_for(rows)
        width = 0
        l7_window = getattr(self._datapath, "l7_fast_window", None)
        if l7_window is not None:
            width = l7_window()
        pstage = self._pl_stage_for(rows, width) if width else None
        off = 0
        for item in items:
            soa, n, pl = item[0], item[1], item[2] \
                if len(item) > 2 else None
            for fi, f in enumerate(PACKED_FIELDS):
                stage[fi, off:off + n] = soa[f][:n]
            if pstage is not None:
                if pl is None:
                    pstage[off:off + n] = -1
                else:
                    w = min(width, pl.shape[1])
                    pstage[off:off + n, :w] = pl[:n, :w]
                    if w < width:
                        pstage[off:off + n, w:] = -1
                    if pl.shape[1] > width:
                        # bytes beyond the engine window: poison the
                        # overflowing rows (fail-to-redirect) instead
                        # of silently judging a truncated string
                        over = (pl[:n, width:] >= 0).any(axis=1)
                        pstage[off:off + n][over] = -2
            off += n
        # pad rows are copies of the first real record: they re-touch
        # an existing flow's CT entry instead of minting new keys
        stage[:, total:rows] = stage[:, :1]
        if pstage is not None:
            # pad payloads stay absent: a duplicated header row with a
            # real payload could flip the pad's verdict arm
            pstage[total:rows] = -1
        return stage, pstage

    def _finalize_records(self, handle, weights: Sequence[int]):
        verdict, identity, n = handle
        total = sum(weights)
        d2h = span(self.family, "d2h", launch=n) \
            if self._telemetry() else NO_SPAN
        with d2h:
            v = np.asarray(verdict)[:total].astype(np.int32)   # sync-ok: the serving path's one blocking boundary (stage="complete")
            i = np.asarray(identity)[:total].astype(np.int32)  # sync-ok: same transfer, already realized by the line above
        out = _LaunchResults()
        out.verdicts = v
        off = 0
        for w in weights:
            out.append((v[off:off + w], i[off:off + w]))
            off += w
        return out

    def _account(self, results) -> None:
        """``policy_verdicts_total`` from the launch's host copy (the
        engine does not read the verdicts a second time)."""
        count_policy_verdicts(results.verdicts)

    @staticmethod
    def _deny_records(item):
        n = item[1]
        return (np.full(n, DROP_POLICY, np.int32),
                np.zeros(n, np.int32))
