"""The incident flight recorder (observability/events.py) + serving
SLO tier (observability/slo.py), and the loudness lint: every degraded
condition status() can report must have a matching flight-recorder
event type AND a metric series — a new failure mode can't ship silent.
"""

import threading
import time

import pytest

from cilium_tpu.observability.events import (DEGRADED_SIGNALS,
                                             EVENT_TYPES,
                                             FlightRecorder, recorder)
from cilium_tpu.observability.slo import SLOTracker
from cilium_tpu.utils import metrics as metrics_mod


# ----------------------------------------------------- recorder core

class TestFlightRecorder:
    def test_seq_monotonic_and_forward_paging(self):
        fr = FlightRecorder(capacity=16)
        evs = [fr.record("dataplane-breaker-trip", detail=f"e{i}",
                         shard=i % 2) for i in range(5)]
        assert [e.seq for e in evs] == [1, 2, 3, 4, 5]
        got = fr.events(since=2, limit=0)
        assert [e.seq for e in got] == [3, 4, 5]
        # type + shard filters compose with the cursor
        got = fr.events(since=0, event_type="dataplane-breaker-trip",
                        shard=1)
        assert [e.seq for e in got] == [2, 4]
        assert fr.last_seq == 5

    def test_bounded_ring_evicts_oldest_and_accounts(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record("serving-overload", state="on", i=i)
        assert fr.stats()["ringed"] == 4
        assert fr.evicted == 6
        # the surviving events are the NEWEST, cursors intact
        assert [e.seq for e in fr.events(limit=0)] == [7, 8, 9, 10]

    def test_eviction_accounting_split_by_evicted_type(self):
        """The dropped accounting names WHICH type overran the ring: a
        noisy emitter flooding the recorder shows up as its own type's
        eviction count, not an anonymous aggregate a quieter type
        could hide behind."""
        fr = FlightRecorder(capacity=4)
        for _ in range(6):
            fr.record("serving-overload", state="on")
        for _ in range(2):
            fr.record("map-pressure-warning", map="ct", shard=None)
        # 8 recorded, 4 survive; the 4 evicted are the oldest — all
        # the noisy emitter's
        st = fr.stats()
        assert fr.evicted == 4
        assert st["evicted-by-type"] == {"serving-overload": 4}
        # push the quieter type out too: both types now accounted
        for _ in range(4):
            fr.record("serving-overload", state="on")
        by_type = fr.stats()["evicted-by-type"]
        assert by_type["map-pressure-warning"] == 2
        assert sum(by_type.values()) == fr.evicted

    def test_eviction_counter_labeled_by_type(self):
        ctr = metrics_mod.registry._metrics[
            "cilium_tpu_flight_recorder_dropped_total"]
        before = ctr.value(labels={"type": "serving-overload"})
        fr = FlightRecorder(capacity=2)
        for _ in range(5):
            fr.record("serving-overload", state="on")
        assert ctr.value(
            labels={"type": "serving-overload"}) == before + 3

    def test_undeclared_type_raises(self):
        fr = FlightRecorder()
        with pytest.raises(ValueError):
            fr.record("made-up-event")

    def test_event_rendering_and_wire_dict(self):
        fr = FlightRecorder()
        e = fr.record("kvstore-degraded", detail="etcd gone",
                      shard=None, outage=3)
        d = e.to_dict()
        assert d["type"] == "kvstore-degraded"
        assert d["attrs"] == {"outage": 3}
        assert "kvstore-degraded: etcd gone (outage=3)" \
            in e.describe()
        e2 = fr.record("dataplane-degraded", shard=2)
        assert e2.describe().startswith("[shard 2] ")
        assert len(fr.timeline()) == 2

    def test_trace_id_rides_along(self):
        from cilium_tpu.observability.tracer import tracer
        tracer.configure(enabled=True)
        fr = FlightRecorder()
        with tracer.span("incident-test"):
            e = fr.record("drift-audit", status="FAILING",
                          divergences=1)
        assert e.trace_id != ""

    def test_global_recorder_counts_metric(self):
        before = metrics_mod.registry._metrics[
            "cilium_tpu_flight_recorder_events_total"].value(
            labels={"type": "map-pressure-warning"})
        recorder.record("map-pressure-warning", map="ct", shard=None)
        after = metrics_mod.registry._metrics[
            "cilium_tpu_flight_recorder_events_total"].value(
            labels={"type": "map-pressure-warning"})
        assert after == before + 1

    def test_thread_safe_unique_seqs(self):
        fr = FlightRecorder(capacity=4096)
        out = []

        def spin():
            out.extend(fr.record("serving-overload", state="on").seq
                       for _ in range(200))

        threads = [threading.Thread(target=spin) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(set(out)) == 800


# ------------------------------------------------------- SLO tracker

class TestSLOTracker:
    def test_latency_percentiles_and_breaches(self):
        slo = SLOTracker()
        slo.configure(objective_s=0.010, error_budget=0.1)
        for _ in range(90):
            slo.observe("lane-a", 0.001)
        for _ in range(10):
            slo.observe("lane-a", 0.050)   # breach
        snap = slo.snapshot()["lanes"]["lane-a"]
        assert snap["requests"] == 100
        assert snap["breaches"] == 10
        # 10% breaches / 10% budget = burn rate 1.0
        assert snap["burn-rate"] == pytest.approx(1.0, abs=0.01)
        assert snap["p50-us"] == pytest.approx(1000.0, rel=0.2)
        assert snap["p99-us"] >= 10_000.0
        assert snap["worst-us"] == pytest.approx(50_000.0, rel=0.01)

    def test_lane_objective_from_deadline(self):
        slo = SLOTracker()
        slo.configure(objective_s=1.0, error_budget=0.001)
        # an explicit per-lane objective (the admission deadline)
        # overrides the default
        slo.observe("lane-d", 0.02, objective_s=0.01)
        snap = slo.snapshot()["lanes"]["lane-d"]
        assert snap["objective-ms"] == 10.0
        assert snap["breaches"] == 1

    def test_running_breach_count_matches_the_window_sum(self):
        """The burn rate kept from a running breach count equals the
        one summed over the outcome window, after every observation of
        a random stream long enough to roll the window over."""
        import random

        from cilium_tpu.observability.slo import (SERVING_SLO_BURN,
                                                  WINDOW)
        rng = random.Random(23)
        slo = SLOTracker()
        slo.configure(objective_s=0.010, error_budget=0.01)
        lane = f"lane-burn-{time.monotonic_ns()}"
        outcomes = []
        for _ in range(3 * WINDOW + 17):
            lat = rng.choice((0.001, 0.002, 0.020, 0.5)) \
                if rng.random() < 0.3 else 0.004
            slo.observe(lane, lat)
            outcomes.append(lat > 0.010)
            window = outcomes[-WINDOW:]
            want = round(sum(window) / len(window) / 0.01, 4)
            assert SERVING_SLO_BURN.value(labels={"lane": lane}) == want
        snap = slo.snapshot()["lanes"][lane]
        window = outcomes[-WINDOW:]
        assert snap["burn-rate"] == round(sum(window) / len(window)
                                          / 0.01, 4)
        assert snap["breaches"] == sum(outcomes)

    def test_queue_ring_bounded_and_sampled(self):
        slo = SLOTracker()
        for i in range(300):
            slo.sample_queue("lane-q", queued=i, inflight=i % 3,
                             pending_weight=i * 2, shard=1)
        ring = slo.queue_ring("lane-q")
        assert len(ring) == 256           # bounded
        assert ring[-1]["pending"] == 299 * 2
        snap = slo.snapshot()["lanes"]["lane-q"]
        assert snap["shard"] == 1
        assert snap["queue"]["inflight"] == 299 % 3

    def test_top_lines_render(self):
        slo = SLOTracker()
        slo.observe("verdict-s0", 0.002, shard=0)
        slo.sample_queue("verdict-s0", 4, 2, 128, shard=0)
        lines = slo.top_lines()
        assert "LANE" in lines[0] and "BURN" in lines[0]
        assert any("verdict-s0" in line for line in lines[1:])

    def test_dispatcher_feeds_the_tier(self):
        """Plumbing: a ContinuousDispatcher resolution observes the
        ticket latency into the process tracker and samples the queue
        — no engine needed (host-only lane)."""
        from cilium_tpu.datapath.serving import ContinuousDispatcher
        from cilium_tpu.observability.slo import slo_tracker
        lane = f"slo-test-{time.monotonic_ns()}"
        d = ContinuousDispatcher(
            launch=lambda items, total: list(items),
            finalize=lambda handle, weights: [i * 2 for i in handle],
            deny=lambda item: -1, lane=lane)
        try:
            tickets = [d.submit(i) for i in range(8)]
            for i, t in enumerate(tickets):
                assert t.result(timeout=10.0) == i * 2
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                snap = slo_tracker.snapshot()["lanes"].get(lane)
                if snap and snap["requests"] >= 8 and \
                        snap["queue-samples"] > 0:
                    break
                time.sleep(0.01)
            assert snap["requests"] >= 8
            assert snap["queue-samples"] > 0
            assert snap["p99-us"] > 0.0
        finally:
            d.close()


# ------------------------------------------------------ loudness lint

SIGNAL_KEYS = {"state", "status", "mode", "warnings", "drift-audit"}


def _degraded_sections(status):
    """status() sections that can report a degraded condition: any
    dict section carrying a state/status/mode/warnings signal key."""
    return {k for k, v in status.items()
            if isinstance(v, dict) and SIGNAL_KEYS & set(v)}


def test_loudness_lint_every_degraded_signal_has_event_and_metric():
    """A live daemon's status() is introspected for every section
    that reports a degraded condition; each must be covered by
    DEGRADED_SIGNALS with declared flight-recorder event types and
    registered metric series — shipping a new failure mode without a
    timeline event and a metric is a test failure, not a review nit."""
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.utils.option import DaemonConfig
    d = Daemon(config=DaemonConfig(
        state_dir="", drift_audit_interval_s=0,
        ct_checkpoint_interval_s=0))
    try:
        sections = _degraded_sections(d.status())
    finally:
        d.shutdown()
    assert sections, "status() lost its degraded-signal sections"
    uncovered = sections - set(DEGRADED_SIGNALS)
    assert not uncovered, (
        "status() sections reporting degraded conditions without "
        "flight-recorder coverage (add them to "
        f"observability/events.py DEGRADED_SIGNALS): {uncovered}")
    stale = set(DEGRADED_SIGNALS) - sections
    assert not stale, (
        f"DEGRADED_SIGNALS names status() sections that no longer "
        f"exist: {stale}")
    with metrics_mod.registry._lock:
        registered = set(metrics_mod.registry._metrics)
    for section, cover in DEGRADED_SIGNALS.items():
        assert cover["events"], section
        for ev in cover["events"]:
            assert ev in EVENT_TYPES, (
                f"{section} names undeclared event type {ev!r}")
        assert cover["metrics"], section
        for m in cover["metrics"]:
            assert m in registered, (
                f"{section} names unregistered metric {m!r}")


def test_every_event_type_belongs_to_a_degraded_signal():
    """The other direction: no orphan event types — each declared
    type is reachable from some degraded condition's coverage, so
    EVENT_TYPES can't accrete stale docs."""
    covered = {ev for cover in DEGRADED_SIGNALS.values()
               for ev in cover["events"]}
    orphans = set(EVENT_TYPES) - covered
    assert not orphans, (
        f"EVENT_TYPES declares types no DEGRADED_SIGNALS entry "
        f"covers: {orphans}")


def test_event_types_have_descriptions():
    for name, help_text in EVENT_TYPES.items():
        assert help_text and len(help_text) > 10, name
