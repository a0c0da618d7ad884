"""Runtime self-telemetry (cilium_tpu/observability/).

Covers the tracer (span trees, context propagation, fake clocks,
bounded buffer, disabled no-op), the policy-propagation latency
tracker, the map-pressure report, JIT/compile telemetry, the
pipeline-stage breakdown, full-registry Prometheus conformance
(every declared series exposed, histograms with zero observations
included), the three previously-dead metric wirings
(PROXY_UPSTREAM_TIME, KVSTORE_OPERATIONS, POLICY_VERDICTS), and the
live-daemon end-to-end acceptance path: insert rule -> the
policy_implementation_delay histogram increments and /debug/traces
shows the revision's span tree (import -> compile -> device apply ->
first verdict).
"""

import io
import json
import re
import sys
import threading
import time

import numpy as np
import pytest

from cilium_tpu.observability import (POLICY_IMPLEMENTATION_DELAY,
                                      PolicyPropagationTracker,
                                      compute_pressure, jit_telemetry,
                                      pipeline_report, record_stage)
from cilium_tpu.observability.tracer import NOOP_SPAN, Tracer
from cilium_tpu.utils.metrics import (KVSTORE_OPERATIONS,
                                      POLICY_VERDICTS,
                                      PROXY_UPSTREAM_TIME, Histogram,
                                      registry)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ------------------------------------------------------------------ tracer

class TestTracer:
    def test_nested_spans_thread_local_parenting(self):
        clock = FakeClock()
        tr = Tracer(capacity=64, clock=clock)
        with tr.span("outer", attrs={"k": 1}) as outer:
            clock.advance(1.0)
            with tr.span("inner") as inner:
                clock.advance(0.5)
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        tree = tr.tree(outer.trace_id)
        assert tree["spans"][0]["name"] == "outer"
        assert tree["spans"][0]["children"][0]["name"] == "inner"
        assert tree["spans"][0]["duration-s"] == pytest.approx(1.5)
        assert tree["spans"][0]["children"][0]["duration-s"] == \
            pytest.approx(0.5)

    def test_explicit_parent_context_across_threads(self):
        tr = Tracer(capacity=64)
        with tr.span("root") as root:
            ctx = root.context
        done = threading.Event()

        def worker():
            tr.span("child-on-other-thread", parent=ctx).finish()
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
        tree = tr.tree(ctx.trace_id)
        names = [c["name"] for c in tree["spans"][0]["children"]]
        assert "child-on-other-thread" in names

    def test_disabled_is_noop(self):
        tr = Tracer(enabled=False)
        span = tr.span("nope")
        assert span is NOOP_SPAN
        with span:
            pass
        assert tr.snapshot() == []
        assert tr.child_span("also-nope") is NOOP_SPAN

    def test_child_span_requires_active_trace(self):
        tr = Tracer()
        assert tr.child_span("orphan") is NOOP_SPAN
        with tr.span("parent"):
            child = tr.child_span("kv-op")
            assert child is not NOOP_SPAN
            child.finish()

    def test_bounded_ring_evicts_and_counts(self):
        tr = Tracer(capacity=8)
        for i in range(20):
            tr.span(f"s{i}", root=True).finish()
        assert len(tr.snapshot()) == 8
        assert tr.dropped == 12
        # newest survive
        assert tr.snapshot()[-1]["name"] == "s19"

    def test_trace_summaries_and_find(self):
        tr = Tracer(capacity=64)
        with tr.span("alpha", attrs={"revision": 7}):
            with tr.span("beta"):
                pass
        summaries = tr.traces()
        assert summaries[-1]["root"] == "alpha"
        assert summaries[-1]["spans"] == 2
        assert tr.find_trace(revision=7) == summaries[-1]["trace-id"]
        assert tr.find_trace(revision=12345) is None

    def test_error_status_on_exception(self):
        tr = Tracer(capacity=8)
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        assert tr.snapshot()[-1]["status"] == "error"

    def test_configure_capacity_preserves_spans(self):
        tr = Tracer(capacity=4)
        for i in range(4):
            tr.span(f"s{i}", root=True).finish()
        tr.configure(capacity=16)
        assert len(tr.snapshot()) == 4
        assert tr.capacity == 16


# ------------------------------------------------------- propagation latency

class TestPropagationTracker:
    def _tracker(self):
        clock = FakeClock()
        tr = Tracer(capacity=256, clock=clock)
        return PolicyPropagationTracker(tracer=tr, clock=clock), \
            tr, clock

    def test_full_journey_observes_histogram(self):
        tracker, tr, clock = self._tracker()
        before = POLICY_IMPLEMENTATION_DELAY.total_count()
        tracker.revision_imported(5, rules=3, import_seconds=0.01)
        clock.advance(0.2)
        with tracker.stage_span(5, "policy.compile", {"endpoint": 1}):
            clock.advance(0.1)
        tracker.revision_compiled(5)
        with tracker.stage_span(5, "policy.device-apply"):
            clock.advance(0.05)
        tracker.revision_applied(5)
        clock.advance(0.15)
        tracker.revision_served(5)
        assert POLICY_IMPLEMENTATION_DELAY.total_count() == before + 1
        rec = tracker.report(1)[0]
        assert rec["revision"] == 5
        assert rec["first-verdict-delay-s"] == pytest.approx(0.51)
        assert rec["compile-delay-s"] == pytest.approx(0.31)
        assert rec["device-apply-delay-s"] == pytest.approx(0.36)
        # span tree: import is the root, stages + first-verdict nest
        tree = tr.tree(tracker.trace_id_of(5))
        root = tree["spans"][0]
        assert root["name"].startswith("policy.import")
        child_names = [c["name"] for c in root["children"]]
        assert any(n == "policy.compile" for n in child_names)
        assert any(n == "policy.device-apply" for n in child_names)
        assert any(n.startswith("policy.first-verdict")
                   for n in child_names)

    def test_superseded_revisions_complete_together(self):
        tracker, _tr, clock = self._tracker()
        before = POLICY_IMPLEMENTATION_DELAY.total_count()
        tracker.revision_imported(2)
        clock.advance(1.0)
        tracker.revision_imported(3)
        clock.advance(1.0)
        tracker.revision_served(3)
        # both pending revisions closed by the one serving dispatch
        assert POLICY_IMPLEMENTATION_DELAY.total_count() == before + 2
        recs = {r["revision"]: r for r in tracker.report()}
        assert recs[2]["first-verdict-delay-s"] == pytest.approx(2.0)
        assert recs[3]["first-verdict-delay-s"] == pytest.approx(1.0)

    def test_served_is_monotonic_and_idempotent(self):
        tracker, _tr, clock = self._tracker()
        before = POLICY_IMPLEMENTATION_DELAY.total_count()
        tracker.revision_imported(4)
        tracker.revision_served(4)
        tracker.revision_served(4)  # repeat: no double count
        tracker.revision_served(3)  # stale: ignored
        assert POLICY_IMPLEMENTATION_DELAY.total_count() == before + 1

    def test_history_bounded(self):
        tracker, _tr, _clock = self._tracker()
        tracker.capacity = 4
        for rev in range(10, 30):
            tracker.revision_imported(rev)
        assert len(tracker.report(100)) == 4
        assert tracker.report(100)[-1]["revision"] == 29


# ------------------------------------------- histogram zero-observation fix

class TestHistogramZeroObservations:
    def test_declared_histogram_exposes_zero_series(self):
        h = Histogram("cilium_tpu_test_empty_hist", "empty",
                      buckets=(0.1, 1.0))
        lines = h.expose()
        assert "cilium_tpu_test_empty_hist_sum 0.0" in lines
        assert "cilium_tpu_test_empty_hist_count 0" in lines
        inf = [l for l in lines if 'le="+Inf"' in l]
        assert inf == ['cilium_tpu_test_empty_hist_bucket'
                       '{le="+Inf"} 0']
        # one line per bucket + inf + sum + count
        assert len(lines) == 2 + 3

    def test_observation_replaces_zero_series(self):
        h = Histogram("cilium_tpu_test_one_hist", "one",
                      buckets=(0.1, 1.0))
        h.observe(0.05)
        lines = h.expose()
        assert "cilium_tpu_test_one_hist_count 1" in lines
        # the synthetic empty series is gone
        assert lines.count("cilium_tpu_test_one_hist_count 1") == 1
        assert h.count() == 1 and h.sum_value() == pytest.approx(0.05)


class TestObserveManyEdgeCases:
    """The batched-ingest path (observe_many) at its boundaries: a
    zero-count call, negative observation values, numpy-integer
    counts, and the monitor's per-score path fed an empty batch —
    each must keep the exposition Prometheus-conformant."""

    def test_count_zero_is_a_noop_on_every_series(self):
        h = Histogram("cilium_tpu_test_many_zero", "zc",
                      buckets=(0.1, 1.0))
        h.observe_many(0.5, 0)
        assert h.count() == 0
        assert h.sum_value() == 0.0
        lines = h.expose()
        assert "cilium_tpu_test_many_zero_count 0" in lines
        assert "cilium_tpu_test_many_zero_sum 0.0" in lines
        assert 'cilium_tpu_test_many_zero_bucket{le="+Inf"} 0' \
            in lines
        # still the full declared series, nothing duplicated
        assert len(lines) == 2 + 3

    def test_negative_values_bucket_cumulatively(self):
        h = Histogram("cilium_tpu_test_many_neg", "neg",
                      buckets=(0.1, 1.0))
        h.observe_many(-2.0, 3)
        # a negative observation lands in EVERY bucket (cumulative
        # le-semantics) and drives _sum negative — never a lost count
        assert h.count() == 3
        assert h.sum_value() == pytest.approx(-6.0)
        lines = h.expose()
        assert 'cilium_tpu_test_many_neg_bucket{le="0.1"} 3' in lines
        assert 'cilium_tpu_test_many_neg_bucket{le="+Inf"} 3' in lines
        # bucket counts stay monotonically non-decreasing in le order
        counts = [int(l.rsplit(" ", 1)[1]) for l in lines
                  if "_bucket" in l]
        assert counts == sorted(counts)

    def test_bucket_counts_match_a_cumulative_recount(self):
        """Each observation lands in one bucket and exposition
        accumulates: every ``le`` line equals a direct count of the
        observations at or below it, bucket edges included."""
        import random
        rng = random.Random(7)
        buckets = (0.001, 0.01, 0.1, 1.0)
        h = Histogram("cilium_tpu_test_many_recount", "rc",
                      buckets=buckets)
        values = [rng.choice(buckets + (-1.0, 0.0, 2.0, 0.05, 0.0005))
                  for _ in range(500)]
        for v in values:
            h.observe(v)
        lines = h.expose()
        for ub in buckets:
            want = sum(v <= ub for v in values)
            assert f'cilium_tpu_test_many_recount_bucket{{le="{ub!r}"}} ' \
                f"{want}" in lines
        assert f'cilium_tpu_test_many_recount_bucket{{le="+Inf"}} ' \
            f"{len(values)}" in lines

    def test_numpy_integer_counts_coerce(self):
        import numpy as np
        h = Histogram("cilium_tpu_test_many_np", "np",
                      buckets=(0.1, 1.0))
        h.observe_many(0.05, np.int64(4))
        h.observe_many(0.5, np.int32(2))
        assert h.count() == 6
        assert isinstance(h.count(), int)
        assert h.sum_value() == pytest.approx(0.05 * 4 + 0.5 * 2)

    def test_monitor_per_score_path_with_empty_batch(self):
        import numpy as np
        from cilium_tpu.monitor import MonitorHub
        from cilium_tpu.utils.metrics import (THREAT_SCORES,
                                              THREAT_VERDICTS)
        hub = MonitorHub()
        empty = np.zeros(0, dtype=np.int32)
        scores_before = THREAT_SCORES.total_count()
        verdicts_before = THREAT_VERDICTS.total()
        # an empty batch with the threat lane attached must be a
        # clean no-op: no samples, no counters, no exceptions
        hub.ingest_batch(empty, empty, empty, empty, empty, empty,
                         tiers=empty, match_slots=empty,
                         threat_out=empty)
        assert THREAT_SCORES.total_count() == scores_before
        assert THREAT_VERDICTS.total() == verdicts_before
        assert hub.tail(10) == []
        assert hub.lost == 0
        assert hub.top_dropped_rules() == []


# ------------------------------------------------- registry-wide conformance

def _parse_metrics(text):
    """Parse exposition text -> (helps, types, samples)."""
    helps, types, samples = {}, {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name, _, rest = line[len("# HELP "):].partition(" ")
            helps[name] = rest
        elif line.startswith("# TYPE "):
            name, _, kind = line[len("# TYPE "):].partition(" ")
            types[name] = kind
        else:
            m = re.fullmatch(
                r"([a-zA-Z_:][a-zA-Z0-9_:]*)"
                r"(\{.*\})? ([0-9eE+.\-]+|NaN)", line)
            assert m, f"unparseable sample line: {line!r}"
            samples.append((m.group(1), m.group(2) or "",
                            m.group(3)))
    return helps, types, samples


class TestPrometheusConformance:
    def test_full_registry_exposition(self):
        text = registry.expose_text()
        helps, types, samples = _parse_metrics(text)
        # every registered metric has HELP and TYPE
        with registry._lock:
            metrics = dict(registry._metrics)
        for name, metric in metrics.items():
            assert types.get(name) == metric.kind, name
            assert name in helps and helps[name], \
                f"{name} missing HELP"
        # no duplicate series (name + labelset unique)
        seen = set()
        for name, labels, _v in samples:
            assert (name, labels) not in seen, \
                f"duplicate series {name}{labels}"
            seen.add((name, labels))
        # histograms expose _sum/_count (+Inf bucket) per declared
        # metric, observations or not
        sample_names = {s[0] for s in samples}
        for name, metric in metrics.items():
            if metric.kind == "histogram":
                assert f"{name}_sum" in sample_names, name
                assert f"{name}_count" in sample_names, name
                assert any(n == f"{name}_bucket" and 'le="+Inf"' in l
                           for n, l, _ in samples), name
            else:
                assert name in sample_names, \
                    f"{name} declared but exposes no samples"

    def test_every_metric_has_help_text(self):
        with registry._lock:
            metrics = list(registry._metrics.values())
        missing = [m.name for m in metrics if not m.help]
        assert not missing, f"metrics without help text: {missing}"


# --------------------------------------------------------------- map pressure

class TestMapPressure:
    def test_compute_pressure_warnings(self):
        inventory = {
            "ct": {"slots": 100, "occupied": 95, "max-probe": 8},
            "ct6": {"slots": 100, "occupied": 10, "max-probe": 8},
            "policy": {"endpoints": 8, "slots": 64, "attached": 8},
            "hubble-flows": {"slots": 64, "occupied": 32},
            "ipcache": {"entries": 12},
            "lb": {"services": 3},
        }
        report = compute_pressure(inventory, warn_threshold=0.9)
        maps = report["maps"]
        assert maps["ct"]["pressure"] == pytest.approx(0.95)
        assert maps["ct6"]["pressure"] == pytest.approx(0.10)
        assert maps["policy-rows"]["pressure"] == pytest.approx(1.0)
        assert maps["hubble-flows"]["pressure"] == pytest.approx(0.5)
        assert maps["ipcache"]["pressure"] is None
        warn_maps = [w.split(":")[0] for w in report["warnings"]]
        assert set(warn_maps) == {"ct", "policy-rows"}
        # gauges updated in lockstep with the report
        from cilium_tpu.observability import MAP_PRESSURE
        assert MAP_PRESSURE.value(labels={"map": "ct"}) == \
            pytest.approx(0.95)

    def test_live_engine_pressure(self):
        from cilium_tpu.datapath.engine import Datapath
        from cilium_tpu.policy.mapstate import PolicyMapState
        dp = Datapath(ct_slots=1 << 8)
        dp.load_policy([PolicyMapState()], revision=1,
                       ipcache_prefixes={"10.0.0.0/8": 2})
        report = dp.map_pressure()
        assert report["maps"]["ct"]["capacity"] == 1 << 8
        assert report["maps"]["ct"]["pressure"] == 0.0
        assert report["warnings"] == []


# ------------------------------------------------------------ jit telemetry

class TestJitTelemetry:
    def test_hit_miss_classification(self):
        """Compiles come from JAX's compile events, by jitted function;
        a persistent-cache hit reported inside a compile makes it a
        hit, any other event is not a compile."""
        from cilium_tpu.observability.jitstats import (CACHE_HIT_EVENT,
                                                       COMPILE_EVENT,
                                                       JitTelemetry)
        t = JitTelemetry()
        t.on_duration(COMPILE_EVENT, 1.5, fun_name="step")   # compiled
        t.on_event(CACHE_HIT_EVENT)                           # loaded
        t.on_duration(COMPILE_EVENT, 0.001, fun_name="step")
        t.on_duration(COMPILE_EVENT, 1.2, fun_name="step")   # new shape
        t.on_duration(COMPILE_EVENT, 1.0, fun_name="other")  # new program
        t.on_duration("/jax/core/other_duration", 9.0, fun_name="step")
        rep = t.report()
        assert rep["compiles"] == {"step": 3, "other": 1}
        assert rep["cache-hits"] == 1 and rep["cache-misses"] == 3
        assert rep["compile-seconds"]["step"] == pytest.approx(2.701)

    def test_disabled_records_nothing(self):
        from cilium_tpu.observability.jitstats import (COMPILE_EVENT,
                                                       JitTelemetry)
        t = JitTelemetry()
        t.enabled = False
        t.on_duration(COMPILE_EVENT, 1.5, fun_name="step")
        assert t.report()["cache-misses"] == 0
        assert t.report()["compiles"] == {}

    def test_engine_accounts_compiles_and_hits(self):
        """The engine's first dispatch compiles its step (counted under
        the step's name); a second at the same geometry is a jit-cache
        hit and compiles nothing."""
        from cilium_tpu.datapath.engine import Datapath, \
            make_full_batch
        from cilium_tpu.policy.mapstate import PolicyMapState
        before = jit_telemetry.report()
        dp = Datapath(ct_slots=1 << 8)
        dp.load_policy([PolicyMapState()], revision=1,
                       ipcache_prefixes={})
        pkt = make_full_batch(endpoint=[0], saddr=[1], daddr=[2],
                              sport=[1], dport=[80])
        dp.process(pkt, now=10)
        first = jit_telemetry.report()
        dp.process(pkt, now=11)
        after = jit_telemetry.report()
        step = "jit(full_datapath_step)"
        assert first["cache-misses"] >= before["cache-misses"] + 1
        assert first["compiles"].get(step, 0) >= \
            before["compiles"].get(step, 0) + 1
        assert after["compiles"].get(step) == first["compiles"][step]
        assert pipeline_report()["jit"]["compile"]["count"] >= 1
        assert after["device-bytes"].get("engine-tables", 0) > 0

    def test_engine_telemetry_disabled_is_silent(self):
        from cilium_tpu.datapath.engine import Datapath, \
            make_full_batch
        from cilium_tpu.policy.mapstate import PolicyMapState
        dp = Datapath(ct_slots=1 << 8)
        dp.telemetry_enabled = False
        dp.load_policy([PolicyMapState()], revision=1,
                       ipcache_prefixes={})
        before = jit_telemetry.report()
        stages_before = pipeline_report().get("engine-v4", {})
        pkt = make_full_batch(endpoint=[0], saddr=[1], daddr=[2],
                              sport=[1], dport=[80])
        jit_telemetry.enabled = False
        try:
            dp.process(pkt, now=10)
        finally:
            jit_telemetry.enabled = True
        after = jit_telemetry.report()
        assert after["cache-misses"] == before["cache-misses"]
        assert pipeline_report().get("engine-v4", {}) == stages_before
        assert not dp._pending_verdicts


# ------------------------------------------------------------ pipeline stages

class TestPipelineStages:
    def test_report_shares_and_blocking_flags(self):
        record_stage("test-family", "pack", 0.001)
        record_stage("test-family", "pack", 0.003)
        record_stage("test-family", "sync", 0.006)
        rep = pipeline_report()["test-family"]
        assert rep["pack"]["count"] >= 2
        assert rep["sync"]["blocking-boundary"] is True
        assert rep["pack"]["blocking-boundary"] is False
        total = sum(s["share-pct"] for s in rep.values())
        assert total == pytest.approx(100.0, abs=0.5)

    def test_histogram_series_exported(self):
        record_stage("test-family2", "dispatch", 0.002)
        text = registry.expose_text()
        assert 'cilium_tpu_pipeline_stage_seconds_count' \
            '{family="test-family2",stage="dispatch"}' in text

    def test_stage_span_lands_on_the_profiler_timeline(self, tmp_path):
        """``stage()`` records the slice like ``record_stage`` and puts
        it on a profile as ``<family>.<name>`` with its metadata."""
        import glob
        import os

        import jax
        from jax.profiler import ProfileData

        from cilium_tpu.observability.stages import stage
        jax.profiler.start_trace(str(tmp_path))
        try:
            with stage("test-family3", "pack", launch=3):
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        rep = pipeline_report()["test-family3"]["pack"]
        assert rep["count"] == 1 and rep["total-s"] >= 0.002
        path, = glob.glob(os.path.join(str(tmp_path), "plugins",
                                       "profile", "*", "*.xplane.pb"))
        found = [dict(e.stats) for p in ProfileData.from_file(path).planes
                 for ln in p.lines for e in ln.events
                 if e.name == "test-family3.pack"]
        assert len(found) == 1 and found[0]["launch"] == 3

    def test_gc_is_timed_while_the_stage_lock_is_held(self):
        """A collection that starts while another thread holds the
        stage lock finishes (the hook takes no lock) and is counted
        by generation under family ``runtime``."""
        import gc

        from cilium_tpu.observability import stages
        before = pipeline_report()["runtime"]["gc-gen2"]["count"]
        held, release = threading.Event(), threading.Event()

        def holder():
            with stages._lock:
                held.set()
                release.wait(30)

        h = threading.Thread(target=holder, daemon=True)
        h.start()
        assert held.wait(10)
        collector = threading.Thread(target=gc.collect, daemon=True)
        collector.start()
        collector.join(timeout=30)
        finished = not collector.is_alive()
        release.set()
        h.join(timeout=10)
        assert finished, "gc.collect() blocked on the stage lock"
        rep = pipeline_report()["runtime"]
        assert rep["gc-gen2"]["count"] >= before + 1
        assert rep["gc-gen2"]["total-s"] > 0
        assert set(rep) == {"gc-gen0", "gc-gen1", "gc-gen2"}


# ------------------------------------------------ named stages of the step

class TestStepScopes:
    def test_step_text_names_its_stages(self):
        """Each stage of the fused step is a named scope: the lowered
        program's locations and the compiled program's op names carry
        them."""
        from cilium_tpu.datapath.engine import Datapath
        from cilium_tpu.datapath.pipeline import PACKED_FIELDS
        from cilium_tpu.policy.mapstate import PolicyMapState
        dp = Datapath(ct_slots=1 << 8)
        dp.load_policy([PolicyMapState()], revision=1,
                       ipcache_prefixes={"10.0.0.0/8": 2})
        packed = np.zeros((len(PACKED_FIELDS), 16), np.int32)
        lowered = dp._step_packed.lower(*dp._lower_args_packed(packed))
        texts = (lowered.as_text(debug_info=True),
                 lowered.compile().as_text())
        for text in texts:
            for scope in ("policy", "ipcache", "ct"):
                assert f"/{scope}/" in text, scope


# ----------------------------------------------- previously-dead metric wires

class TestWiredMetrics:
    def test_policy_verdicts_from_engine_path(self):
        from cilium_tpu.datapath.engine import Datapath, \
            make_full_batch
        from cilium_tpu.policy.mapstate import (EGRESS, PolicyKey,
                                                PolicyMapState,
                                                PolicyMapStateEntry)
        st = PolicyMapState({
            PolicyKey(identity=2, dest_port=80, nexthdr=6,
                      direction=EGRESS): PolicyMapStateEntry()})
        dp = Datapath(ct_slots=1 << 8)
        dp.load_policy([st], revision=1,
                       ipcache_prefixes={"0.0.0.0/0": 2})
        allowed0 = POLICY_VERDICTS.value(
            labels={"outcome": "allowed"})
        denied0 = POLICY_VERDICTS.value(labels={"outcome": "denied"})
        pkt = make_full_batch(endpoint=[0, 0], saddr=[1, 1],
                              daddr=[2, 2], sport=[999, 999],
                              dport=[80, 22])
        dp.process(pkt, now=10)
        dp.flush_telemetry()
        assert POLICY_VERDICTS.value(
            labels={"outcome": "allowed"}) == allowed0 + 1
        assert POLICY_VERDICTS.value(
            labels={"outcome": "denied"}) == denied0 + 1

    def test_kvstore_operations_counted(self):
        from cilium_tpu.kvstore.remote import RemoteBackend
        from cilium_tpu.kvstore.server import KVStoreServer
        srv = KVStoreServer(port=0).start()
        try:
            kv = RemoteBackend(port=srv.port)
            set0 = KVSTORE_OPERATIONS.value(
                labels={"backend": "remote", "op": "set"})
            get0 = KVSTORE_OPERATIONS.value(
                labels={"backend": "remote", "op": "get"})
            kv.set("a/b", b"1")
            kv.get("a/b")
            kv.get("a/missing")
            assert KVSTORE_OPERATIONS.value(
                labels={"backend": "remote", "op": "set"}) == set0 + 1
            assert KVSTORE_OPERATIONS.value(
                labels={"backend": "remote", "op": "get"}) == get0 + 2
            kv.close()
        finally:
            srv.shutdown()

    def test_etcd_operations_counted(self):
        from cilium_tpu.kvstore.etcd import EtcdBackend
        from cilium_tpu.kvstore.mini_etcd import MiniEtcd
        mini = MiniEtcd().start()
        try:
            kv = EtcdBackend(port=mini.port, lease_ttl=5)
            put0 = KVSTORE_OPERATIONS.value(
                labels={"backend": "etcd", "op": "kv-put"})
            rng0 = KVSTORE_OPERATIONS.value(
                labels={"backend": "etcd", "op": "kv-range"})
            kv.set("x", b"y")
            kv.get("x")
            assert KVSTORE_OPERATIONS.value(
                labels={"backend": "etcd", "op": "kv-put"}) == put0 + 1
            assert KVSTORE_OPERATIONS.value(
                labels={"backend": "etcd",
                        "op": "kv-range"}) >= rng0 + 1
            kv.close()
        finally:
            mini.shutdown()

    def test_proxy_upstream_time_http(self):
        import socket
        import socketserver
        from cilium_tpu.l7.socket_proxy import (ListenerContext,
                                                SocketProxy)

        ok = (b"HTTP/1.1 200 OK\r\ncontent-length: 2\r\n\r\nhi")

        class _Up(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        class _H(socketserver.BaseRequestHandler):
            def handle(self):
                data = b""
                while b"\r\n\r\n" not in data:
                    chunk = self.request.recv(4096)
                    if not chunk:
                        return
                    data += chunk
                self.request.sendall(ok)

        up = _Up(("127.0.0.1", 0), _H)
        threading.Thread(target=up.serve_forever, daemon=True).start()
        proxy = SocketProxy()
        before = PROXY_UPSTREAM_TIME.count(
            labels={"protocol": "http"})
        try:
            port = proxy.start_listener(0, ListenerContext(
                redirect_id="r1", parser_type="http",
                orig_dst=lambda peer: ("127.0.0.1",
                                       up.server_address[1])))
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=5) as s:
                s.sendall(b"GET / HTTP/1.1\r\nhost: a\r\n"
                          b"content-length: 0\r\n\r\n")
                resp = b""
                s.settimeout(5)
                while b"hi" not in resp:
                    chunk = s.recv(4096)
                    if not chunk:
                        break
                    resp += chunk
            assert b"200 OK" in resp
            deadline = time.time() + 5
            while time.time() < deadline and \
                    PROXY_UPSTREAM_TIME.count(
                        labels={"protocol": "http"}) == before:
                time.sleep(0.02)
            assert PROXY_UPSTREAM_TIME.count(
                labels={"protocol": "http"}) == before + 1
            assert PROXY_UPSTREAM_TIME.sum_value(
                labels={"protocol": "http"}) >= 0.0
        finally:
            proxy.shutdown()
            up.shutdown()
            up.server_close()


# ------------------------------------------------------- live-daemon e2e

@pytest.fixture
def agent(tmp_path):
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.daemon.rest import APIServer
    from cilium_tpu.utils.option import DaemonConfig
    d = Daemon(config=DaemonConfig(state_dir=""), builders=2)
    server = APIServer(d).start()
    yield d, server
    server.shutdown()
    d.shutdown()


RULES = [{
    "endpointSelector": {"matchLabels": {"id": "server"}},
    "ingress": [{
        "fromEndpoints": [{"matchLabels": {"id": "client"}}],
        "toPorts": [{"ports": [{"port": "80",
                                "protocol": "TCP"}]}]}],
    "labels": ["k8s:policy=obs-e2e"],
}]


def _get(server, path):
    import urllib.request
    with urllib.request.urlopen(server.base_url + path,
                                timeout=10) as r:
        return json.loads(r.read())


def _cli(server, *argv):
    from cilium_tpu.cli import main as cli_main
    out = io.StringIO()
    old = sys.stdout
    sys.stdout = out
    try:
        rc = cli_main(["--api", server.base_url, *argv])
    finally:
        sys.stdout = old
    return rc, out.getvalue()


class TestDaemonEndToEnd:
    def test_propagation_delay_and_trace_tree(self, agent):
        from cilium_tpu.datapath.engine import make_full_batch
        from cilium_tpu.policy.jsonio import rules_from_json
        d, server = agent
        d.endpoint_create(1, ipv4="10.200.0.21",
                          labels=["k8s:id=server"])
        d.endpoint_create(2, ipv4="10.200.0.22",
                          labels=["k8s:id=client"])
        before = POLICY_IMPLEMENTATION_DELAY.total_count()
        rev = d.policy_add(rules_from_json(json.dumps(RULES)))
        assert d.wait_for_policy_revision(rev)
        # no verdicts yet: the journey is still open
        assert POLICY_IMPLEMENTATION_DELAY.total_count() == before
        ep = d.endpoints.lookup(1)
        batch = make_full_batch(
            endpoint=[ep.table_slot], saddr=["10.200.0.22"],
            daddr=["10.200.0.21"], sport=[44000], dport=[80],
            direction=[0])
        verdict, _e, _i, _n = d.datapath.process(batch)
        verdict.block_until_ready()
        # acceptance: histogram count increments ...
        assert POLICY_IMPLEMENTATION_DELAY.total_count() == \
            before + 1
        # ... and /debug/traces shows the revision's span tree:
        # import -> compile -> device apply -> first verdict
        tree = _get(server, f"/debug/traces?revision={rev}")
        root = tree["spans"][0]
        assert root["name"] == f"policy.import rev={rev}"
        child_names = [c["name"] for c in root["children"]]
        assert "policy.compile" in child_names
        assert "policy.device-apply" in child_names
        assert f"policy.first-verdict rev={rev}" in child_names
        # compile happened before device-apply in the tree ordering
        assert child_names.index("policy.compile") < \
            child_names.index("policy.device-apply")
        # the delay is also in /metrics via REST
        text = _get_raw(server, "/metrics")
        assert "policy_implementation_delay_seconds_count" in text
        # the summaries list includes this trace
        summary = _get(server, "/debug/traces")
        assert any(t["trace-id"] == tree["trace-id"]
                   for t in summary["traces"])
        assert any(r["revision"] == rev
                   for r in summary["propagation"])

    def test_debug_pipeline_and_status_surfaces(self, agent):
        from cilium_tpu.datapath.engine import make_full_batch
        d, server = agent
        d.endpoint_create(1, ipv4="10.200.0.31",
                          labels=["k8s:id=a"])
        ep = d.endpoints.lookup(1)
        batch = make_full_batch(endpoint=[ep.table_slot],
                                saddr=["10.200.0.32"],
                                daddr=["10.200.0.31"], sport=[1],
                                dport=[80], direction=[0])
        d.datapath.process(batch)
        rep = _get(server, "/debug/pipeline")
        assert "engine-v4" in rep
        assert "dispatch" in rep["engine-v4"]
        st = _get(server, "/healthz")
        assert "map-pressure" in st
        assert "ct" in st["map-pressure"]["maps"]
        assert st["telemetry"]["tracing"]["enabled"] is True
        assert "cache-misses" in st["telemetry"]["jit"]
        # CLI surfaces
        rc, out = _cli(server, "status", "--verbose")
        assert rc == 0
        assert "JIT:" in out and "Tracing:" in out
        rc, out = _cli(server, "trace")
        assert rc == 0 and "TRACE" in out

    def test_cli_trace_tree_by_revision(self, agent):
        from cilium_tpu.policy.jsonio import rules_from_json
        d, server = agent
        d.endpoint_create(1, ipv4="10.200.0.41",
                          labels=["k8s:id=server"])
        rev = d.policy_add(rules_from_json(json.dumps(RULES)))
        assert d.wait_for_policy_revision(rev)
        rc, out = _cli(server, "trace", "--revision", str(rev))
        assert rc == 0
        assert f"policy.import rev={rev}" in out
        assert "policy.compile" in out
        # unknown revision: 404 surfaces as the CLI's typed APIError
        from cilium_tpu.cli import APIError
        with pytest.raises(APIError) as exc:
            _cli(server, "trace", "--revision", "99999")
        assert exc.value.status == 404

    def test_bugtool_contains_observability_members(self, agent,
                                                    tmp_path):
        import tarfile
        from cilium_tpu.bugtool import collect
        d, _server = agent
        path = collect(d, str(tmp_path / "bt.tar.gz"))
        with tarfile.open(path) as tar:
            names = [n.split("/", 1)[1] for n in tar.getnames()]
        for member in ("traces.json", "map-pressure.json",
                       "compile-telemetry.json", "pipeline.json"):
            assert member in names, names

    def test_tracing_disabled_config(self, tmp_path):
        from cilium_tpu.daemon import Daemon
        from cilium_tpu.utils.option import DaemonConfig
        d = Daemon(config=DaemonConfig(state_dir="",
                                       enable_tracing=False))
        try:
            assert d.datapath.telemetry_enabled is False
            assert d.tracer.enabled is False
            st = d.status()
            assert st["telemetry"]["tracing"]["enabled"] is False
        finally:
            d.shutdown()
            # the tracer is process-global: re-enable for the rest of
            # the test session
            d.tracer.configure(enabled=True)


def _get_raw(server, path):
    import urllib.request
    with urllib.request.urlopen(server.base_url + path,
                                timeout=10) as r:
        return r.read().decode()
