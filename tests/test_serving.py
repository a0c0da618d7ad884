"""The latency-tier serving path (datapath/serving.py): shared
continuous micro-batching with async double-buffered dispatch.

Pins the PR's contracts:

- the power-of-two bucket ladder is ONE helper shared by the verdict
  service, the DFA row bucketing and the serving dispatcher (bounded
  jit cache by construction);
- concurrent submitters from different endpoints get bit-exact
  verdicts vs the synchronous oracle (x3 seeds) and every ticket maps
  back to exactly its submitted frames;
- a dispatch that raises fails closed — denies exactly the frames in
  that batch, leaves every other batch untouched;
- with the shared dispatcher serializing device work, the engine-lock
  convoy is gone: lock-wait no longer dominates dispatch under
  concurrent callers, and the serving stages expose exactly one
  blocking boundary ("complete", with its device->host copy "d2h");
- one launch records each of its stages once, on the profiler's
  timeline too, under one launch number its tickets carry; the
  supervisor's oracle refresh is a span with its four parts.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from bench import build_config1
from cilium_tpu.datapath.engine import Datapath, make_full_batch
from cilium_tpu.datapath.events import DROP_POLICY
from cilium_tpu.datapath.serving import (ContinuousDispatcher,
                                         VerdictDispatcher)
from cilium_tpu.utils.bucketing import bucket_size


# ----------------------------------------------------------- bucket ladder

def test_bucket_ladder_pinned():
    """Bucket boundaries are load-bearing: every jitted program's
    cache size is O(log B) only because these exact edges hold."""
    assert bucket_size(0) == 16
    assert bucket_size(1) == 16
    assert bucket_size(16) == 16
    assert bucket_size(17) == 32
    assert bucket_size(255) == 256
    assert bucket_size(256) == 256
    assert bucket_size(257) == 512
    assert bucket_size(4096) == 4096
    assert bucket_size(4097) == 8192
    assert bucket_size(3, min_rows=1) == 4
    with pytest.raises(AssertionError):
        bucket_size(4, min_rows=12)  # non-pow2 floor forks the ladder


def test_bucket_helper_is_shared_across_tiers():
    import cilium_tpu.verdict_service as vs
    from cilium_tpu.ops.dfa_ops import bucket_rows
    assert vs._bucket is bucket_size
    data = np.zeros((17, 8), np.int32)
    assert bucket_rows(data).shape[0] == bucket_size(17)
    assert bucket_rows(np.zeros((5, 8), np.int32),
                       min_rows=4).shape[0] == bucket_size(5, 4)


# ------------------------------------------------------------ test helpers

def _load_dp(telemetry=False, n_rules=40, n_endpoints=8):
    states, prefixes = build_config1(n_rules=n_rules,
                                     n_endpoints=n_endpoints)
    dp = Datapath(ct_slots=1 << 12)
    dp.telemetry_enabled = telemetry
    dp.load_policy(states, revision=1, ipcache_prefixes=prefixes)
    return dp


_SPORT_SEQ = [20000]


def _chunk(rng, n, n_endpoints=8):
    """One SoA record chunk (PacketRing pop_batch layout).  Sports are
    globally unique so no 5-tuple ever repeats: conntrack state can
    then never couple concurrent submitters' verdicts."""
    base = _SPORT_SEQ[0]
    _SPORT_SEQ[0] += n
    return {
        "endpoint": rng.integers(0, n_endpoints, n).astype(np.int32),
        "saddr": rng.integers(0, 1 << 32, n,
                              dtype=np.uint32).view(np.int32),
        "daddr": rng.integers(0, 1 << 32, n,
                              dtype=np.uint32).view(np.int32),
        "sport": ((base + np.arange(n)) % 64000 + 1024
                  ).astype(np.int32),
        "dport": rng.integers(1, 65536, n).astype(np.int32),
        "proto": np.full(n, 6, np.int32),
        "direction": np.ones(n, np.int32),
        "tcp_flags": np.full(n, 0x02, np.int32),
        "is_fragment": np.zeros(n, np.int32),
        "length": np.full(n, 256, np.int32),
    }


def _oracle_verdicts(oracle_dp, chunk, n):
    """The synchronous reference: the same records, alone, unpadded,
    through a pristine engine."""
    pkt = make_full_batch(**{k: v[:n] for k, v in chunk.items()})
    v, _e, i, _nat = oracle_dp.process(pkt)
    return (np.asarray(v).astype(np.int32),
            np.asarray(i).astype(np.int32))


# ------------------------------------------- oracle parity under concurrency

@pytest.mark.parametrize("seed", [3, 5, 7])
def test_concurrent_submitters_bit_exact_vs_sync_oracle(seed):
    dp = _load_dp()
    oracle = _load_dp()
    disp = VerdictDispatcher(dp, max_batch=4096, lane=f"par{seed}")
    rng = np.random.default_rng(seed)
    n_threads, chunks_per = 4, 5
    chunks = [[_chunk(rng, int(rng.integers(1, 300)))
               for _ in range(chunks_per)] for _ in range(n_threads)]
    results = {}
    errors = []

    def submitter(tid):
        try:
            tickets = [disp.submit_records(c, len(c["sport"]))
                       for c in chunks[tid]]
            for ci, t in enumerate(tickets):
                v, i = t.result(timeout=120)
                assert t.error is None, t.error
                results[(tid, ci)] = (v, i)
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=submitter, args=(tid,))
               for tid in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors, errors
    try:
        for tid in range(n_threads):
            for ci, chunk in enumerate(chunks[tid]):
                n = len(chunk["sport"])
                v, i = results[(tid, ci)]
                assert v.shape == (n,) and i.shape == (n,)
                ov, oi = _oracle_verdicts(oracle, chunk, n)
                np.testing.assert_array_equal(v, ov)
                np.testing.assert_array_equal(i, oi)
        st = disp.stats()
        assert st["frames"] == n_threads * chunks_per
        assert st["errors"] == 0
    finally:
        disp.close()


# ------------------------------------------------- ticket <-> item mapping

def test_core_tickets_map_back_to_their_items():
    """200 items from 8 threads through a host-only core: every ticket
    resolves to exactly f(its own item), regardless of how the
    dispatcher grouped the launches."""
    disp = ContinuousDispatcher(
        launch=lambda items, total: list(items),
        finalize=lambda handle, weights: [x * 2 + 1 for x in handle],
        deny=lambda item: None, max_batch=16, window=0.002,
        lane="map-test")
    out = {}

    def run(base):
        for k in range(25):
            item = base + k
            out[item] = disp.submit(item)
        # resolve after all submits: launches interleave across threads

    threads = [threading.Thread(target=run, args=(i * 1000,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    try:
        for item, ticket in out.items():
            assert ticket.result(timeout=30) == item * 2 + 1
            assert ticket.error is None
        assert disp.batches >= 200 / 16  # max_batch actually bounded
    finally:
        disp.close()


# ----------------------------------------------------------- fail closed

def test_failed_dispatch_denies_exactly_that_batch():
    def launch(items, total):
        if any(it == "poison" for it in items):
            raise RuntimeError("engine down")
        return list(items)

    disp = ContinuousDispatcher(
        launch=launch,
        finalize=lambda handle, weights: [True] * len(handle),
        deny=lambda item: False, max_batch=64, window=0.002,
        lane="fc-test")
    try:
        good1 = [disp.submit(f"a{i}") for i in range(4)]
        assert all(t.result(timeout=30) is True for t in good1)
        bad = [disp.submit("poison" if i == 2 else f"b{i}")
               for i in range(4)]
        for t in bad:
            assert t.result(timeout=30) is False   # fail closed
            assert isinstance(t.error, RuntimeError)
        good2 = [disp.submit(f"c{i}") for i in range(4)]
        for t in good2:
            assert t.result(timeout=30) is True    # untouched
            assert t.error is None
        assert disp.errors == 1
    finally:
        disp.close()


def test_engine_lane_fails_closed_without_policy():
    """The engine-backed lane's deny is a real DROP_POLICY verdict for
    exactly the submitted records."""
    dp = Datapath(ct_slots=1 << 10)  # no policy loaded -> raises
    disp = VerdictDispatcher(dp, lane="no-policy")
    try:
        rng = np.random.default_rng(1)
        t = disp.submit_records(_chunk(rng, 9), 9)
        v, i = t.result(timeout=30)
        assert t.error is not None
        assert v.shape == (9,) and (v == DROP_POLICY).all()
        assert (i == 0).all()
    finally:
        disp.close()


def test_closed_dispatcher_fails_closed_immediately():
    disp = ContinuousDispatcher(
        launch=lambda items, total: items,
        finalize=lambda handle, weights: [True] * len(handle),
        deny=lambda item: False, lane="closed-test")
    disp.close()
    t = disp.submit("x")
    assert t.result(timeout=5) is False
    assert t.error is not None


# --------------------------------------------- admission control (shed)

def test_bounded_queue_sheds_overflow_fail_closed():
    """The pending queue is weight-bounded: overflow is shed at
    submit time with a ShedError (reason "overflow") and a real deny
    result — never queued, never dispatched."""
    from cilium_tpu.datapath.serving import ShedError
    release = threading.Event()

    def slow_launch(items, total):
        release.wait(5.0)
        return list(items)

    disp = ContinuousDispatcher(
        slow_launch, lambda h, w: [True] * len(h),
        deny=lambda item: False, max_batch=4, max_pending=8,
        lane="shed-ovl")
    try:
        tickets = [disp.submit(i) for i in range(64)]
        shed = [t for t in tickets if isinstance(t.error, ShedError)]
        assert shed and all(t.error.reason == "overflow"
                            and t.value is False for t in shed)
        # the bound held: never more than max_pending queued
        assert disp.max_pending_seen <= 8
        release.set()
        accepted = [t for t in tickets if t.error is None
                    or not isinstance(t.error, ShedError)]
        for t in accepted:
            assert t.result(timeout=30) is True
        assert disp.stats()["shed"]["overflow"] == len(shed)
    finally:
        release.set()
        disp.close()


def test_expired_deadline_sheds_at_drain_time():
    from cilium_tpu.datapath.serving import ShedError
    gate = threading.Event()

    def gated_launch(items, total):
        gate.wait(5.0)
        return list(items)

    disp = ContinuousDispatcher(
        gated_launch, lambda h, w: [True] * len(h),
        deny=lambda item: False, max_batch=2, lane="shed-dl")
    try:
        head = disp.submit("head")          # occupies the dispatcher
        doomed = [disp.submit(i, deadline=0.01) for i in range(8)]
        time.sleep(0.05)                    # let the deadlines lapse
        gate.set()
        assert head.result(timeout=30) is True
        shed = [t for t in doomed
                if isinstance(t.error, ShedError)
                and t.error.reason == "deadline"]
        for t in doomed:
            t.result(timeout=30)
        assert shed, "expired work must be shed, not dispatched"
        assert all(t.value is False for t in shed)
    finally:
        gate.set()
        disp.close()


def test_overload_watermark_hysteresis():
    """The dataplane_overloaded gauge flips at the high watermark and
    clears only at the low watermark (hysteresis, no flapping)."""
    from cilium_tpu.utils.metrics import DATAPLANE_OVERLOADED
    release = threading.Event()

    def slow_launch(items, total):
        release.wait(10.0)
        return list(items)

    disp = ContinuousDispatcher(
        slow_launch, lambda h, w: [True] * len(h),
        deny=lambda item: False, max_batch=1, max_pending=100,
        overload_high=0.5, overload_low=0.1, lane="hyst")
    try:
        tickets = [disp.submit(i) for i in range(80)]
        assert disp.overloaded                      # >= 50 queued
        assert DATAPLANE_OVERLOADED.value(
            labels={"lane": "hyst"}) == 1.0
        release.set()
        for t in tickets:
            t.result(timeout=60)
        deadline = time.monotonic() + 10
        while disp.overloaded and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not disp.overloaded                  # drained past low
        assert DATAPLANE_OVERLOADED.value(
            labels={"lane": "hyst"}) == 0.0
    finally:
        release.set()
        disp.close()


def test_verdict_batcher_pushes_back_when_overloaded():
    """VerdictBatcher.check answers an immediate fail-closed deny
    while its lane is overloaded instead of queuing more work."""
    from cilium_tpu.l7.parser import VerdictBatcher
    release = threading.Event()

    def slow_check(items):
        release.wait(5.0)
        return [True] * len(items)

    async def run():
        vb = VerdictBatcher(slow_check, max_wait=0.0, max_batch=2,
                            max_pending=4, name="vb-push")
        try:
            # wedge the lane: two launches in flight, the completion
            # blocked in slow_check — nothing drains anymore
            head = [asyncio.ensure_future(vb.check(i))
                    for i in range(3)]
            await asyncio.sleep(0.05)
            # now fill the queue behind the blocked lane
            fill = [asyncio.ensure_future(vb.check(100 + i))
                    for i in range(3)]
            await asyncio.sleep(0.05)
            assert vb.overloaded            # >= high watermark queued
            pushed_back = await vb.check("late")
            assert pushed_back is False     # immediate deny, no queue
            release.set()
            results = await asyncio.gather(*(head + fill))
            # everything accepted before overload resolved honestly
            assert all(results)
            return True
        finally:
            release.set()
            vb.close()

    assert asyncio.run(run())


# ------------------------------------------------- lock convoy + stages

def test_lock_wait_no_longer_dominates_under_concurrent_callers():
    from cilium_tpu.observability import stages
    stages.reset()
    dp = _load_dp(telemetry=True)
    disp = dp.serving()
    assert disp is dp.serving()  # one shared lane per engine
    rng = np.random.default_rng(11)
    errors = []

    def caller(tid):
        try:
            for _ in range(6):
                t = disp.submit_records(_chunk(rng, 256), 256)
                t.result(timeout=120)
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors, errors
    rep = stages.pipeline_report()
    eng = rep["engine-v4"]
    # the convoy is gone: one dispatcher thread owns device dispatch,
    # so waiting on the engine lock is negligible next to dispatch
    assert eng["lock-wait"]["total-s"] < 0.5 * eng["dispatch"]["total-s"], eng
    srv = rep[disp.family]
    assert set(srv) <= {"queue-wait", "pack", "dispatch", "complete",
                        "handoff", "d2h", "resolve"}
    blocking = sorted(s for s, d in srv.items()
                      if d["blocking-boundary"])
    # exactly ONE blocking boundary on the serving path, and it is the
    # ticket-completion transfer (one batch behind the launch front):
    # "complete", and the device->host copy inside it
    assert blocking == ["complete", "d2h"], srv


def test_one_launch_records_each_stage_once(tmp_path):
    """A supervised lane's single launch: pack, dispatch, complete,
    handoff, d2h and resolve each once, the annotated ones on the
    profile under the launch number its ticket carries."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from cilium_tpu.datapath.supervisor import DeviceSupervisor
    from cilium_tpu.observability import stages
    dp = _load_dp(telemetry=True)
    disp = VerdictDispatcher(dp, supervisor=DeviceSupervisor(dp),
                             lane=f"spans{time.monotonic_ns()}")
    rng = np.random.default_rng(5)
    try:
        disp.submit_records(_chunk(rng, 8), 8).result(timeout=120)
        jax.profiler.start_trace(str(tmp_path))
        try:
            t = disp.submit_records(_chunk(rng, 8), 8)
            t.result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        disp.close()
    assert t.error is None and t.launch == disp.batches == 2
    srv = stages.pipeline_report()[disp.family]
    for name in ("queue-wait", "pack", "dispatch", "complete",
                 "handoff", "d2h", "resolve"):
        assert srv[name]["count"] == 2, (name, srv)
    assert srv["handoff"]["total-s"] > 0
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    seen = {}
    for p in ProfileData.from_file(path).planes:
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(disp.family + "."):
                    seen.setdefault(e.name, []).append(
                        dict(e.stats)["launch"])
    assert seen == {f"{disp.family}.{n}": [2] for n in
                    ("dispatch", "pack", "complete", "d2h", "resolve")}


def test_lane_counts_outcomes_from_its_own_host_copy():
    """policy_verdicts_total counts every record the lane answers, from
    the verdicts it copied back; the engine queues no second read."""
    from cilium_tpu.utils.metrics import POLICY_VERDICTS
    dp = _load_dp(telemetry=True)
    disp = VerdictDispatcher(dp, lane=f"outcomes{time.monotonic_ns()}")
    outcomes = ("allowed", "denied", "redirected")
    before = {o: POLICY_VERDICTS.value(labels={"outcome": o})
              for o in outcomes}
    rng = np.random.default_rng(9)
    try:
        v, _i = disp.submit_records(_chunk(rng, 40), 40).result(
            timeout=120)
    finally:
        disp.close()
    want = {"allowed": int((v == 0).sum()), "denied": int((v < 0).sum()),
            "redirected": int((v > 0).sum())}
    assert {o: POLICY_VERDICTS.value(labels={"outcome": o}) - before[o]
            for o in outcomes} == want
    assert not dp._pending_verdicts


def test_oracle_refresh_is_a_span_with_its_parts():
    from cilium_tpu.datapath.supervisor import DeviceSupervisor
    from cilium_tpu.observability import stages
    dp = _load_dp(telemetry=True)
    parts = ("oracle-refresh", "copy-states", "compile-lpm",
             "snapshot-ct", "decode-ct")

    def read():
        rep = stages.pipeline_report().get("supervisor", {})
        zero = {"count": 0, "total-s": 0.0}
        return {p: (rep.get(p, zero)["count"], rep.get(p, zero)["total-s"])
                for p in parts}

    before = read()
    assert DeviceSupervisor(dp).oracle.refresh()
    after = read()
    assert {p: after[p][0] for p in parts} == \
        {p: before[p][0] + 1 for p in parts}
    took = {p: after[p][1] - before[p][1] for p in parts}
    # the four parts run inside the span (totals are rounded to 1 us)
    assert sum(took[p] for p in parts[1:]) <= took["oracle-refresh"] + 1e-5
    # telemetry off: the refresh records nothing
    dp.telemetry_enabled = False
    DeviceSupervisor(dp).oracle.refresh()
    assert read() == after


# -------------------------------------------- VerdictBatcher split path

def test_verdict_batcher_dispatch_split_parity():
    from cilium_tpu.l7.http import HTTPPolicyEngine, HTTPRequest
    from cilium_tpu.l7.parser import VerdictBatcher
    from cilium_tpu.policy.api import PortRuleHTTP
    eng = HTTPPolicyEngine([PortRuleHTTP(method="GET",
                                         path="/public/.*")])
    split = eng.dispatch_split()
    assert split is not None
    reqs = [HTTPRequest(method="GET",
                        path=f"/public/{i}" if i % 2 == 0
                        else f"/admin/{i}")
            for i in range(32)]

    async def run():
        vb = VerdictBatcher(lambda rs: list(eng.check(rs)),
                            max_wait=0.002, dispatch_split=split)
        res = await asyncio.gather(*[vb.check(r) for r in reqs])
        return vb, res

    vb, res = asyncio.run(run())
    try:
        assert res == [i % 2 == 0 for i in range(32)]
        assert vb.checked == 32 and vb.batches < 32
        # parity with the one-shot engine path
        np.testing.assert_array_equal(np.array(res), eng.check(reqs))
    finally:
        vb.close()
    # allow-all engines have no device program to split
    assert HTTPPolicyEngine([]).dispatch_split() is None
    from cilium_tpu.l7.dns import DNSPolicyEngine
    assert DNSPolicyEngine([]).dispatch_split() is None


def test_dns_dispatch_split_parity():
    from cilium_tpu.l7.dns import DNSPolicyEngine
    from cilium_tpu.policy.api import FQDNSelector
    eng = DNSPolicyEngine([FQDNSelector(match_pattern="*.example.com")])
    dispatch, finalize = eng.dispatch_split()
    names = ["a.example.com", "b.other.org", "c.example.com"]
    handle = dispatch(names)
    got = finalize(handle, len(names))
    np.testing.assert_array_equal(got, eng.allowed(names))


# ------------------------------------- fused flows/provenance still correct

def test_serving_with_flows_and_provenance_parity():
    """The packed serving step must carry the SAME fused program
    tails as process(): Hubble flow aggregation scatters and
    provenance outputs, bit-exact verdicts included."""
    dp = _load_dp()
    dp.enable_flow_aggregation(slots=1 << 10)
    dp.enable_provenance()
    oracle = _load_dp()
    oracle.enable_flow_aggregation(slots=1 << 10)
    oracle.enable_provenance()
    disp = VerdictDispatcher(dp, lane="fused")
    rng = np.random.default_rng(9)
    try:
        chunk = _chunk(rng, 100)
        t = disp.submit_records(chunk, 100)
        v, i = t.result(timeout=120)
        assert t.error is None
        ov, oi = _oracle_verdicts(oracle, chunk, 100)
        np.testing.assert_array_equal(v, ov)
        np.testing.assert_array_equal(i, oi)
        # the flow table really was fused into the packed launch
        assert dp.flow_stats()["occupied"] > 0 or \
            dp.flow_stats().get("lost", 0) > 0, dp.flow_stats()
        assert dp.last_provenance is not None
    finally:
        disp.close()


# --------------------------------------------------- double-buffer overlap

def test_steady_state_keeps_batches_in_flight():
    """Sustained submission must overlap: with depth 2 the dispatcher
    resolves ticket N while N+1 is already launched — observable as
    strictly fewer completes than submissions at any point mid-burst,
    and total correctness at the end."""
    dp = _load_dp()
    disp = VerdictDispatcher(dp, max_batch=256, lane="overlap")
    rng = np.random.default_rng(2)
    try:
        chunks = [_chunk(rng, 64) for _ in range(12)]
        tickets = [disp.submit_records(c, 64) for c in chunks]
        vs = [t.result(timeout=120) for t in tickets]
        assert all(t.error is None for t in tickets)
        oracle = _load_dp()
        for c, (v, i) in zip(chunks, vs):
            ov, oi = _oracle_verdicts(oracle, c, 64)
            np.testing.assert_array_equal(v, ov)
        assert disp.stats()["batches"] >= 3  # really multiple launches
    finally:
        disp.close()
