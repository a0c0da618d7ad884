"""The sharded verdict dataplane (parallel/sharded.py) on the
8-virtual-device mesh: placement, oracle parity with flows+provenance
fused, per-shard fault domains (shard-kill journey), shard-aware
delta-apply, per-shard pressure, and the supervision-off
byte-identical contract.

The acceptance journey: with (dp=2, ep=4), a fatal fault injected into
one shard leaves the other shards serving bit-exact device verdicts,
the failed shard serves fail-static with established flows preserved,
and per-shard gated recovery closes without a global pause.
"""

import time

import numpy as np
import pytest

from bench import build_config1
from cilium_tpu.datapath.engine import Datapath, make_full_batch
from cilium_tpu.parallel import (ShardedDatapath, ShardedTableManager,
                                 ep_submesh, make_mesh, shard_batch)
from cilium_tpu.utils.faultinject import DeviceFaultInjector
from cilium_tpu.utils.metrics import (DATAPLANE_RECOVERIES,
                                      DATAPLANE_SHARD_FAULTS,
                                      DATAPLANE_SHARD_MODE)

N_ENDPOINTS = 8
N_SHARDS = 4

_STATES, _PREFIXES = build_config1(n_rules=30, n_endpoints=N_ENDPOINTS)
_SPORT = [30000]


def _chunk(rng, n, hit_frac=0.5):
    """SoA record chunk spanning all endpoints; ``hit_frac`` of daddrs
    land inside installed ipcache prefixes so a share ALLOWs (and
    creates CT entries)."""
    base = _SPORT[0]
    _SPORT[0] += n
    daddr = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    cidrs = list(_PREFIXES)
    for j in range(int(n * hit_frac)):
        a = cidrs[j % len(cidrs)].split("/")[0].split(".")
        daddr[j] = (int(a[0]) << 24) | (int(a[1]) << 16) | \
            (int(a[2]) << 8) | 7
    return {
        "endpoint": rng.integers(0, N_ENDPOINTS, n).astype(np.int32),
        "saddr": rng.integers(0, 1 << 32, n,
                              dtype=np.uint32).view(np.int32),
        "daddr": daddr.view(np.int32),
        "sport": ((base + np.arange(n)) % 64000 + 1024
                  ).astype(np.int32),
        "dport": rng.integers(1, 65536, n).astype(np.int32),
        "proto": np.full(n, 6, np.int32),
        "direction": np.ones(n, np.int32),
        "tcp_flags": np.full(n, 0x02, np.int32),
        "is_fragment": np.zeros(n, np.int32),
        "length": np.full(n, 256, np.int32),
    }


def _cp(c):
    return {k: v.copy() for k, v in c.items()}


@pytest.fixture(scope="module")
def plane():
    """(dp=2, ep=4) sharded plane with flows AND provenance fused into
    every shard's compiled program — the full-pipeline configuration
    the acceptance journey runs under."""
    p = ShardedDatapath(n_shards=N_SHARDS, ct_slots=1 << 10)
    p.telemetry_enabled = False
    p.configure_supervision(enabled=True, watchdog_s=5.0,
                            failure_threshold=1, reset_s=0.05)
    p.enable_flow_aggregation(slots=1 << 10)
    p.enable_provenance()
    p.load_policy(_STATES, revision=1, ipcache_prefixes=_PREFIXES)
    yield p
    p.serving().close()


@pytest.fixture(scope="module")
def oracle():
    """Single-engine compiler oracle over the same states, flows +
    provenance fused the same way."""
    dp = Datapath(ct_slots=1 << 10)
    dp.telemetry_enabled = False
    dp.enable_flow_aggregation(slots=1 << 10)
    dp.enable_provenance()
    dp.load_policy(_STATES, revision=1, ipcache_prefixes=_PREFIXES)
    return dp


# ------------------------------------------------------------- mesh fixes

def test_make_mesh_overprovision_raises():
    import jax
    n = len(jax.devices())
    with pytest.raises(ValueError, match="available"):
        make_mesh(n + 1)
    with pytest.raises(ValueError, match="divisible"):
        make_mesh(n, ep_parallel=3 if n % 3 else n + 1)


def test_ep_submesh_bounds_and_shape():
    mesh = make_mesh(ep_parallel=4)
    sub = ep_submesh(mesh, 2)
    assert sub.devices.shape == (mesh.devices.shape[0], 1)
    assert list(sub.devices[:, 0]) == list(mesh.devices[:, 2])
    with pytest.raises(ValueError):
        ep_submesh(mesh, 4)


def test_shard_batch_places_only_batch_leading_leaves():
    import jax.numpy as jnp
    mesh = make_mesh(ep_parallel=1)   # all devices on dp
    dp = mesh.devices.shape[0]
    b = dp * 4
    tree = {"pkt": jnp.zeros((b, 3), jnp.int32),
            "vec": jnp.zeros(b, jnp.int32),
            "table": jnp.zeros((b + 1, 5), jnp.int32),
            "scalar": jnp.int32(7)}
    placed = shard_batch(mesh, tree, batch=b)
    from cilium_tpu.parallel.mesh import DP_AXIS
    assert placed["pkt"].sharding.spec[0] == DP_AXIS
    assert placed["vec"].sharding.spec[0] == DP_AXIS
    # NOT [B]-leading: replicated, never sliced along the wrong axis
    assert placed["table"].sharding.is_fully_replicated
    assert placed["scalar"].sharding.is_fully_replicated


# ------------------------------------------------------- placement layout

def test_shard_tables_reside_on_their_own_column(plane):
    mesh = plane.mesh
    for k, eng in enumerate(plane.shards):
        want = {d.id for d in mesh.devices[:, k]}
        tbl = eng._tables.datapath.key_id
        assert {d.id for d in tbl.sharding.device_set} == want
        # the packed dispatch buffers and CT pack live on the column too
        import jax
        for buf in eng._tbufs4 + tuple(
                jax.tree_util.tree_leaves(eng.ct.state)):
            assert {d.id for d in buf.sharding.device_set} == want


# ------------------------------------------------------------ oracle parity

@pytest.mark.parametrize("seed", [3, 5])
def test_sharded_oracle_parity_flows_and_provenance(plane, oracle,
                                                    seed):
    """Verdict AND identity parity vs the single-engine compiler
    oracle under the (2, 4) mesh, with the flow-aggregation and
    provenance stages fused into both compiled programs; provenance
    tiers and decoded matched rules agree per packet."""
    rng = np.random.default_rng(seed)
    c = _chunk(rng, 96)
    v, i = plane.classify_records(_cp(c), 96)
    pkt = make_full_batch(**c)
    dv, _e, di, _n = oracle.process(pkt)
    dv, di = np.asarray(dv), np.asarray(di)
    np.testing.assert_array_equal(v, dv)
    np.testing.assert_array_equal(i, di)

    # provenance: per-shard tiers/slots mirror the oracle's
    otier = np.asarray(oracle.last_provenance.tier)
    oslot = np.asarray(oracle.last_provenance.match_slot)
    odecode = oracle.rule_decoder()
    owner = c["endpoint"] % N_SHARDS
    for k, eng in enumerate(plane.shards):
        idx = np.flatnonzero(owner == k)
        if idx.size == 0:
            continue
        prov = eng.last_provenance
        assert prov is not None
        tier_k = np.asarray(prov.tier)[:idx.size]
        slot_k = np.asarray(prov.match_slot)[:idx.size]
        np.testing.assert_array_equal(tier_k, otier[idx])
        decode = eng.rule_decoder()
        for row, j in enumerate(idx.tolist()):
            mine, theirs = decode(slot_k[row]), odecode(oslot[j])
            if theirs is None:
                assert mine is None
                continue
            assert mine is not None
            # shard-local endpoint row maps back to the global slot
            assert mine["endpoint-slot"] * N_SHARDS + k == \
                theirs["endpoint-slot"]
            for f in ("identity", "dport", "proto", "direction",
                      "proxy-port"):
                assert mine[f] == theirs[f], (f, mine, theirs)
    # the fused flow tables saw the traffic (shard-local residency)
    assert sum(s["occupied"] for s in
               plane.flow_stats()["per-shard"].values()
               if s) > 0


def test_policy_replay_routes_global_slots(plane, oracle):
    eps = list(range(N_ENDPOINTS))
    ids = [300 + e for e in eps]
    rows = plane.policy_replay(eps, ids, [80] * len(eps),
                               [6] * len(eps), [1] * len(eps))
    orows = oracle.policy_replay(eps, ids, [80] * len(eps),
                                 [6] * len(eps), [1] * len(eps))
    for r, o in zip(rows, orows):
        assert r["endpoint-slot"] == o["endpoint-slot"]
        assert r["shard"] == r["endpoint-slot"] % N_SHARDS
        assert r["verdict"] == o["verdict"]
        assert r["tier"] == o["tier"]


# ------------------------------------------------------ shard-kill journey

@pytest.mark.parametrize("seed,victim", [(11, 1), (13, 2)])
def test_shard_kill_journey(plane, oracle, seed, victim):
    """Fatal fault on one shard: siblings stay bit-exact on device
    (breakers closed, no global pause), the victim serves fail-static
    with established flows preserved, and the gated per-shard recovery
    closes with dataplane_recoveries_total incremented."""
    rng = np.random.default_rng(seed)
    lane = plane.serving()
    sup = lane.lanes[victim].supervisor

    c1 = _chunk(rng, 64)
    t = lane.submit_records(_cp(c1), 64)
    v1, _i1 = t.result(timeout=120)
    assert t.error is None
    sup.oracle.refresh()
    # feed the oracle the same pre-fault traffic so CT views agree
    dv1 = np.asarray(oracle.process(make_full_batch(**c1))[0])
    np.testing.assert_array_equal(v1, dv1)

    rec_before = DATAPLANE_RECOVERIES.total()
    faults_before = DATAPLANE_SHARD_FAULTS.value(
        labels={"shard": str(victim), "kind": "fatal"})
    inj = DeviceFaultInjector()
    sup.install_fault_hook(inj)
    assert inj.shard == victim
    inj.fail_launch(times=1, fatal=True)

    kill = _chunk(rng, 16)
    kill["endpoint"] = np.full(16, victim, np.int32)
    t = lane.submit_records(_cp(kill), 16)
    t.result(timeout=120)
    assert t.error is None                 # fail-static, not denied
    st = plane.supervision_status()
    assert st["mode"] == "degraded"
    assert st["degraded-shards"] == [victim]
    assert DATAPLANE_SHARD_MODE.value(
        labels={"shard": str(victim)}) == 1.0
    assert DATAPLANE_SHARD_FAULTS.value(
        labels={"shard": str(victim), "kind": "fatal"}) == \
        faults_before + 1

    # sibling shards: bit-exact on device through the fault, breakers
    # closed, dispatchers still launching (no global pause)
    sibling_batches = {k: lane.lanes[k].batches
                      for k in range(N_SHARDS) if k != victim}
    fresh = _chunk(rng, 96)
    t = lane.submit_records(_cp(fresh), 96)
    v2, i2 = t.result(timeout=120)
    assert t.error is None
    dv2, _e, di2, _n = oracle.process(make_full_batch(**fresh))
    dv2, di2 = np.asarray(dv2), np.asarray(di2)
    mask = (fresh["endpoint"] % N_SHARDS) != victim
    np.testing.assert_array_equal(v2[mask], dv2[mask])
    np.testing.assert_array_equal(i2[mask], di2[mask])
    # victim rows: fail-static new-flow 'oracle' policy is bit-exact
    # with the device decision too (PR 8 property, now per shard)
    np.testing.assert_array_equal(v2[~mask], dv2[~mask])
    for k, before in sibling_batches.items():
        assert lane.lanes[k].supervisor.breaker.state == "closed"
        assert lane.lanes[k].batches > before

    # established flows on the victim keep their verdicts
    t = lane.submit_records(_cp(c1), 64)
    vs, _ = t.result(timeout=120)
    assert t.error is None
    vmask = (c1["endpoint"] % N_SHARDS) == victim
    allowed = vmask & (v1 >= 0)
    if allowed.any():
        np.testing.assert_array_equal(vs[allowed],
                                      np.maximum(v1[allowed], 0))
    assert sup.fail_static_records > 0

    # heal -> per-shard gated recovery (rebuild + drift replay on the
    # victim's slice only) closes the breaker, counts the recovery
    inj.heal()
    deadline = time.monotonic() + 20.0
    while sup.mode != "ok" and time.monotonic() < deadline:
        time.sleep(0.05)
        lane.submit_records(_cp(kill), 16).result(timeout=120)
    assert sup.mode == "ok"
    assert DATAPLANE_RECOVERIES.total() > rec_before
    assert plane.supervision_status()["mode"] == "ok"
    assert DATAPLANE_SHARD_MODE.value(
        labels={"shard": str(victim)}) == 0.0
    # drain the oracle's CT of this test's flows is unnecessary: each
    # parametrization uses fresh sports (module-global counter)


# --------------------------------------------- shard-aware delta-apply

def test_sharded_table_manager_touches_only_owning_shard():
    from cilium_tpu.policy.mapstate import (INGRESS, PolicyKey,
                                            PolicyMapState,
                                            PolicyMapStateEntry)
    mgr = ShardedTableManager(N_SHARDS)
    slots = {eid: mgr.attach(eid) for eid in range(8)}
    # interleaved global slots: shard derivable by modulo
    for eid, g in slots.items():
        assert g % N_SHARDS == eid % N_SHARDS
        assert mgr.slot_of(eid) == g
    st = PolicyMapState()
    st[PolicyKey(identity=300, dest_port=443, nexthdr=6,
                 direction=INGRESS)] = PolicyMapStateEntry()
    owner = mgr.shard_of_endpoint(5)
    before = {k: (m.generation, m.key_id, m.key_meta, m.value)
              for k, m in enumerate(mgr.shards)}
    out = mgr.sync_endpoint(5, st, revision=2)
    assert out["shard"] == owner
    for k, m in enumerate(mgr.shards):
        gen, kid, kmeta, val = before[k]
        if k == owner:
            assert m.key_id is not kid    # the owning slice changed
        else:
            # untouched shards: same generation, same tensors
            assert m.generation == gen
            assert m.key_id is kid
            assert m.key_meta is kmeta
            assert m.value is val
    merged = mgr.states_by_slot()
    assert merged[slots[5]].keys() == st.keys()


def test_sharded_manager_drives_plane_refresh():
    mgr = ShardedTableManager(N_SHARDS)
    p = ShardedDatapath(n_shards=N_SHARDS, ct_slots=1 << 8)
    p.telemetry_enabled = False
    p.use_table_manager(mgr, ipcache_prefixes={"10.0.0.0/8": 300})
    from cilium_tpu.policy.mapstate import (INGRESS, PolicyKey,
                                            PolicyMapState,
                                            PolicyMapStateEntry)
    eid = 6
    g = mgr.attach(eid)
    st = PolicyMapState()
    st[PolicyKey(identity=300, dest_port=5432, nexthdr=6,
                 direction=INGRESS)] = PolicyMapStateEntry()
    mgr.sync_endpoint(eid, st, revision=3)
    p.refresh_policy(3)
    assert p.revision == 3
    row = p.policy_replay([g], [300], [5432], [6], [0])[0]
    assert row["verdict"] == 0 and row["shard"] == g % N_SHARDS
    row = p.policy_replay([g], [999999], [5432], [6], [0])[0]
    assert row["verdict"] < 0
    # the replicated ipcache: a host oracle over the plane reads shard
    # 0's compiled LPM
    from cilium_tpu.datapath.supervisor import HostStaticOracle
    assert p.compiled_ipcache is p.shards[0].compiled_ipcache
    oracle = HostStaticOracle(p)
    oracle.refresh()
    assert oracle._identity_of(0x0A010203) == 300
    assert oracle._policy_verdict(g, 300, 5432, 6, INGRESS) == 0


# ------------------------------------------------- per-shard pressure/GC

def test_per_shard_map_pressure_and_gauges(plane):
    from cilium_tpu.observability.pressure import (MAP_SHARD_ENTRIES,
                                                   MAP_SHARD_PRESSURE)
    rep = plane.map_pressure(0.9)
    assert set(rep["shards"]) == {str(k) for k in range(N_SHARDS)}
    for k in range(N_SHARDS):
        maps = rep["shards"][str(k)]["maps"]
        assert "ct" in maps and "policy-rows" not in maps or True
        assert MAP_SHARD_ENTRIES.value(
            labels={"map": "ct", "shard": str(k)}) == \
            maps["ct"]["occupied"]
        assert MAP_SHARD_PRESSURE.value(
            labels={"map": "ct", "shard": str(k)}) == \
            maps["ct"]["pressure"]
    # aggregate view: summed occupancy over summed capacity
    assert rep["maps"]["ct"]["capacity"] == \
        sum(rep["shards"][str(k)]["maps"]["ct"]["capacity"]
            for k in range(N_SHARDS))


def test_shard_local_warn_threshold():
    from cilium_tpu.observability.pressure import compute_pressure
    inv = {"ct": {"slots": 100, "occupied": 95, "max-probe": 4}}
    rep = compute_pressure(inv, 0.9, shard=2)
    assert rep["shard"] == 2
    assert any(w.startswith("shard 2: ct:") for w in rep["warnings"])


def test_shard_aware_gc_and_ct_entries(plane):
    v4, v6 = plane.ct_entries()
    assert v4 > 0          # journeys above established flows
    swept = plane.gc(now=(1 << 31) - 1)   # far future: all expire
    assert swept >= v4
    assert plane.ct_entries()[0] == 0


def test_ct_snapshot_restore_round_trip():
    p = ShardedDatapath(n_shards=N_SHARDS, ct_slots=1 << 8)
    p.telemetry_enabled = False
    v4, v6 = p.snapshot_ct()
    assert int(np.array(v4["shards"])[0]) == N_SHARDS
    assert p.restore_ct_snapshots(v4, v6) == 0
    bad = dict(v4)
    bad["shards"] = np.array([N_SHARDS + 1], np.int64)
    with pytest.raises(ValueError):
        p.restore_ct_snapshots(bad, v6)


# -------------------------------------- supervision-off byte-identical

def test_sharded_supervision_off_is_byte_identical():
    """Supervision is host-side only, per shard: with it disabled the
    sharded program each shard compiles is byte-identical, and the
    lanes carry no supervisors."""
    import jax.numpy as jnp
    states, prefixes = build_config1(n_rules=10, n_endpoints=4)
    mesh = make_mesh(2, ep_parallel=2)
    planes = {}
    for label, enabled in (("on", True), ("off", False)):
        p = ShardedDatapath(mesh=mesh, ct_slots=1 << 8)
        p.telemetry_enabled = False
        p.configure_supervision(enabled=enabled)
        p.load_policy(states, revision=1, ipcache_prefixes=prefixes)
        planes[label] = p
    packed = jnp.zeros((10, 16), jnp.int32)
    for k in range(2):
        lowered = []
        for p in planes.values():
            eng = p.shards[k]
            lowered.append(eng._step_packed.lower(
                *eng._lower_args_packed(packed)).as_text())
        assert lowered[0] == lowered[1]
    lane_off = planes["off"].serving()
    lane_on = planes["on"].serving()
    try:
        assert all(sv is None for sv in lane_off.supervisors)
        assert all(sv is not None for sv in lane_on.supervisors)
        for sv in lane_on.supervisors:
            assert sv.shard is not None
    finally:
        lane_off.close()
        lane_on.close()


# ------------------------------------------------- daemon-level journey

def test_daemon_sharded_journey_status_names_shard():
    """The acceptance journey on a LIVE daemon with
    dataplane_shards=4: regeneration lands rows on per-shard slices,
    a shard fault degrades exactly that shard (status names it), and
    gated recovery restores ok."""
    import json

    import jax
    jax.config.update("jax_platforms", "cpu")
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.policy.jsonio import rules_from_json
    from cilium_tpu.utils.option import DaemonConfig

    cfg = DaemonConfig(state_dir="", drift_audit_interval_s=0,
                       ct_checkpoint_interval_s=0,
                       supervisor_reset_s=0.05,
                       supervisor_watchdog_s=5.0,
                       supervisor_failure_threshold=2,
                       dataplane_shards=4)
    d = Daemon(config=cfg)
    try:
        d.endpoint_create(1, ipv4="10.200.0.10",
                          labels=["k8s:id=web"])
        d.endpoint_create(2, ipv4="10.200.0.11", labels=["k8s:id=db"])
        rules = rules_from_json(json.dumps([{
            "endpointSelector": {"matchLabels": {"id": "db"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"id": "web"}}],
                "toPorts": [{"ports": [{"port": "5432",
                                        "protocol": "TCP"}]}]}],
            "labels": ["k8s:policy=t"]}]))
        rev = d.policy_add(rules)
        assert d.wait_for_policy_revision(rev, timeout=60)
        st = d.status()["dataplane"]
        assert st["status"] == "ok"
        assert st["geometry"]["ep"] == 4

        slot = d.endpoints.lookup(2).table_slot
        victim = slot % 4
        lane = d.datapath.serving()
        sup = lane.lanes[victim].supervisor
        web_ip = (10 << 24) | (200 << 16) | 10
        db_ip = (10 << 24) | (200 << 16) | 11

        def records(n, dport, sport0):
            return {
                "endpoint": np.full(n, slot, np.int32),
                "saddr": np.full(n, web_ip,
                                 np.uint32).view(np.int32),
                "daddr": np.full(n, db_ip, np.uint32).view(np.int32),
                "sport": (sport0 + np.arange(n)).astype(np.int32),
                "dport": np.full(n, dport, np.int32),
                "proto": np.full(n, 6, np.int32),
                "direction": np.zeros(n, np.int32),
                "tcp_flags": np.full(n, 0x02, np.int32),
                "is_fragment": np.zeros(n, np.int32),
                "length": np.full(n, 256, np.int32)}

        allowed = records(8, 5432, 40000)
        t = lane.submit_records(_cp(allowed), 8)
        v, _i = t.result(timeout=120)
        assert t.error is None and (v == 0).all()
        sup.oracle.refresh()

        rec_before = DATAPLANE_RECOVERIES.total()
        inj = DeviceFaultInjector()
        sup.install_fault_hook(inj)
        inj.fail_launch(times=2)
        for _ in range(2):
            lane.submit_records(_cp(allowed), 8).result(timeout=120)
        st = d.status()["dataplane"]
        assert st["mode"] == "degraded"
        assert st["degraded-shards"] == [victim]
        assert f"shard(s) [{victim}]" in st["status"]

        # established flows keep ALLOW on the degraded shard; a
        # disallowed NEW flow stays denied
        t = lane.submit_records(_cp(allowed), 8)
        vs, _ = t.result(timeout=120)
        assert t.error is None and (vs == 0).all()
        t = lane.submit_records(records(8, 80, 41000), 8)
        vd, _ = t.result(timeout=120)
        assert t.error is None and (vd < 0).all()

        inj.heal()
        time.sleep(0.1)
        t = lane.submit_records(_cp(allowed), 8)
        v2, _ = t.result(timeout=120)
        assert t.error is None and (v2 == 0).all()
        assert sup.mode == "ok"
        assert DATAPLANE_RECOVERIES.total() > rec_before
        st = d.status()["dataplane"]
        assert st["mode"] == "ok" and st["status"] == "ok"
        # the recovery gate ran the full drift audit over GLOBAL slots
        assert d.drift_report() is not None
        assert d.drift_report()["status"] in ("ok", "idle")
        # per-shard pressure rode the status path
        mp = d.status()["map-pressure"]
        assert set(mp["shards"]) == {"0", "1", "2", "3"}
    finally:
        d.shutdown()
