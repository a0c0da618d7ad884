"""Dense broadcast-compare verdict engine vs the scalar oracle and the
hash engine — both the jnp path and the Pallas kernel (interpret mode
on CPU).
"""

import numpy as np

import jax.numpy as jnp

from cilium_tpu.compiler.policy_tables import (compile_endpoints,
                                               oracle_verdict)
from cilium_tpu.ops.dense_verdict import (DenseVerdictEngine,
                                          compile_dense,
                                          dense_verdict_pallas,
                                          dense_verdict_step)
from cilium_tpu.policy.mapstate import (EGRESS, INGRESS, PolicyKey,
                                        PolicyMapState,
                                        PolicyMapStateEntry)


def _random_states(n_endpoints=4, n_rules=24, seed=5):
    rng = np.random.default_rng(seed)
    states = []
    idents = rng.integers(256, 400, 16)
    ports = rng.integers(1, 2048, 16)
    for _ in range(n_endpoints):
        st = PolicyMapState()
        for _ in range(n_rules):
            st[PolicyKey(identity=int(rng.choice(idents)),
                         dest_port=int(rng.choice(ports)), nexthdr=6,
                         direction=int(rng.integers(0, 2)))] = \
                PolicyMapStateEntry(
                    proxy_port=int(rng.integers(0, 2) * 11000))
        # L3-only + L4-wildcard entries exercise stages 2/3
        st[PolicyKey(identity=int(rng.choice(idents)),
                     direction=INGRESS)] = PolicyMapStateEntry()
        st[PolicyKey(identity=0, dest_port=80, nexthdr=6,
                     direction=INGRESS)] = \
            PolicyMapStateEntry(proxy_port=15001)
        states.append(st)
    return states


def _random_queries(states, batch, seed=6):
    rng = np.random.default_rng(seed)
    n_ep = len(states)
    return (rng.integers(0, n_ep, batch).astype(np.int32),
            rng.integers(250, 410, batch).astype(np.int32),
            rng.choice(np.r_[rng.integers(1, 2048, 32), 80],
                       batch).astype(np.int32),
            np.full(batch, 6, np.int32),
            rng.integers(0, 2, batch).astype(np.int32),
            np.full(batch, 256, np.int32))


def test_dense_jnp_matches_oracle_and_counters():
    states = _random_states()
    eng = DenseVerdictEngine(states)
    ep, ident, dport, proto, dirn, length = _random_queries(states, 1024)
    verdict = np.asarray(eng(ep, ident, dport, proto, dirn, length))
    n_hits = 0
    for i in range(1024):
        want = oracle_verdict(states[ep[i]], int(ident[i]),
                              int(dport[i]), int(proto[i]), int(dirn[i]))
        assert verdict[i] == want, (i, want, verdict[i])
        if want != -1:
            n_hits += 1
    # counters: every non-drop packet attributed to exactly one entry
    assert int(np.asarray(eng.counters_packets).sum()) == n_hits
    assert int(np.asarray(eng.counters_bytes).sum()) == n_hits * 256


def test_dense_pallas_matches_jnp():
    states = _random_states(seed=7)
    tables = compile_dense(states)
    ep, ident, dport, proto, dirn, length = _random_queries(states, 512,
                                                            seed=8)
    arr = lambda x: jnp.asarray(x)
    v_ref, cpk_ref, cby_ref = dense_verdict_step(
        tables, jnp.zeros_like(tables.ep, jnp.uint32),
        jnp.zeros_like(tables.ep, jnp.uint32), arr(ep), arr(ident),
        arr(dport), arr(proto), arr(dirn), arr(length))
    v_pl, cpk_pl, cby_pl = dense_verdict_pallas(
        tables, arr(ep), arr(ident), arr(dport), arr(proto), arr(dirn),
        arr(length), block_b=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_pl))
    np.testing.assert_array_equal(np.asarray(cpk_ref),
                                  np.asarray(cpk_pl).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(cby_ref),
                                  np.asarray(cby_pl).astype(np.uint32))


def test_dense_engine_pallas_path():
    states = _random_states(seed=9)
    eng = DenseVerdictEngine(states, use_pallas=True, block_b=128,
                             interpret=True)
    assert eng.use_pallas
    ep, ident, dport, proto, dirn, length = _random_queries(states, 256,
                                                            seed=10)
    verdict = np.asarray(eng(ep, ident, dport, proto, dirn, length))
    for i in range(256):
        want = oracle_verdict(states[ep[i]], int(ident[i]),
                              int(dport[i]), 6, int(dirn[i]))
        assert verdict[i] == want
    # counters accumulated through the pallas path too
    assert int(np.asarray(eng.counters_packets).sum()) == \
        int((verdict != -1).sum())


def test_dense_matches_hash_engine():
    """Dense and hash engines must agree verdict-for-verdict on the
    same map states — the parity the bench's winner-selection relies
    on."""
    from cilium_tpu.datapath.verdict import VerdictEngine, \
        make_packet_batch
    states = _random_states(seed=12)
    dense = DenseVerdictEngine(states)
    hash_eng = VerdictEngine(compile_endpoints(states, revision=1))
    ep, ident, dport, proto, dirn, length = _random_queries(states, 512,
                                                            seed=13)
    dense_v = np.asarray(dense(ep, ident, dport, proto, dirn, length))
    hash_v = np.asarray(hash_eng(make_packet_batch(
        endpoint=ep, identity=ident, dport=dport, proto=proto,
        direction=dirn, length=length)))
    np.testing.assert_array_equal(dense_v, hash_v)


def test_dense_empty_and_padding():
    eng = DenseVerdictEngine([PolicyMapState()])
    v = np.asarray(eng(np.zeros(4, np.int32), np.full(4, 300, np.int32),
                       np.full(4, 80, np.int32), np.full(4, 6, np.int32),
                       np.zeros(4, np.int32), np.full(4, 100, np.int32)))
    assert (v == -1).all()
    # padding rows (ep=-1) can never match a real endpoint
    assert int(np.asarray(eng.counters_packets).sum()) == 0


def test_dense_lpm_matches_oracle():
    from cilium_tpu.compiler.lpm import ipv4_to_u32, oracle_lpm
    from cilium_tpu.ops.dense_verdict import (compile_dense_lpm,
                                              dense_lpm_lookup)
    prefixes = {"10.0.0.0/8": 100, "10.1.0.0/16": 200,
                "10.1.2.0/24": 300, "10.1.2.3/32": 400,
                "0.0.0.0/0": 2, "192.168.0.0/16": 500}
    lpm = compile_dense_lpm(prefixes)
    queries = ["10.1.2.3", "10.1.2.9", "10.1.9.9", "10.9.9.9",
               "192.168.1.1", "8.8.8.8"]
    addrs = jnp.asarray(np.array([ipv4_to_u32(q) for q in queries],
                                 np.uint32).view(np.int32))
    found, value = dense_lpm_lookup(lpm, addrs)
    assert np.asarray(found).all()  # 0.0.0.0/0 catches everything
    for q, v in zip(queries, np.asarray(value)):
        assert oracle_lpm(prefixes, q) == int(v), q


def test_dense_datapath_step_end_to_end():
    from cilium_tpu.compiler.lpm import ipv4_to_u32
    from cilium_tpu.ops.dense_verdict import (compile_dense_lpm,
                                              dense_datapath_step)
    # identity 300 lives at 10.1.0.0/16; endpoint 0 allows it on 80/TCP
    st = PolicyMapState()
    st[PolicyKey(identity=300, dest_port=80, nexthdr=6,
                 direction=INGRESS)] = PolicyMapStateEntry()
    tables = compile_dense([st])
    lpm = compile_dense_lpm({"10.1.0.0/16": 300})
    n = tables.ep.shape[0]
    addrs = jnp.asarray(np.array(
        [ipv4_to_u32("10.1.2.3"), ipv4_to_u32("8.8.8.8")],
        np.uint32).view(np.int32))
    z = lambda v: jnp.asarray(np.array(v, np.int32))
    verdict, identity, cpk, cby = dense_datapath_step(
        tables, lpm, jnp.zeros(n, jnp.uint32), jnp.zeros(n, jnp.uint32),
        z([0, 0]), addrs, z([80, 80]), z([6, 6]), z([0, 0]),
        z([256, 256]))
    v = np.asarray(verdict)
    assert v[0] == 0       # known identity allowed
    assert v[1] == -1      # world dropped
    ids = np.asarray(identity)
    assert ids[0] == 300 and ids[1] == 2
    assert int(np.asarray(cpk).sum()) == 1


def test_dense_pallas_multi_tile_parity():
    """Entry axis larger than one tile: the 2-D grid must accumulate
    stage partials across tiles and still match the jnp path exactly
    (verdicts AND per-entry counters)."""
    states = _random_states(n_endpoints=16, n_rules=100, seed=12)
    tables = compile_dense(states)
    n = int(tables.ep.shape[0])
    tile_n = 256
    assert n > 2 * tile_n  # genuinely multi-tile
    ep, ident, dport, proto, dirn, length = _random_queries(states, 512,
                                                            seed=13)
    arr = lambda x: jnp.asarray(x)
    v_ref, cpk_ref, cby_ref = dense_verdict_step(
        tables, jnp.zeros_like(tables.ep, jnp.uint32),
        jnp.zeros_like(tables.ep, jnp.uint32), arr(ep), arr(ident),
        arr(dport), arr(proto), arr(dirn), arr(length))
    v_pl, cpk_pl, cby_pl = dense_verdict_pallas(
        tables, arr(ep), arr(ident), arr(dport), arr(proto), arr(dirn),
        arr(length), block_b=128, tile_n=tile_n, interpret=True)
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_pl))
    np.testing.assert_array_equal(np.asarray(cpk_ref),
                                  np.asarray(cpk_pl).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(cby_ref),
                                  np.asarray(cby_pl).astype(np.uint32))


def test_dense_pallas_non_tile_multiple_padding():
    """N not a multiple of tile_n: padding rows (ep=-1) must never
    match and the counter scatter must stay within the real N."""
    states = _random_states(n_endpoints=3, n_rules=50, seed=14)
    tables = compile_dense(states)
    n = int(tables.ep.shape[0])
    tile_n = 384  # LANE-padded N=384*k only by luck; force check
    ep, ident, dport, proto, dirn, length = _random_queries(states, 256,
                                                            seed=15)
    arr = lambda x: jnp.asarray(x)
    v_ref, cpk_ref, cby_ref = dense_verdict_step(
        tables, jnp.zeros_like(tables.ep, jnp.uint32),
        jnp.zeros_like(tables.ep, jnp.uint32), arr(ep), arr(ident),
        arr(dport), arr(proto), arr(dirn), arr(length))
    v_pl, cpk_pl, cby_pl = dense_verdict_pallas(
        tables, arr(ep), arr(ident), arr(dport), arr(proto), arr(dirn),
        arr(length), block_b=256, tile_n=tile_n, interpret=True)
    assert cpk_pl.shape[0] == n
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_pl))
    np.testing.assert_array_equal(np.asarray(cpk_ref),
                                  np.asarray(cpk_pl).astype(np.uint32))
