#!/usr/bin/env python
"""Benchmark: BASELINE config 1 — L3/L4 CIDR+port policy verdict throughput.

Builds a 100-rule CIDR+port policy (BASELINE.json configs[0]), compiles it
two ways, and streams synthetic packet batches through both verdict
engines:

  hash  — ipcache LPM + 3-stage hash-probe verdict (gather-based)
  dense — broadcast-compare LPM + verdict (gather-free; the TPU-first
          layout: [B, N] int32 compares on the VPU)

Both engines implement bpf/lib/policy.h __policy_can_access semantics
exactly (tests enforce parity with the scalar oracle). The headline
number is the faster engine on this hardware.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
vs_baseline is measured throughput / the 10M verdicts/s north-star target
(BASELINE.md; the reference repo publishes no absolute numbers).
"""

import json
import os
import sys
import time

import numpy as np

_START = time.perf_counter()


def build_config1(n_rules=100, n_endpoints=16, seed=7):
    """100 CIDR+port allow rules -> map states + prefix table."""
    from cilium_tpu.policy.mapstate import (EGRESS, PolicyKey,
                                            PolicyMapState,
                                            PolicyMapStateEntry)
    rng = np.random.default_rng(seed)
    prefixes = {}
    states = [PolicyMapState() for _ in range(n_endpoints)]
    ident = 256
    for i in range(n_rules):
        plen = int(rng.choice([16, 24]))
        addr = f"{rng.integers(1, 224)}.{rng.integers(0, 256)}." + \
            (f"{rng.integers(0, 256)}.0" if plen == 24 else "0.0")
        prefixes[f"{addr}/{plen}"] = ident
        port = int(rng.integers(1, 65536))
        for st in states:
            st[PolicyKey(identity=ident, dest_port=port, nexthdr=6,
                         direction=EGRESS)] = PolicyMapStateEntry()
        if i % 5 == 0:
            for st in states:
                st[PolicyKey(identity=ident,
                             direction=EGRESS)] = PolicyMapStateEntry()
        ident += 1
    return states, prefixes


def _time_engine(step, iters):
    lat = []
    t0 = time.perf_counter()
    for _ in range(iters):
        t1 = time.perf_counter()
        step()
        lat.append(time.perf_counter() - t1)
    return time.perf_counter() - t0, lat


def _lat_gate(host_small, threshold_us):
    """Latency target check at b256 over the measured series (unpinned
    and, when available, cpu-pinned — the busy-poll deployment mode):
    met if the best series is under threshold."""
    vals = [host_small.get(k) for k in ("host_cache_p99_us_b256",
                                        "host_cache_pinned_p99_us_b256")]
    vals = [v for v in vals if isinstance(v, (int, float))]
    return bool(vals) and min(vals) < threshold_us


def _progress(stage, **kw):
    """Incremental capture on stderr: if a later stage stalls or
    fails, everything measured so far is already on record."""
    print(json.dumps({"progress": stage, **kw}), file=sys.stderr,
          flush=True)


def _smoke_result():
    """A full-shaped synthetic result for exercising the output
    contract (``--smoke``): same keys and realistic sizes as a real
    run, no measurement, so the driver-contract test (final stdout
    line parses and is <1.5KB, full result persisted to
    BENCH_FULL_*.json) runs in seconds."""
    suite = {}
    for name, v in (("identity-l4", 124_000_000), ("http-regex",
                    9_500_000), ("kafka-acl", 2_100_000),
                    ("fqdn", 15_600_000), ("capacity", 14_000_000),
                    ("incremental", 363),
                    ("flows-overhead", 1_200_000),
                    ("tracing-overhead", 1_250_000),
                    ("provenance-overhead", 1_250_000)):
        suite[name] = {"metric": name, "value": v, "unit": "x/s",
                       "vs_baseline": round(v / 1e7, 3),
                       "extra": {"batch": 8192, "smoke": True,
                                 "p99_batch_latency_us": 1000.0,
                                 "engine_selection":
                                 {"tag": "stride3-int32-C29",
                                  "strategy": "stride", "k": 3,
                                  "dtype": "int32", "classes": 29,
                                  "states": 96}}}
    # the l7-fast config's pinned output schema: proxy-bypass rate,
    # per-request fast vs proxy-bound percentiles per protocol, and
    # the disabled-path byte-identity gate
    suite["l7-fast"] = {
        "metric": "l7_fast_proxy_bypass_rate", "value": 80,
        "unit": "%", "vs_baseline": 1.6,
        "extra": {"smoke": True, "window": 128,
                  "programs": {"programs": 2, "regexes": 7,
                               "states": 120, "k": 2, "classes": 30,
                               "window": 128,
                               "resident_bytes": 500000,
                               "protocols": {"http": 1, "dns": 1}},
                  "batch": 4096, "requests_per_sec": 2_000_000,
                  "bypass_rate": 0.8, "decided_on_device": 3277,
                  "undecidable_mix": 0.2,
                  "http": {"requests": 120, "fast_p50_us": 400.0,
                           "fast_p99_us": 800.0,
                           "proxy_p50_us": 900.0,
                           "proxy_p99_us": 2400.0,
                           "proxy_connections_fast_leg": 0,
                           "proxy_connections_proxy_leg": 125,
                           "p99_speedup": 3.0},
                  "dns": {"requests": 120, "fast_p50_us": 380.0,
                          "fast_p99_us": 750.0,
                          "engine_p50_us": 9.0,
                          "engine_p99_us": 25.0},
                  "gate_bypass_ge_50pct": True,
                  "gate_fast_p99_beats_proxy": True,
                  "fast_disabled_byte_identical": True}}
    # the threat-score config's pinned output schema: fused-scoring
    # overhead vs the pre-threat program, the enforce-mode arm sample,
    # the train->hot-swap push proof, and the disabled-path gate
    suite["threat-score"] = {
        "metric": "threat_score_verdicts_per_sec", "value": 1_150_000,
        "unit": "verdicts/s", "vs_baseline": 0.115,
        "extra": {"smoke": True, "batch": 65536, "rounds": 5,
                  "baseline_vps": 1_200_000,
                  "threat_vps": 1_150_000,
                  "overhead_pct": 4.2,
                  "gate_overhead_le_10pct": True,
                  "model": {"features": 12, "hidden": 1,
                            "resident-bytes": 92,
                            "config": {"mode": "shadow",
                                       "generation": 1}},
                  "score_mean": 141.0,
                  "enforce": {"scored": 3000, "rate_limited": 600,
                              "redirected": 0, "dropped": 496},
                  "hot_swap": {"push_ms": 3.1,
                               "hot_swap_applied": True,
                               "zero_repacks": True,
                               "trained_flows": 4096,
                               "generation": 2,
                               "pre_push_batch_ms": 55.0,
                               "post_push_batch_ms": 56.0,
                               "no_serving_pause": True},
                  "threat_disabled_byte_identical": True}}
    # the analytics-overhead config's pinned output schema: fused
    # sketch-plane overhead vs the pre-analytics program, the mid-
    # serving epoch swap, the attack-shape decode leg, and the
    # disabled-path byte-identity gate
    suite["analytics-overhead"] = {
        "metric": "analytics_overhead_verdicts_per_sec",
        "value": 1_120_000, "unit": "verdicts/s",
        "vs_baseline": 0.112,
        "extra": {"smoke": True, "batch": 65536, "rounds": 5,
                  "baseline_vps": 1_180_000,
                  "analytics_vps": 1_120_000,
                  "overhead_pct": 5.1,
                  "gate_overhead_le_10pct": True,
                  "geometry": {"width": 4096, "depth": 2,
                               "lanes": 4, "stripe": 16},
                  "epoch_swap": {"swap_ms": 0.9,
                                 "pre_swap_batch_ms": 55.0,
                                 "post_swap_batch_ms": 56.0,
                                 "no_serving_pause": True},
                  "attack": {"attacker_identity": 256,
                             "legit_rows": 3072, "scan_rows": 512,
                             "syn_flood_rows": 512,
                             "top_talker_identity": 256,
                             "top_talker_bytes": 798720,
                             "gate_top_talker_named_attacker": True,
                             "scan_suspects": [256],
                             "scan_suspect_dports": 512,
                             "gate_scan_view_fired": True,
                             "top_spreader_identity": 256},
                  "analytics_disabled_byte_identical": True}}
    # the overload config's pinned output schema: per-multiplier legs
    # with accepted-latency percentiles + shed accounting, admission
    # control vs the unbounded pre-change queue
    leg = lambda p99, shed, q: {  # noqa: E731 — schema fixture
        "offered_frames": 1000, "offered_records_per_sec": 700000,
        "accepted": 900, "shed": 100, "shed_rate": shed,
        "shed_reasons": {"overflow": 90, "deadline": 10},
        "accepted_p50_ms": p99 / 2, "accepted_p99_ms": p99,
        "max_queue_records": q}
    suite["overload"] = {
        "metric": "overload_p99_containment_2x", "value": 7,
        "unit": "x", "vs_baseline": 7.0,
        "extra": {"smoke": True,
                  "capacity_records_per_sec": 360_000,
                  "frame_records": 256, "horizon_s": 1.0,
                  "deadline_s": 0.08, "max_pending_records": 16384,
                  "legs": {
                      "admission": {"1x": leg(33.0, 0.01, 16384),
                                    "2x": leg(47.0, 0.12, 16384),
                                    "4x": leg(112.0, 0.63, 16384)},
                      "unbounded": {"1x": leg(24.0, 0.0, 4352),
                                    "2x": leg(334.0, 0.0, 188928),
                                    "4x": leg(1004.0, 0.0, 664832)}},
                  "admission_bounds_queue": True,
                  "admission_p99_bounded_2x": True}}
    # the mesh-shard config's pinned output schema: mesh geometry, a
    # beyond-reference capacity leg, and a shard-kill degradation leg
    suite["mesh-shard"] = {
        "metric": "mesh_shard_verdicts_per_sec", "value": 720_000,
        "unit": "verdicts/s", "vs_baseline": 0.072,
        "extra": {"smoke": True,
                  "mesh": {"devices": 8, "dp": 2, "ep": 4},
                  "capacity": {
                      "policy_endpoints": 1024,
                      "entries_per_endpoint": 16384,
                      "policy_entries": 16_777_216,
                      "ipcache_entries": 578_048,
                      "beyond_reference": {
                          "reference_policy_entries": 8_388_608,
                          "reference_ipcache_entries": 512_000,
                          "policy": True, "ipcache": True},
                      "per_mesh_verdicts_per_sec": 720_000,
                      "batch_per_shard": 65536,
                      "policy_build_seconds": 15.0,
                      "ipcache_build_seconds": 9.0,
                      "policy_device_mbytes_per_shard": 340.0,
                      "shard0_devices": [0, 4]},
                  "degraded": {
                      "killed_shard": 0, "killed_mode": "degraded",
                      "healthy_verdicts_per_sec": 400_000,
                      "one_shard_down_verdicts_per_sec": 120_000,
                      "degraded_ratio": 0.3,
                      "fail_static_records": 3072,
                      "healthy_shards_stayed_closed": True,
                      "frame_records": 1024},
                  "federated_flows": {
                      "flows_only_verdicts_per_sec": 180_000,
                      "federated_verdicts_per_sec": 172_000,
                      "overhead_vs_flows_only": 0.044,
                      "gate_overhead_le_10pct": True,
                      "drains": 120, "federated_queries": 120,
                      "drained_flows": 4096,
                      "flow_table_slots": 4096, "shards": 4},
                  "at_full_capacity": True}}
    # the control-churn config's pinned output schema: three legs
    # (healthy / outage / reconnect) with journal depth, reconcile
    # time, and regenerations avoided vs a naive full resync
    suite["control-churn"] = {
        "metric": "control_churn_ops_per_sec", "value": 5,
        "unit": "ops/s", "vs_baseline": 0.1,
        "extra": {"smoke": True, "endpoints": 20,
                  "legs": {
                      "healthy": {"churn_ops_per_sec": 5.2},
                      "outage": {"churn_ops_per_sec": 9.9,
                                 "journal_depth": 4,
                                 "local_identities": 4,
                                 "staleness_seconds": 2.0},
                      "reconnect": {
                          "reconcile_seconds": 3.4,
                          "journal_replayed": 4, "repaired": 0,
                          "promoted": 4, "regenerations": 4,
                          "naive_full_resync_regens": 20,
                          "regenerations_avoided": 16}}}}
    # the dispatch-floor config's pinned output schema: per-batch-size
    # flatten+dispatch probes (packed vs legacy-pytree) + end-to-end
    # step times + the jitted-step leaf-count reduction
    row = lambda r: {  # noqa: E731 — schema fixture
        "legacy_dispatch_p50_us": 11.7, "packed_dispatch_p50_us": 6.8,
        "reduction": r, "legacy_step_p50_us": 545.0,
        "packed_step_p50_us": 583.4}
    suite["dispatch-floor"] = {
        "metric": "dispatch_floor_reduction_b256", "value": 1.74,
        "unit": "x", "vs_baseline": 1.16,
        "extra": {"smoke": True,
                  "per_batch_us": {"1": row(1.77), "256": row(1.74),
                                   "4096": row(1.98)},
                  "leaf_counts": {"packed-step": 8, "v6-step": 17,
                                  "legacy-step": 36, "reduction": 4.5},
                  "reduction_floor_met": True,
                  "pack_stats": {"full-packs": 1, "row-writes": 0,
                                 "leaf-writes": 0}}}
    # the latency-tier config's pinned output schema: per-batch-size
    # sync vs serving p50/p99 plus the coalescing block
    suite["latency-tier"] = {
        "metric": "latency_tier_b256_p99_speedup", "value": 6.2,
        "unit": "x", "vs_baseline": 1.24,
        "extra": {"smoke": True, "serving_depth": 2,
                  "under_100us_b256": False,
                  "per_batch_us": {
                      "256": {"sync_p50_us": 900.0,
                              "sync_p99_us": 2400.0,
                              "serving_p50_us": 300.0,
                              "serving_p99_us": 390.0,
                              "serving_interval_us": 310.0,
                              "p99_speedup": 6.2}},
                  "coalesce": {"submitters": 16, "frames": 640,
                               "frame_p99_us": 700.0,
                               "mean_records_per_launch": 9.0,
                               "launches": 71,
                               "sync_b1_p99_us": 1900.0},
                  "eliminated_boundaries": ["smoke"]}}
    return {"metric": "policy_verdicts_per_sec_config1_100rules",
            "value": 1_290_000, "unit": "verdicts/s",
            "vs_baseline": 0.129,
            "extra": {"smoke": True, "batch": 131072, "engine": "dense",
                      "p99_batch_latency_us": 101_000.0,
                      "small_batch_p99_us": {
                          "host_cache_p99_us_b256": 33.3,
                          "host_cache_pinned_p99_us_b256": 34.0,
                          "device_rt_p99_us_b256": 1800.0},
                      "latency_under_50us_p99": True,
                      "latency_under_35us_p99": True,
                      "suite_configs": suite}}


def run_bench():
    from cilium_tpu.utils.platform import (emit_result,
                                           enable_compile_cache,
                                           require_device)
    platform, kind, count = require_device()
    device = {"platform": platform, "kind": kind, "count": count}
    if "--smoke" in sys.argv:
        res = _smoke_result()
        res["extra"].update(backend=platform, on_accel=platform == "tpu",
                            device=device)
        emit_result(res)
        return
    enable_compile_cache()
    backend, on_accel = platform, platform == "tpu"

    import jax
    import jax.numpy as jnp

    _progress("backend", backend=backend, on_accel=on_accel)

    argv_nums = [a for a in sys.argv[1:] if not a.startswith("--")]
    batch = int(argv_nums[0]) if argv_nums else 1 << 20
    if not on_accel and not argv_nums:
        batch = 1 << 17  # CPU smoke runs use a smaller default

    states, prefixes = build_config1()

    rng = np.random.default_rng(1)
    n_endpoints = len(states)
    ep = rng.integers(0, n_endpoints, batch, dtype=np.int32)
    src = rng.integers(0, 2 ** 32, batch, dtype=np.uint32).view(np.int32)
    dport = rng.integers(1, 65536, batch, dtype=np.int32)
    proto = np.full(batch, 6, np.int32)
    direction = np.ones(batch, np.int32)
    length = np.full(batch, 512, np.int32)

    # ---- hash engine (LPM gather + 3-stage probe) ----------------------
    from cilium_tpu.compiler.lpm import compile_lpm
    from cilium_tpu.compiler.policy_tables import compile_endpoints
    from cilium_tpu.datapath.pipeline import RawPacketBatch, make_step

    compiled_policy = compile_endpoints(states, revision=1)
    compiled_lpm = compile_lpm(prefixes)
    h_step, h_tables, h_counters = make_step(compiled_policy, compiled_lpm)
    pkt = RawPacketBatch(
        endpoint=jnp.asarray(ep), src_addr=jnp.asarray(src),
        dport=jnp.asarray(dport), proto=jnp.asarray(proto),
        direction=jnp.asarray(direction), length=jnp.asarray(length),
        is_fragment=jnp.asarray(np.zeros(batch, np.int32)))

    hstate = {"counters": h_counters}

    def hash_iter():
        verdict, identity, hstate["counters"] = h_step(
            h_tables, hstate["counters"], pkt)
        verdict.block_until_ready()

    hash_iter()  # compile
    _progress("hash_compiled")

    # ---- dense engine (gather-free broadcast compare) ------------------

    from cilium_tpu.ops.dense_verdict import (compile_dense,
                                              compile_dense_lpm,
                                              dense_datapath_step)

    d_tables = compile_dense(states)
    d_lpm = compile_dense_lpm(prefixes)
    n_entries = int(d_tables.ep.shape[0])
    d_step = jax.jit(dense_datapath_step, donate_argnums=(2, 3))
    dstate = {"cpk": jnp.zeros(n_entries, jnp.uint32),
              "cby": jnp.zeros(n_entries, jnp.uint32)}
    d_args = (jnp.asarray(ep), jnp.asarray(src), jnp.asarray(dport),
              jnp.asarray(proto), jnp.asarray(direction),
              jnp.asarray(length))

    def dense_iter():
        verdict, identity, dstate["cpk"], dstate["cby"] = d_step(
            d_tables, d_lpm, dstate["cpk"], dstate["cby"], *d_args)
        verdict.block_until_ready()

    dense_iter()  # compile
    _progress("dense_compiled")

    # ---- probe both, run the winner longer -----------------------------
    probe_iters = 3
    h_probe, _ = _time_engine(hash_iter, probe_iters)
    d_probe, _ = _time_engine(dense_iter, probe_iters)
    winner = "dense" if d_probe < h_probe else "hash"
    win_iter = dense_iter if winner == "dense" else hash_iter
    _progress("probed", hash_vps=round(probe_iters * batch / h_probe),
              dense_vps=round(probe_iters * batch / d_probe),
              winner=winner)

    iters = 30 if on_accel else 10
    elapsed, lat = _time_engine(win_iter, iters)
    sync_vps = iters * batch / elapsed
    p99_us = float(np.percentile(np.array(lat), 99) * 1e6)

    # streaming mode: every dispatch in flight before one final sync —
    # the steady state the serving dispatcher (datapath/serving.py)
    # actually runs the engine in, where per-dispatch host overhead
    # overlaps device compute instead of adding to it.  This is the
    # headline; the per-dispatch sync series above stays in extras.
    def hash_launch():
        verdict, _identity, hstate["counters"] = h_step(
            h_tables, hstate["counters"], pkt)
        return verdict

    def dense_launch():
        verdict, _identity, dstate["cpk"], dstate["cby"] = d_step(
            d_tables, d_lpm, dstate["cpk"], dstate["cby"], *d_args)
        return verdict

    win_launch = dense_launch if winner == "dense" else hash_launch
    p_iters = iters * 2
    jax.block_until_ready([win_launch() for _ in range(2)])  # warm
    t0 = time.perf_counter()
    outs = [win_launch() for _ in range(p_iters)]
    jax.block_until_ready(outs)
    vps = p_iters * batch / (time.perf_counter() - t0)
    _progress("throughput", vps=round(vps), sync_vps=round(sync_vps),
              p99_batch_latency_us=round(p99_us, 1))

    # ---- small-batch latency: the <50us p99 half of the north star -----
    # Device path: FULL round trip (host numpy in -> verdict back on
    # host), the worst case for a latency-critical small batch.  Host
    # path: the C++ verdict cache (native/fastpath.py) — the eBPF
    # hit-path analog that small batches take without any device hop.
    small = {}
    d_small_step = jax.jit(dense_datapath_step)  # no donation: reuse args
    for sb in (256, 1024, 4096):
        idx = slice(0, sb)
        np_args = (ep[idx], src[idx], dport[idx], proto[idx],
                   direction[idx], length[idx])
        cpk = jnp.zeros(n_entries, jnp.uint32)
        cby = jnp.zeros(n_entries, jnp.uint32)

        def dev_iter():
            v, _i, _c, _b = d_small_step(d_tables, d_lpm, cpk, cby,
                                         *np_args)
            np.asarray(v)  # device->host sync included

        dev_iter()  # compile this shape
        lat_iters = 200 if on_accel else 30
        _t, lat = _time_engine(dev_iter, lat_iters)
        small[f"device_rt_p99_us_b{sb}"] = round(
            float(np.percentile(np.array(lat), 99) * 1e6), 1)
    _progress("small_batch_device", **small)

    host_small = {}
    try:
        from cilium_tpu.native.fastpath import HostVerdictPath
        hp = HostVerdictPath()
        for eid, st in enumerate(states):
            hp.sync_endpoint(eid, st)
        # post-ipcache identities (the hit path runs AFTER identity
        # resolution, like the in-kernel policymap): half installed
        # rule identities, half strangers
        idents = np.where(rng.random(4096) < 0.5,
                          rng.integers(256, 356, 4096),
                          rng.integers(1 << 16, 1 << 20, 4096)) \
            .astype(np.uint32)
        # latency-tuned window: GC pauses are the dominant outlier at
        # these microsecond scales (a production latency path pins GC
        # the same way); the whole 3-stage fallback is one native call
        # through preallocated buffers (native/fastpath._Scratch).
        # p99 over >=10k iterations, unpinned AND cpu-pinned (the
        # busy-poll deployment mode; identical when the cpuset has one
        # cpu).
        import gc
        gc_was_on = gc.isenabled()
        gc.disable()
        lat_iters = 10_000

        def _measure(tag):
            for sb in (256, 1024, 4096):
                idx = slice(0, sb)

                def host_iter():
                    hp.classify(0, idents[idx], dport[idx],
                                proto[idx], direction[idx])

                host_iter()
                _t, lat = _time_engine(host_iter, lat_iters)
                lat_us = np.array(lat) * 1e6
                host_small[f"host_cache{tag}_p99_us_b{sb}"] = round(
                    float(np.percentile(lat_us, 99)), 1)
                host_small[f"host_cache{tag}_p50_us_b{sb}"] = round(
                    float(np.percentile(lat_us, 50)), 1)

        try:
            _measure("")
            try:
                allowed = sorted(os.sched_getaffinity(0))
                os.sched_setaffinity(0, {allowed[-1]})
                host_small["pinned_cpu"] = allowed[-1]
                _measure("_pinned")
            finally:
                try:
                    os.sched_setaffinity(0, set(allowed))
                except Exception:  # noqa: BLE001
                    pass
        finally:
            if gc_was_on:
                gc.enable()
        hp.close()
    except Exception as e:  # noqa: BLE001 — native build optional
        host_small = {"host_cache": f"unavailable: {e!r}"}
    _progress("small_batch_host", **host_small)

    # ---- the other BASELINE configs, time-budgeted ---------------------
    # The driver captures bench.py's single line; folding the suite in
    # (with a deadline guard so config 1's number is never at risk)
    # gets every config an on-accel record in one capture.
    suite = {}
    deadline = _START + float(os.environ.get("CILIUM_TPU_BENCH_BUDGET",
                                             330))
    try:
        import bench_suite
        # latency-tier leads: the serving-path latency claim must
        # never be the config the time budget drops; overload rides
        # right behind it (the survivable-serving admission claim).
        # control-churn runs LAST: the one config that spins a live
        # daemon + MiniEtcd + fault proxies inside this process stays
        # downstream of every micro-bench, so its background threads
        # and teardown can never perturb their measurements
        for name in ("latency-tier", "dispatch-floor", "overload",
                     "mesh-shard",
                     "identity-l4", "http-regex", "kafka-acl", "fqdn",
                     "l7-fast",
                     "capacity", "incremental", "flows-overhead",
                     "tracing-overhead", "provenance-overhead",
                     "threat-score", "analytics-overhead",
                     "control-churn"):
            if time.perf_counter() > deadline:
                suite[name] = "skipped: time budget"
                continue
            try:
                r = bench_suite.CONFIGS[name](on_accel)
                # the FULL per-config result rides along: emit_result
                # persists it to BENCH_FULL_<ts>.json and prints only
                # the compact contract line, so size no longer
                # constrains what's recorded here
                suite[name] = r
                _progress("suite", config=name, value=r["value"],
                          vs_baseline=r["vs_baseline"])
            except Exception as e:  # noqa: BLE001 — partial > nothing
                suite[name] = f"failed: {e!r}"
                _progress("suite_failed", config=name, error=repr(e))
    except Exception as e:  # noqa: BLE001
        suite = {"suite": f"unavailable: {e!r}"}

    target = 10_000_000.0  # BASELINE.md north star: >=10M verdicts/s
    emit_result({
        "metric": "policy_verdicts_per_sec_config1_100rules",
        "value": round(vps),
        "unit": "verdicts/s",
        "vs_baseline": round(vps / target, 3),
        "extra": {"batch": batch, "iters": iters, "engine": winner,
                  "mode": "pipelined",
                  "sync_vps": round(sync_vps),
                  "p99_batch_latency_us": round(p99_us, 1),
                  "hash_probe_vps": round(probe_iters * batch / h_probe),
                  "dense_probe_vps": round(probe_iters * batch / d_probe),
                  "small_batch_p99_us": {**small, **host_small},
                  # BASELINE latency north star (<50us small-batch):
                  # served by the host fast path (two-tier design — the
                  # policymap-analog C++ cache takes small batches, the
                  # TPU takes bulk)
                  "latency_under_50us_p99": _lat_gate(host_small, 50.0),
                  # structural-margin gate: the target must not flip on
                  # scheduler noise (round-4 lesson: 41us one run,
                  # 51.6us the next) — judged on the best of the
                  # unpinned and pinned (busy-poll deployment) series
                  "latency_under_35us_p99": _lat_gate(host_small, 35.0),
                  "suite_configs": suite,
                  "backend": backend, "on_accel": on_accel,
                  "device": device,
                  "policy_entries": compiled_policy.entry_count(),
                  "dense_entries": n_entries,
                  "lpm_entries": compiled_lpm.entry_count()},
    })


if __name__ == "__main__":
    run_bench()
