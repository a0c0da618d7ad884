"""The bench output contract the driver depends on.

Round 5 regression class: the final stdout line of bench.py grew past
the driver's ~2KB tail capture and the official record carried
``parsed: null``.  These tests pin the fixed contract so it can't
recur:

- ``bench.py --smoke`` (the full output pipeline over a synthetic
  result) must end with ONE stdout line that parses as JSON, is under
  1.5KB, and carries the device, the gates and the per-config suite
  pairs;
- the full result must land in a BENCH_FULL_<ts>.json file the compact
  line points at;
- ``compact_bench_line`` must stay under the limit even for bloated
  inputs (size guard drops blocks, never truncates mid-JSON).
"""

import json
import os
import subprocess
import sys

import pytest

from cilium_tpu.utils.platform import MAX_FINAL_LINE, compact_bench_line

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LINE_LIMIT = 1500  # the issue's contract: final line < 1.5KB


def _run_smoke(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CILIUM_TPU_BENCH_FULL_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    return proc


def test_smoke_final_line_parses_and_fits(tmp_path):
    proc = _run_smoke(tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines, "no stdout at all"
    final = lines[-1]
    assert len(final.encode()) < LINE_LIMIT, \
        f"final line is {len(final.encode())}B"
    parsed = json.loads(final)
    # headline + provenance
    assert parsed["metric"] and parsed["unit"]
    extra = parsed["extra"]
    assert "backend" in extra and "on_accel" in extra
    assert extra["device"] == {"platform": "cpu", "kind": "cpu",
                               "count": 8}
    # both latency gates
    assert "latency_under_50us_p99" in extra
    assert "latency_under_35us_p99" in extra
    # per-config {value, vs_baseline} pairs
    suite = extra["suite"]
    for name in ("identity-l4", "http-regex", "kafka-acl", "fqdn",
                 "l7-fast", "capacity", "incremental", "latency-tier",
                 "dispatch-floor", "overload", "mesh-shard",
                 "threat-score", "analytics-overhead",
                 "control-churn"):
        assert name in suite, f"{name} missing from compact suite"
        assert "value" in suite[name]
        assert "vs_baseline" in suite[name]
    # engine attributability rides along
    assert suite["http-regex"].get("eng")


def test_smoke_writes_full_result_file(tmp_path):
    proc = _run_smoke(tmp_path)
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    full_name = final["extra"].get("full")
    assert full_name and full_name.startswith("BENCH_FULL_")
    full = json.load(open(tmp_path / full_name))
    res = full["result"]
    # the FULL suite detail survives in the file (dropped from the line)
    http = res["extra"]["suite_configs"]["http-regex"]
    assert http["extra"]["engine_selection"]
    # the latency-tier schema is pinned: per-batch-size sync vs
    # serving p50/p99 (b256 is the acceptance row) + coalescing block
    lat = res["extra"]["suite_configs"]["latency-tier"]
    assert lat["unit"] == "x"
    b256 = lat["extra"]["per_batch_us"]["256"]
    for key in ("sync_p50_us", "sync_p99_us", "serving_p50_us",
                "serving_p99_us", "serving_interval_us",
                "p99_speedup"):
        assert key in b256, key
    assert "under_100us_b256" in lat["extra"]
    co = lat["extra"]["coalesce"]
    for key in ("frame_p99_us", "mean_records_per_launch",
                "sync_b1_p99_us"):
        assert key in co, key
    # the dispatch-floor schema is pinned: per-batch flatten+dispatch
    # probes (packed vs legacy), end-to-end step times, and the
    # jitted-step leaf-count reduction
    df = res["extra"]["suite_configs"]["dispatch-floor"]
    assert df["unit"] == "x"
    b256 = df["extra"]["per_batch_us"]["256"]
    for key in ("legacy_dispatch_p50_us", "packed_dispatch_p50_us",
                "reduction", "legacy_step_p50_us",
                "packed_step_p50_us"):
        assert key in b256, key
    lc = df["extra"]["leaf_counts"]
    for key in ("packed-step", "legacy-step", "reduction"):
        assert key in lc, key
    assert "reduction_floor_met" in df["extra"]
    # the l7-fast schema is pinned: proxy-bypass rate, per-request
    # fast vs proxy-bound percentiles per protocol, and the
    # disabled-path byte-identity gate
    l7 = res["extra"]["suite_configs"]["l7-fast"]
    assert l7["unit"] == "%"
    for key in ("bypass_rate", "decided_on_device", "programs",
                "gate_bypass_ge_50pct", "gate_fast_p99_beats_proxy",
                "fast_disabled_byte_identical"):
        assert key in l7["extra"], key
    for key in ("fast_p50_us", "fast_p99_us", "proxy_p50_us",
                "proxy_p99_us", "p99_speedup",
                "proxy_connections_fast_leg"):
        assert key in l7["extra"]["http"], key
    for key in ("fast_p50_us", "fast_p99_us", "engine_p99_us"):
        assert key in l7["extra"]["dns"], key
    # the threat-score schema is pinned: fused-scoring overhead vs the
    # pre-threat program (gated <= 10%), the enforce-mode arm sample,
    # the train->hot-swap zero-repack proof, and the disabled-path
    # byte-identity gate
    th = res["extra"]["suite_configs"]["threat-score"]
    assert th["unit"] == "verdicts/s"
    for key in ("baseline_vps", "threat_vps", "overhead_pct",
                "gate_overhead_le_10pct", "enforce",
                "threat_disabled_byte_identical"):
        assert key in th["extra"], key
    for key in ("scored", "rate_limited", "redirected", "dropped"):
        assert key in th["extra"]["enforce"], key
    hs = th["extra"]["hot_swap"]
    for key in ("push_ms", "hot_swap_applied", "zero_repacks",
                "generation", "no_serving_pause"):
        assert key in hs, key
    # the analytics-overhead schema is pinned: fused sketch-plane
    # overhead vs the pre-analytics program (gated <= 10%), the
    # mid-serving epoch swap, the attack-shape decode leg, and the
    # disabled-path byte-identity gate
    an = res["extra"]["suite_configs"]["analytics-overhead"]
    assert an["unit"] == "verdicts/s"
    for key in ("baseline_vps", "analytics_vps", "overhead_pct",
                "gate_overhead_le_10pct", "geometry", "attack",
                "analytics_disabled_byte_identical"):
        assert key in an["extra"], key
    for key in ("width", "depth", "lanes", "stripe"):
        assert key in an["extra"]["geometry"], key
    sw = an["extra"]["epoch_swap"]
    for key in ("swap_ms", "pre_swap_batch_ms", "post_swap_batch_ms",
                "no_serving_pause"):
        assert key in sw, key
    atk = an["extra"]["attack"]
    for key in ("attacker_identity", "top_talker_identity",
                "gate_top_talker_named_attacker", "scan_suspects",
                "gate_scan_view_fired"):
        assert key in atk, key
    # the overload schema is pinned: per-multiplier legs with accepted
    # percentiles + shed accounting, admission vs unbounded
    ovl = res["extra"]["suite_configs"]["overload"]
    assert ovl["unit"] == "x"
    for leg_name in ("admission", "unbounded"):
        for mult in ("1x", "2x", "4x"):
            row = ovl["extra"]["legs"][leg_name][mult]
            for key in ("offered_frames", "accepted", "shed",
                        "shed_rate", "shed_reasons",
                        "accepted_p50_ms", "accepted_p99_ms",
                        "max_queue_records"):
                assert key in row, (leg_name, mult, key)
    assert "admission_bounds_queue" in ovl["extra"]
    assert "admission_p99_bounded_2x" in ovl["extra"]
    # the mesh-shard schema is pinned: mesh geometry, the
    # beyond-reference capacity leg, and the shard-kill degraded leg
    ms = res["extra"]["suite_configs"]["mesh-shard"]
    assert ms["unit"] == "verdicts/s"
    for key in ("devices", "dp", "ep"):
        assert key in ms["extra"]["mesh"], key
    cap = ms["extra"]["capacity"]
    for key in ("policy_entries", "ipcache_entries",
                "per_mesh_verdicts_per_sec", "beyond_reference",
                "policy_build_seconds", "shard0_devices"):
        assert key in cap, key
    deg = ms["extra"]["degraded"]
    for key in ("killed_shard", "healthy_verdicts_per_sec",
                "one_shard_down_verdicts_per_sec",
                "fail_static_records",
                "healthy_shards_stayed_closed"):
        assert key in deg, key
    # the federated-flows leg is pinned: flows-fused sharded serving
    # with federation draining concurrently, gated <= 10% overhead
    fed = ms["extra"]["federated_flows"]
    for key in ("flows_only_verdicts_per_sec",
                "federated_verdicts_per_sec",
                "overhead_vs_flows_only", "gate_overhead_le_10pct",
                "drains", "federated_queries", "drained_flows"):
        assert key in fed, key
    # the control-churn schema is pinned: healthy/outage/reconnect
    # legs with journal depth, reconcile time, and the
    # regenerations-avoided-vs-naive-full-resync accounting
    cc = res["extra"]["suite_configs"]["control-churn"]
    assert cc["unit"] == "ops/s"
    legs = cc["extra"]["legs"]
    assert "churn_ops_per_sec" in legs["healthy"]
    for key in ("churn_ops_per_sec", "journal_depth",
                "local_identities", "staleness_seconds"):
        assert key in legs["outage"], key
    for key in ("reconcile_seconds", "journal_replayed", "promoted",
                "regenerations", "naive_full_resync_regens",
                "regenerations_avoided"):
        assert key in legs["reconnect"], key


def test_compact_line_size_guard_under_bloat():
    """Even a hostile, oversized full result must compact to a single
    parseable line under the limit."""
    device = {"platform": "tpu", "kind": "k" * 40, "count": 1}
    bloated = {"metric": "m" * 100, "value": 1, "unit": "x/s",
               "vs_baseline": 1.0,
               "extra": {"backend": "cpu", "on_accel": False,
                         "device": device,
                         "latency_under_50us_p99": True,
                         "latency_under_35us_p99": False,
                         "suite_configs": {
                             f"config-{i}": {"value": 10 ** 9,
                                             "vs_baseline": 1.234,
                                             "extra": {"pad": "y" * 500}}
                             for i in range(40)}}}
    out = compact_bench_line(bloated)
    line = json.dumps(out)
    assert len(line.encode()) <= MAX_FINAL_LINE
    assert json.loads(line)["metric"] == "m" * 100
    # the device block never yields to the size guard
    assert json.loads(line)["extra"]["device"] == device


def test_compact_line_keeps_gates_and_suite_when_small():
    parsed = {"metric": "m", "value": 2, "unit": "v/s",
              "vs_baseline": 2.0,
              "extra": {"backend": "cpu", "on_accel": False,
                        "latency_under_50us_p99": True,
                        "latency_under_35us_p99": True,
                        "small_batch_p99_us": {
                            "host_cache_p99_us_b256": 30.0},
                        "suite_configs": {
                            "fqdn": {"value": 7, "vs_baseline": 7.0,
                                     "extra": {"engine_selection":
                                               {"tag": "stride3"}}},
                            "broken": "failed: boom"}}}
    out = compact_bench_line(parsed, full_file="/tmp/BENCH_FULL_x.json")
    assert out["extra"]["suite"]["fqdn"] == \
        {"value": 7, "vs_baseline": 7.0, "eng": "stride3"}
    assert out["extra"]["suite"]["broken"].startswith("failed")
    assert out["extra"]["p99_b256_us"]["host"] == 30.0
    assert out["extra"]["full"] == "BENCH_FULL_x.json"


@pytest.mark.parametrize("flag", [True, False])
def test_full_capacity_flag_parses(flag):
    """--full-capacity reaches bench_capacity (scale fields only; the
    heavy build is not run here)."""
    import inspect

    import bench_suite
    sig = inspect.signature(bench_suite.bench_capacity)
    assert "full_capacity" in sig.parameters
    # flag plumbing in run_suite: the arg filter must strip options
    args = ["capacity", "--full-capacity"] if flag else ["capacity"]
    wanted = [a for a in args if not a.startswith("--")]
    assert wanted == ["capacity"]
