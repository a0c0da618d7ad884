"""The benchmark's own tests (``benchmark/``), on the CPU at small sizes.

- the generator gives the same traffic for the same seed;
- the plain reference agrees with the served path on every mix, and the
  control (policy without conntrack) put in the program's place does
  not;
- a run with the timed path broken underneath comes out not correct;
- the generator's keys (close, hot_shift, pod_peer_share) and the
  reference's conntrack closing, by hand;
- the trace reduction, on a trace recorded on the chip and on one made
  by hand;
- the result line's schema, and no result without a TPU;
- the node-share step compiles for a described v5e at 524,288 CT slots.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import control  # noqa: E402
import deploy  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

TINY = {"endpoints": 6, "entries_per_endpoint": 96, "pods": 600,
        "cidrs": 24, "ct_slots": 65536, "policy_rows": 8,
        "policy_slots": 256}
# flows short enough that many close inside a tiny run
SHORT = {"kind": "lomax", "mean": 12, "alpha": 1.5, "max": 1000}
TINY_MIX = {"node-share.saturate": {"pool": 256, "submitters": 4,
                                    "flow_len": SHORT},
            "node-share.rr": {"pool": 256, "rate": 3000, "flow_len": SHORT},
            "netperf-pair.tcp-rr": {}}
SEED = 2 ** 31 + 77


def _cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    return cfg


def tiny_run(cell, seconds=1.0, hook=None, monkeypatch=None):
    """One run of ``cell`` at the tiny size, on the CPU."""
    if monkeypatch is not None:
        # open-loop cells warm every bucket up to the lane's max batch;
        # at this size 128 rows is the most a launch can hold
        monkeypatch.setattr(run, "MAX_BATCH", 128)
    ov = {"traffic": dict(TINY_MIX[cell], check_one_in=1)}
    if cell.startswith("node-share"):
        ov["config"] = TINY
    return run.main(["--workload", cell, "--seed", str(SEED),
                     "--seconds", str(seconds), "--trace", "0"],
                    require_tpu=False, hook=hook, overrides=ov)


def test_generator_same_seed_same_traffic():
    dep = deploy.build(_cfg("node-share"), 5)
    again = deploy.build(_cfg("node-share"), 5)
    assert dep.prefixes == again.prefixes
    mix = traffic.load_mix("saturate")

    def rounds(seed):
        pool = traffic.Pool(traffic.FlowSource(dep, mix, seed, 1, 4), 64,
                            sample_mod=4)
        return [pool.round() for _ in range(300)]   # flows end and renew

    a, b, c = rounds(SEED), rounds(SEED), rounds(SEED + 1)
    for (ra, ma), (rb, mb) in zip(a, b):
        for f in traffic.FIELDS:
            np.testing.assert_array_equal(ra[f], rb[f])
        np.testing.assert_array_equal(ma["sampled"], mb["sampled"])
    assert any((ra["sport"] != rc["sport"]).any()
               for (ra, _m), (rc, _n) in zip(a, c))
    rr = dict(traffic.load_mix("rr"), rate=5000)
    t1, s1 = traffic.open_schedule(rr, SEED, 2.0)
    t2, s2 = traffic.open_schedule(rr, SEED, 2.0)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(s1, s2)
    assert s1.min() >= 1 and s1.max() <= 64


def test_tuples_never_repeat():
    """Conntrack entries of two flows never meet: every flow of a pool
    has its own 5-tuple."""
    dep = deploy.build(_cfg("node-share"), 5)
    mix = traffic.load_mix("saturate")
    seen = set()
    for s in range(4):
        src = traffic.FlowSource(dep, mix, SEED, s, 4)
        fl = src.flows(0, 20000)
        for t in zip(fl["caddr"].tolist(), fl["saddr"].tolist(),
                     fl["cport"].tolist(), fl["sport"].tolist(),
                     fl["proto"].tolist()):
            assert t not in seen
            seen.add(t)


@pytest.mark.parametrize("cell", sorted(TINY_MIX))
def test_reference_agrees_with_served_path(cell, monkeypatch, capsys):
    res = tiny_run(cell, seconds=1.5, monkeypatch=monkeypatch)
    assert res["correct"], res["checks"]
    assert res["checks"]["records_checked"][0] > 50
    # the result line: the last stdout line, its keys, checks last
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    if cell.startswith("node-share"):
        # records after a FIN met their closing entries, and agreed
        assert json.loads(out[-2])["closing_checked"] > 20
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" in line["device"]
    names = set(line["metrics"])
    want = {m["name"] for m in run.load_bench()["end_to_end"]
            if run.applies(m, cell)}
    assert names == want
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) or isinstance(m["value"], int)
        assert m["value"] > 0


@pytest.mark.parametrize("cell", sorted(TINY_MIX))
def test_control_in_the_programs_place_is_not_correct(cell, monkeypatch):
    """The control (the reference without conntrack) answers through the
    lane and the same checks: replies and established flows lose their
    verdicts, so the run is not correct; identities stay right."""
    res = tiny_run(cell, seconds=1.5, hook=control.install,
                   monkeypatch=monkeypatch)
    assert not res["correct"]
    assert res["checks"]["verdict_mismatches"][0] > 0
    assert res["checks"]["identity_mismatches"][0] == 0


def _break(kind):
    """Break the timed path underneath the lane: ``frozen`` - the step
    hands back its conntrack state unchanged; ``half`` - half of each
    batch's answers left out; ``flip`` - one answer altered where it is
    produced."""
    def hook(system, _dep):
        dp = system.dp

        def wrap(orig):
            def step(packed, now=None, payload=None):
                if kind == "frozen":
                    import jax.numpy as jnp
                    saved = type(dp.ct.state)(*(jnp.array(b)
                                                for b in dp.ct.state))
                    out = orig(packed, now, payload)
                    dp.ct.state = saved
                    return out
                v, ev, ident, nat = orig(packed, now, payload)
                v, ident = np.array(v), np.array(ident)
                if kind == "half":
                    v[: (len(v) + 1) // 2] = 0
                    ident[: (len(v) + 1) // 2] = 0
                else:
                    v[0] = -7
                return v, ev, ident, nat
            return step
        system.wrap_step(wrap)
    return hook


@pytest.mark.parametrize("kind", ["frozen", "half", "flip"])
def test_broken_timed_path_is_not_correct(kind, monkeypatch):
    res = tiny_run("netperf-pair.tcp-rr", seconds=1.0, hook=_break(kind),
                   monkeypatch=monkeypatch)
    assert not res["correct"]
    assert res["checks"]["verdict_mismatches"][0] + \
        res["checks"]["identity_mismatches"][0] > 0


def test_reference_tiers_and_conntrack():
    """The reference by hand: the three policy tiers, a reply, and an
    answer that may have seen an earlier record or not."""
    row = {"ident": np.array([300, 300, 0]), "port": np.array([80, 0, 53]),
           "proto": np.array([6, 0, 17]), "dir": np.array([0, 0, 0]),
           "proxy": np.array([15000, 0, 0])}
    pol = reference.Policy(row)
    assert pol.verdict(300, 80, 6, 0) == 15000      # exact, redirect
    assert pol.verdict(300, 9, 6, 0) == 0           # L3-only
    assert pol.verdict(300, 9, 6, 1) == -1          # other direction
    assert pol.verdict(7, 53, 17, 0) == 0           # L4-wildcard
    assert pol.verdict(7, 80, 6, 0) == -1           # drop
    dep = deploy.Deployment([row], {"10.0.0.1/32": 300}, [0x0AFF0000],
                            [0x0A000001], [300])
    ref = reference.Reference(dep)
    peer, local = 0x0A000001, 0x0AFF0000
    rec = {"endpoint": np.zeros(3, np.int64),
           "saddr": np.array([peer, local, peer]),
           "daddr": np.array([local, peer, local]),
           "sport": np.array([5000, 80, 5000]),
           "dport": np.array([80, 5000, 80]),
           "proto": np.full(3, 6), "direction": np.array([0, 1, 0]),
           "tcp_flags": np.array([0x02, 0x12, 0x10]),
           "flow": np.zeros(3, np.int64), "k": np.arange(3),
           "side": np.zeros(3, np.int64),
           "submit": np.array([0.0, 2.0, 3.0]),
           "resolve": np.array([1.0, 2.5, 3.5])}
    ident = np.full(3, 300)
    # SYN redirected, the reply allowed through conntrack, then the
    # established flow keeps its proxy port
    assert ref.check(rec, np.array([15000, 0, 15000]), ident)[:2] == (0, 0)
    assert ref.check(rec, np.array([15000, -1, 15000]), ident)[0] == 1
    # the reply sent before the SYN was answered may have missed it
    rec["submit"] = np.array([0.0, 0.5, 3.0])
    assert ref.check(rec, np.array([15000, 0, 15000]), ident)[0] == 0
    assert ref.check(rec, np.array([15000, -1, 15000]), ident)[0] == 0


def _one_flow(packets):
    """A flow from a peer to a local endpoint on port 80, redirected to
    the proxy by policy: ``packets`` is (forward?, flags, submit,
    resolve) each; a forward packet is an ingress record, a reply an
    egress one."""
    row = {"ident": np.array([300]), "port": np.array([80]),
           "proto": np.array([6]), "dir": np.array([0]),
           "proxy": np.array([15000])}
    dep = deploy.Deployment([row], {"10.0.0.1/32": 300}, [0x0AFF0000],
                            [0x0A000001], [300])
    peer, local = 0x0A000001, 0x0AFF0000
    fwd = np.array([p[0] for p in packets])
    n = len(packets)
    rec = {"endpoint": np.zeros(n, np.int64),
           "saddr": np.where(fwd, peer, local),
           "daddr": np.where(fwd, local, peer),
           "sport": np.where(fwd, 5000, 80), "dport": np.where(fwd, 80, 5000),
           "proto": np.full(n, 6), "direction": np.where(fwd, 0, 1),
           "tcp_flags": np.array([p[1] for p in packets]),
           "flow": np.zeros(n, np.int64), "k": np.arange(n),
           "side": np.zeros(n, np.int64),
           "submit": np.array([p[2] for p in packets], float),
           "resolve": np.array([p[3] for p in packets], float)}
    return reference.Reference(dep), rec, np.full(n, 300)


def test_reference_conntrack_closing():
    """FIN, FIN back and the last ACK meet a closing entry and still
    follow it; once both sides have closed, the entry may answer or be
    gone after the close timeout; a SYN reopens a closing entry."""
    S, SA, A, FA = 0x02, 0x12, 0x10, 0x11
    ref, rec, ident = _one_flow([
        (True, S, 0, 0.1), (False, SA, 1, 1.1), (True, A, 2, 2.1),
        (True, FA, 3, 3.1), (False, FA, 4, 4.1), (True, A, 5, 5.1)])
    follow = np.array([15000, 0, 15000, 15000, 0, 15000])
    assert ref.check(rec, follow, ident)[:2] == (0, 0)
    # a closing entry that answered as new (policy: the reply drops)
    assert ref.check(rec, np.array([15000, 0, 15000, 15000, -1, 15000]),
                     ident)[0] == 1
    # 20 s after both sides closed: the entry may have expired (the
    # reply then meets policy, and drops) or not yet been collected
    ref, rec, ident = _one_flow([
        (True, S, 0, 0.1), (True, FA, 1, 1.1), (False, FA, 2, 2.1),
        (False, A, 22, 22.1)])
    for late in (0, -1):
        v = np.array([15000, 15000, 0, late])
        bad_v, _bi, _n, ambiguous, _e = ref.check(rec, v, ident)
        assert (bad_v, ambiguous) == (0, 1)
    # one side closing only: the entry lives on, renewed by traffic
    ref, rec, ident = _one_flow([
        (True, S, 0, 0.1), (True, FA, 1, 1.1), (False, A, 22, 22.1)])
    assert ref.check(rec, np.array([15000, 15000, -1]), ident)[0] == 1
    # both closed, then a SYN reopens the entry before it expires: 20 s
    # later it still answers
    ref, rec, ident = _one_flow([
        (True, S, 0, 0.1), (True, FA, 1, 1.1), (False, FA, 2, 2.1),
        (True, S, 3, 3.1), (False, A, 23, 23.1)])
    assert ref.check(rec, np.array([15000, 15000, 0, 15000, -1]),
                     ident)[0] == 1


def test_generator_close_handshake():
    """A TCP flow opens with a SYN and ends FIN, FIN back, ACK; one
    shorter than five packets ends with one forward FIN; a one-packet
    flow is a bare SYN."""
    dep = deploy.build(_cfg("netperf-pair"), 5)
    client = np.uint32(dep.local_addr[0]).view(np.int32)

    def flow(n):
        fl = {"c_ep": np.array([0]), "s_ep": np.array([1]),
              "caddr": dep.local_addr[:1].astype(np.int64),
              "saddr": dep.local_addr[1:].astype(np.int64),
              "cport": np.array([40000]), "sport": np.array([12866]),
              "proto": np.array([6]), "len": np.array([n])}
        out = []
        for k in range(n):
            rec, m = traffic.packets({"length": 64}, SEED, 0, fl, [3], [k])
            snd = rec["saddr"][m["side"] == 0][0]
            out.append((bool(snd == client), int(rec["tcp_flags"][0])))
        return out

    hs = flow(8)
    assert hs[0] == (True, traffic.SYN)
    assert all(f in (traffic.ACK, traffic.SYN | traffic.ACK)
               for _d, f in hs[1:5])
    closer = hs[5][0]
    assert hs[5:] == [(closer, traffic.FIN | traffic.ACK),
                      (not closer, traffic.FIN | traffic.ACK),
                      (closer, traffic.ACK)]
    short = flow(3)
    assert short[-1] == (True, traffic.FIN | traffic.ACK)
    assert all(not f & traffic.FIN for _d, f in short[:-1])
    assert flow(1) == [(True, traffic.SYN)]


def test_generator_hot_shift_and_peer_share():
    """``hot_shift`` moves the Zipf hot set every so many flows;
    ``pod_peer_share`` sets how many missed flows go to cluster pods."""
    dep = deploy.build(_cfg("node-share"), 5)
    base = dict(traffic.load_mix("saturate"), hit_share=0.0)
    pods = set(dep.pod_addr.tolist())

    def peers(mix, start):
        fl = traffic.FlowSource(dep, mix, SEED, 0).flows(start, 8000)
        ingress = fl["s_ep"] >= 0
        return np.where(ingress, fl["caddr"], fl["saddr"]).tolist()

    def pod_share(mix):
        p = peers(mix, 0)
        return sum(a in pods for a in p) / len(p)

    assert 0.4 < pod_share(base) < 0.6
    assert pod_share(dict(base, pod_peer_share=1.0)) > 0.99
    top = int(dep.pod_addr[0])
    chunk = traffic.FlowSource.CHUNK
    shifted = dict(base, pod_peer_share=1.0,
                   hot_shift={"every_flows": chunk, "by": 100})
    first, second = peers(shifted, 0), peers(shifted, chunk)
    assert first.count(top) > 100 and second.count(top) < 5
    assert second.count(int(dep.pod_addr[100])) > 100
    with pytest.raises(ValueError):
        traffic.FlowSource(dep, dict(base, hot_shift={"every_flows": 5,
                                                      "by": 1}), SEED, 0)


def _recorded():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tcp_rr_trace.json")) as f:
        return json.load(f)


def test_trace_reduction_recorded_chip_trace():
    red = trace_reduce.reduce_planes(_recorded())
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"] < 3.1
    assert red["step"]["name"] == "jit_g"
    assert red["step"]["launches"] > 10
    bd = red["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    assert all(label.startswith("bench.") or label == "none"
               for label, _s in bd["idle_gaps"])


def test_trace_reduction_by_hand():
    ms = 1_000_000
    planes = [
        ("/host:CPU", [("python3", [("bench.window", 0, 10 * ms),
                                    ("bench.wait", 4 * ms, 3 * ms)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_g(1)", 1 * ms, 3 * ms),
                             ("jit_h(2)", 8 * ms, 1 * ms)]),
            ("XLA Ops", [("a", 1 * ms, 2 * ms), ("b", 2 * ms, 2 * ms),
                         ("c", 8 * ms, 1 * ms), ("d", 11 * ms, 1 * ms)])])]
    red = trace_reduce.reduce_planes(planes)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.004)   # [1,4] + [8,9] ms
    assert red["step"] == {"name": "jit_g", "launches": 1,
                           "seconds": pytest.approx(0.003)}
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(0.004)]   # [4, 8] ms
    import readers
    ctx = {"trace": red}
    assert readers.idle_share(ctx) == pytest.approx(60.0)
    assert readers.step_device_us(ctx) == pytest.approx(3000.0)


def test_no_tpu_no_result(tmp_path):
    with pytest.raises(run.NoDevice):
        run.main(["--workload", "netperf-pair.tcp-rr", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = [sys.executable, "benchmark/run.py", "--workload",
            "netperf-pair.tcp-rr", "--seed", "1", "--seconds", "1",
            "--trace", "0"]
    p = subprocess.run(args, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 3 and p.stdout.strip() == ""
    # a checkout that holds only the benchmark's files has no program
    import shutil
    shutil.copytree(BENCH, tmp_path / "benchmark")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(args, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# ------------------------------------------- compile for a described v5e

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows", [16, 32768])
def test_node_share_step_compiles_for_v5e(one_chip, rows):
    """The node-share packed step at its served geometry (128 x 32,768
    policy slots, 152,112 prefixes, 524,288 CT slots, flows on), at the
    cells' smallest and largest launch.  One endpoint's row is filled:
    the geometry, not the content, is what compiles."""
    import jax
    import sut
    from cilium_tpu.datapath.pipeline import PACKED_FIELDS
    with open(os.path.join(BENCH, "configs", "node-share.json")) as f:
        cfg = json.load(f)
    dep = deploy.build(cfg, cfg["deployment_seed"])
    dep.policy = dep.policy[:1]
    system = sut.System(cfg, dep)
    try:
        assert system.geometry()["ct_slots"] == 524288
        assert system.geometry()["slots"] == 32768
        packed = np.zeros((len(PACKED_FIELDS), rows), np.int32)
        args = system.dp._lower_args_packed(packed)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                           sharding=one_chip), args)
        compiled = system.dp._step_packed.lower(*shapes).compile()
        assert compiled.memory_analysis().argument_size_in_bytes > 0
    finally:
        system.close()
