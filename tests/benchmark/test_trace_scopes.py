"""The trace reduction with the program's names (``benchmark/
trace_scopes.py``), and the readers of the program's spans and counters
(``benchmark/metrics/``), on the CPU:

- the recorded chip trace reduces exactly as ``trace_reduce`` reduces
  it (a program without scopes or spans adds nothing but its new keys);
- a hand-built trace gives per-stage device time, the ``mixed`` and
  ``unscoped`` buckets, program span totals and ``/``-joined gap labels;
- each instruction's stage, and the fusions that mix two, are read
  from a program's HLO text, and a real trace's own copy of its
  programs' HLO is found and read;
- the lane, GC and oracle-refresh readers read the stage report, and
  read nothing from a program that has no such stages.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import byname  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scopes  # noqa: E402

MS = 1_000_000


def _recorded():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tcp_rr_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_as_before():
    old = trace_reduce.reduce_planes(_recorded())
    new = trace_scopes.reduce_planes(_recorded())
    assert {k: new[k] for k in old} == old
    assert new["program_spans"] == {}
    assert sum(new["scopes"].values()) == pytest.approx(
        new["scopes"]["unscoped"])
    assert new["scopes"]["unscoped"] > 0


def _hand_built():
    """A 20 ms window: the step runs at 1-5 ms and 12-14 ms; between,
    the host sits in a GC pass (6-10 ms) inside ``bench.submit``."""
    stats = {"device_duration_ps": 1}
    host = [("bench.window", 0, 20 * MS),
            ("bench.submit", 5 * MS, 7 * MS),
            ("runtime.gc-gen2", 6 * MS, 4 * MS),
            ("serving-verdict.dispatch", 0, 1 * MS, {"launch": 1}),
            ("supervisor.oracle-refresh", 11 * MS, 4 * MS),
            ("serving-verdict.pack", 30 * MS, 1 * MS)]   # after
    dev = [("XLA Modules", [("jit_step(1)", 1 * MS, 4 * MS),
                            ("jit_step(1)", 12 * MS, 2 * MS),
                            ("jit_other(2)", 16 * MS, 1 * MS)]),
           ("XLA Ops", [
               ("%gather.1 = s32[8] gather()", 1 * MS, 2 * MS, stats),
               ("%fusion.2 = s32[8] fusion(), calls=%fc.2", 3 * MS,
                1 * MS, stats),
               ("%fusion.3 = s32[8] fusion(), calls=%fc.3", 4 * MS,
                1 * MS, stats),
               ("%slice.4 = s32[8] slice()", 12 * MS, 2 * MS, stats),
               ("%add.5 = s32[8] add()", 16 * MS, 1 * MS, stats)])]
    return [("/host:CPU", [("python3", host)]), ("/device:TPU:0", dev)]


def test_hand_built_trace_names_stages_spans_and_gaps():
    programs = {"jit_step(1)": {"gather.1": "policy", "fusion.2": "ipcache",
                                "fusion.3": "mixed", "slice.4": None},
                "jit_other(2)": {"add.5": "policy"}}
    red = trace_scopes.reduce_planes(_hand_built(), programs=programs)
    old = trace_reduce.reduce_planes(
        [(p, [(ln, [e[:3] for e in ev]) for ln, ev in lines])
         for p, lines in _hand_built()])
    for key in ("window_s", "busy_s", "devices", "modules", "step"):
        assert red[key] == old[key]
    sc = red["scopes"]
    # the step's ops only (jit_other's add is outside it)
    assert sc["policy"] == pytest.approx(0.002)
    assert sc["ipcache"] == pytest.approx(0.001)
    assert sc["mixed"] == pytest.approx(0.001)
    assert sc["unscoped"] == pytest.approx(0.002)
    assert sum(sc.values()) == pytest.approx(red["step"]["seconds"])
    assert red["program_spans"] == {
        "runtime.gc-gen2": {"count": 1, "seconds": pytest.approx(0.004)},
        "serving-verdict.dispatch": {"count": 1,
                                     "seconds": pytest.approx(0.001)},
        "supervisor.oracle-refresh": {"count": 1,
                                      "seconds": pytest.approx(0.004)}}
    assert red["breakdown"]["idle_gaps"] == [
        ["bench.submit/runtime.gc-gen2", pytest.approx(0.007)],  # 5-12
        ["none", pytest.approx(0.003)],                          # 17-20
        ["none/supervisor.oracle-refresh", pytest.approx(0.002)],
        ["none/serving-verdict.dispatch", pytest.approx(0.001)]]  # 0-1
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["policy:%gather.1 = s32[8] gather()"] == \
        pytest.approx(0.002)
    assert ops["%slice.4 = s32[8] slice()"] == pytest.approx(0.002)


HLO = """\
HloModule jit_step, entry_computation_layout={()->s32[8]{0}}

%fc.2 (p.1: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  %g.1 = s32[8]{0} gather(%p.1), metadata={op_name="jit(step)/ipcache/gather"}
  ROOT %a.1 = s32[8]{0} add(%g.1, %g.1), metadata={op_name="jit(step)/policy/add"}
}

%fc.3 (p.2: s32[8]) -> s32[8] {
  %p.2 = s32[8]{0} parameter(0)
  ROOT %a.2 = s32[8]{0} add(%p.2, %p.2), metadata={op_name="jit(step)/ct/add"}
}

ENTRY %main.9 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %fusion.2 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fc.2, metadata={op_name="jit(step)/policy/add"}
  ROOT %fusion.3 = s32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fc.3, metadata={op_name="jit(step)/ct/add"}
}
"""


def test_instruction_scopes_from_program_text():
    scopes = trace_scopes.instruction_scopes(HLO)
    assert scopes["fusion.2"] == "mixed"      # ipcache gather + policy add
    assert scopes["fusion.3"] == "ct"
    assert scopes["g.1"] == "ipcache" and scopes["x"] is None
    event = "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %x), kind=kLoop, " \
        "calls=%fc.2"
    assert scopes[trace_scopes._instr(event)] == "mixed"


def test_a_traces_own_programs_are_read(tmp_path):
    """A profile keeps the HLO of each program it ran: the stages of a
    jitted function's instructions come back from the trace alone."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def staged(x):
        with jax.named_scope("policy"):
            y = x * 3
        with jax.named_scope("ipcache"):
            return (y + 1).sum()

    x = jnp.arange(64.0)
    staged(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        staged(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    with open(path, "rb") as f:
        programs = trace_scopes.program_scopes(
            f.read(), keep=lambda n: n.startswith("jit_staged("))
    assert len(programs) == 1
    found = set(next(iter(programs.values())).values())
    assert found & {"policy", "mixed"} and found & {"ipcache", "mixed"}


def _stages(fam, **stages):
    return {fam: {k: {"count": c, "total-s": t}
                  for k, (c, t) in stages.items()}}


def _ctx(before, after, seconds=10.0):
    return {"stages": (before, after), "window": (0.0, seconds, seconds)}


def test_lane_host_reader():
    read = byname.module("metrics", "lane_host_us.rr").read
    fam = "serving-verdict"
    before = _stages(fam, dispatch=(10, 0.01), handoff=(10, 0.002),
                     resolve=(10, 0.001))
    after = _stages(fam, dispatch=(110, 0.03), handoff=(110, 0.012),
                    resolve=(110, 0.006))
    # per launch: 200 + 100 + 50 us
    assert read(_ctx(before, after)) == pytest.approx(350.0)
    old = _stages(fam, dispatch=(110, 0.03))     # a lane without them
    assert read(_ctx(_stages(fam, dispatch=(10, 0.01)), old)) is None


def test_gc_pause_reader():
    read = byname.module("metrics", "gc_pause_ms_per_s.rr").read
    gens = {f"gc-gen{g}": (0, 0.0) for g in range(3)}
    before = _stages("runtime", **gens)
    after = _stages("runtime", **dict(gens, **{"gc-gen0": (40, 0.004),
                                               "gc-gen2": (1, 0.150)}))
    assert read(_ctx(before, after, 20.0)) == pytest.approx(7.7)
    assert read(_ctx(before, before, 20.0)) == 0.0   # none: a reading
    assert read(_ctx({}, {}, 20.0)) is None          # not timed at all


def test_oracle_refresh_reader():
    read = byname.module("metrics", "oracle_refresh_ms.rr").read
    before = _stages("supervisor", **{"oracle-refresh": (2, 0.5)})
    after = _stages("supervisor", **{"oracle-refresh": (6, 0.9)})
    assert read(_ctx(before, after)) == pytest.approx(100.0)
    assert read(_ctx(after, after)) is None          # none in the window
    assert read(_ctx({}, {})) is None
