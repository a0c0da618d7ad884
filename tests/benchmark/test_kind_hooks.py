"""A configuration's kind brings its roles as files (``benchmark/byname.py``),
on the CPU at ``test_perfbench``'s tiny size.

- the shipped kinds (node-share, pair) resolve to the default system,
  reference and flows, with no events;
- kinds that exist only here, written to a temporary directory and found
  by pointing the lookup there, show each role used: a system subclass's
  counter, a flow source that restricts peers, a reference that
  disagrees, the control answering with the kind's reference, and
  events played inside the window (a policy replaced mid-window), whose
  reference holds the run to the change;
- a mix with events and a kind with no events file stops at set-up;
- the reference's side imports nothing of the program.
"""

import ast
import functools
import glob
import json
import os
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import byname  # noqa: E402
import control  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut  # noqa: E402
import traffic  # noqa: E402
from test_perfbench import SEED, TINY, TINY_MIX  # noqa: E402

CHECKS = ["verdict_mismatches", "identity_mismatches", "fail_static_batches",
          "failed_frames", "records_checked"]

# every test kind serves node-share's deployment
DEPLOYMENT = """
import byname


def build(cfg, seed):
    return byname.module("deployments", "node-share").build(cfg, seed)
"""

# roles of the kind "hooked": each leaves a mark of its use
HOOKED = {
    "systems": """
import sut


class System(sut.System):
    \"\"\"Counts the launches of its lane.\"\"\"

    def __init__(self, cfg, dep):
        super().__init__(cfg, dep)
        self.launches = 0

        def wrap(step):
            def counted(*args, **kw):
                self.launches += 1
                return step(*args, **kw)
            return counted
        self.wrap_step(wrap)
""",
    "flows": """
import numpy as np

import traffic

PEERS = 4


class FlowSource(traffic.FlowSource):
    \"\"\"Every remote peer is one of the deployment's PEERS most popular
    pods.\"\"\"

    def flows(self, start, n):
        fl = super().flows(start, n)
        pods = self.dep.pod_addr[:PEERS].astype(np.int64)
        pick = pods[np.arange(start, start + n) % PEERS]
        fl["caddr"] = np.where(fl["c_ep"] < 0, pick, fl["caddr"])
        fl["saddr"] = np.where(fl["s_ep"] < 0, pick, fl["saddr"])
        return fl
""",
    "references": """
import reference


class Reference(reference.Reference):
    \"\"\"Keeps what it checks and counts the control's calls.\"\"\"

    checked = []
    control_calls = 0

    def check(self, rec, verdict, identity, **kw):
        Reference.checked.append(rec)
        return super().check(rec, verdict, identity, **kw)

    def policy_only(self, rec):
        Reference.control_calls += 1
        return super().policy_only(rec)
""",
}

PLANTED = {"references": """
import reference


class Reference(reference.Reference):
    \"\"\"Disagrees: every peer resolves to world.\"\"\"

    def __init__(self, dep, clock_offset=0.0):
        super().__init__(dep, clock_offset)
        self.identity_of = lambda addr: reference.WORLD
"""}

# the kind "replace": one endpoint's policy replaced inside the window
REPLACE = {
    "systems": """
import sut


class System(sut.System):
    def apply(self, event):
        self.mgr.sync_endpoint(event["endpoint"] + 1,
                               sut.to_state(event["policy"]), revision=2)
        self.dp.refresh_policy()
""",
    "events": """
import numpy as np


def schedule(cfg, mix, dep, seed, seconds):
    \"\"\"At ``at_share`` of the window, ``endpoint``'s policy becomes
    empty: its new flows drop.  A second event falls after the window
    and is never played.\"\"\"
    p = mix["events"]
    empty = {k: np.zeros(0, np.int64)
             for k in ("ident", "port", "proto", "dir", "proxy")}
    event = {"endpoint": p["endpoint"], "policy": empty}
    return [(2.0 * seconds, event), (p["at_share"] * seconds, event)]
""",
    "references": """
import reference


class Reference(reference.Reference):
    \"\"\"A record that met no conntrack entry gets the old policy's
    verdict if answered before an event's start, the new one's if sent
    after its end, and either in between.\"\"\"

    def check(self, rec, verdict, identity, events=()):
        self.changes = [(ev["endpoint"], reference.Policy(ev["policy"]),
                         start, done)
                        for ev, _due, start, done, error in events
                        if error is None]
        return super().check(rec, verdict, identity)

    def policy_verdicts(self, ep, ident, dport, proto, dirn, submit,
                        resolve):
        want = super().policy_verdicts(ep, ident, dport, proto, dirn,
                                       submit, resolve)
        for e, pol, start, done in self.changes:
            if e == ep and resolve >= start:
                new = (pol.verdict(ident, dport, proto, dirn),)
                want = new if submit > done else tuple(set(want + new))
        return want
""",
}


def kind(tmp_path, name, roles):
    """Write kind ``name``'s files under ``tmp_path`` and look it up
    there."""
    files = dict(roles, deployments=DEPLOYMENT)
    for folder, text in files.items():
        (tmp_path / folder).mkdir(exist_ok=True)
        (tmp_path / folder / f"{name}.py").write_text(
            textwrap.dedent(text).lstrip())
    return byname.kind_parts(name, root=str(tmp_path))


def tiny(parts, monkeypatch, cell="node-share.rr", seconds=1.5, hook=None,
         mix=None):
    """One run of ``cell`` at the tiny size with ``parts``, on the CPU."""
    monkeypatch.setattr(run, "MAX_BATCH", 128)
    ov = {"config": dict(TINY, kind=parts.kind),
          "traffic": dict(TINY_MIX[cell], check_one_in=1, **(mix or {}))}
    return run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                     str(seconds), "--trace", "0"], require_tpu=False,
                    hook=hook, overrides=ov, parts=parts)


@pytest.mark.parametrize("name", ["node-share", "pair"])
def test_shipped_kinds_resolve_to_the_defaults(name):
    parts = byname.kind_parts(name)
    assert parts.System is sut.System
    assert parts.Reference is reference.Reference
    assert parts.FlowSource is traffic.FlowSource
    assert parts.events is None
    assert parts.deployment.__file__ == os.path.join(
        BENCH, "deployments", f"{name}.py")


@pytest.mark.parametrize("cell", ["node-share.rr", "node-share.saturate"])
def test_a_kinds_system_flows_and_reference_are_used(tmp_path, cell,
                                                     monkeypatch):
    """Open and closed loop: the kind's system serves every launch, its
    flow source picks every peer, its reference checks every record,
    and the run is correct with the five checks of a cell without
    events."""
    parts = kind(tmp_path, "hooked", HOOKED)
    built = []
    res = tiny(parts, monkeypatch, cell, hook=lambda system, dep:
               built.append((system, dep)))
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == CHECKS
    system, dep = built[0]
    assert type(system) is parts.System and system.launches > 10
    (rec,) = parts.Reference.checked
    assert len(rec["endpoint"]) > 50
    peer = np.where(rec["direction"] == 0, rec["saddr"], rec["daddr"])
    pods = dep.pod_addr[:4].tolist()
    assert set(peer.astype(np.uint32).tolist()) == set(pods)


def test_a_kinds_reference_that_disagrees_fails_the_run(tmp_path,
                                                        monkeypatch):
    parts = kind(tmp_path, "planted", PLANTED)
    res = tiny(parts, monkeypatch)
    assert not res["correct"]
    assert res["checks"]["identity_mismatches"][0] > 0


def test_the_control_answers_with_the_kinds_reference(tmp_path,
                                                      monkeypatch):
    parts = kind(tmp_path, "hooked", HOOKED)
    res = tiny(parts, monkeypatch, hook=functools.partial(
        control.install, Reference=parts.Reference))
    assert not res["correct"]
    assert res["checks"]["verdict_mismatches"][0] > 0
    assert parts.Reference.control_calls > 10


def _no_op(system, _dep):
    system.apply = lambda event: None


def _raising(system, _dep):
    def apply(event):
        raise RuntimeError("planted: the event fails")
    system.apply = apply


@pytest.mark.parametrize("fault", [None, "no-op", "raising"])
def test_events_are_played_inside_the_window(tmp_path, fault, monkeypatch,
                                             capsys):
    """One endpoint's policy is replaced mid-window; the kind's
    reference holds the served verdicts to the change.  Applied, the run
    is correct; not applied, or failing, it is not."""
    parts = kind(tmp_path, "replace", REPLACE)
    hook = {None: None, "no-op": _no_op, "raising": _raising}[fault]
    res = tiny(parts, monkeypatch, seconds=2.0, hook=hook,
               mix={"events": {"at_share": 0.4, "endpoint": 0}})
    checks = res["checks"]
    assert list(checks) == CHECKS + ["failed_events", "events_played"]
    assert checks["events_played"][0] == 1     # the later one is not due
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-2])
    assert info["events_played"] == 1
    p50, top = info["event_apply_s"]
    assert 0 <= p50 <= top
    if fault is None:
        assert res["correct"], checks
    elif fault == "no-op":
        assert not res["correct"]
        assert checks["verdict_mismatches"][0] > 0
        assert checks["failed_events"][0] == 0
    else:
        assert not res["correct"]
        assert checks["failed_events"][0] == 1


def test_a_mix_with_events_needs_an_events_file(monkeypatch):
    """node-share has no events file: the run stops before it builds
    anything."""
    with pytest.raises(RuntimeError, match="no events file"):
        tiny(byname.kind_parts("node-share"), monkeypatch,
             mix={"events": {}})


def _imports(path):
    tree = ast.parse(open(path).read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module


PROGRAM = ("cilium_tpu", "sut")


def _program_imports(path):
    return [m for m in _imports(path) if m.split(".")[0] in PROGRAM]


def test_the_references_side_imports_nothing_of_the_program():
    """Only ``sut.py`` and ``systems/`` may import the program."""
    paths = [os.path.join(BENCH, f"{m}.py")
             for m in ("reference", "traffic", "deploy")]
    for folder in ("references", "flows", "events", "deployments"):
        paths += glob.glob(os.path.join(BENCH, folder, "*.py"))
    assert len(paths) >= 5
    for path in paths:
        assert _program_imports(path) == [], path
    # the scan finds what is there
    assert "cilium_tpu.datapath.engine" in _program_imports(
        os.path.join(BENCH, "sut.py"))
