"""Tests for cross-cutting utils: controller, trigger, completion,
revert, backoff, option, spanstat, metrics.

Modeled on the reference's pkg/{controller,trigger,completion,revert,
option}/..._test.go behaviors.
"""

import threading
import time

import pytest

from cilium_tpu.utils import (Completion, Controller, ControllerManager,
                              ControllerParams, Exponential, IntOptions,
                              OptionSpec, RevertStack, SpanStat, Trigger,
                              WaitGroup)
from cilium_tpu.utils.metrics import Registry
from cilium_tpu.utils.option import (DAEMON_OPTION_LIBRARY, OPTION_ENABLED,
                                     parse_option_value)


# ---------------------------------------------------------------- controller

def test_controller_runs_and_retries():
    calls = []
    fail_until = 2

    def do():
        calls.append(1)
        if len(calls) <= fail_until:
            raise RuntimeError("transient")

    mgr = ControllerManager()
    ctrl = mgr.update_controller(
        "test", ControllerParams(do_func=do, error_retry_base=0.01))
    deadline = time.time() + 5
    while len(calls) < 3 and time.time() < deadline:
        time.sleep(0.01)
    assert len(calls) >= 3
    assert ctrl.status.failure_count == 2
    assert ctrl.status.success_count >= 1
    assert ctrl.status.consecutive_failures == 0
    mgr.remove_all()


def test_controller_update_replaces_func():
    a_calls, b_calls = [], []
    mgr = ControllerManager()
    mgr.update_controller("x", ControllerParams(
        do_func=lambda: a_calls.append(1)))
    time.sleep(0.05)
    # same name => replace, not a second controller
    mgr.update_controller("x", ControllerParams(
        do_func=lambda: b_calls.append(1)))
    deadline = time.time() + 5
    while not b_calls and time.time() < deadline:
        time.sleep(0.01)
    assert b_calls
    status = mgr.status_model()
    assert [s["name"] for s in status] == ["x"]
    assert mgr.remove_controller("x")
    assert not mgr.remove_controller("x")


def test_controller_interval():
    calls = []
    mgr = ControllerManager()
    mgr.update_controller("tick", ControllerParams(
        do_func=lambda: calls.append(time.time()), run_interval=0.02))
    deadline = time.time() + 5
    while len(calls) < 3 and time.time() < deadline:
        time.sleep(0.01)
    assert len(calls) >= 3
    mgr.remove_all()


# ------------------------------------------------------------------- trigger

def test_trigger_folds_bursts():
    runs = []
    got = threading.Event()

    def fn(reasons):
        runs.append(reasons)
        got.set()

    t = Trigger(fn, min_interval=0.05, name="t")
    for i in range(10):
        t.trigger(f"r{i % 2}")
    assert got.wait(5)
    time.sleep(0.15)
    t.shutdown()
    # 10 triggers folded into far fewer runs; reasons deduplicated
    assert 1 <= len(runs) <= 3
    assert set(runs[0]) <= {"r0", "r1"}


def test_trigger_min_interval_spacing():
    stamps = []
    t = Trigger(lambda r: stamps.append(time.time()), min_interval=0.05)
    t.trigger()
    time.sleep(0.01)
    t.trigger()
    deadline = time.time() + 5
    while len(stamps) < 2 and time.time() < deadline:
        time.sleep(0.005)
    t.shutdown()
    assert len(stamps) >= 2
    assert stamps[1] - stamps[0] >= 0.04


# ---------------------------------------------------------------- completion

def test_completion_waitgroup():
    wg = WaitGroup()
    c1 = wg.add_completion()
    c2 = wg.add_completion()
    assert not wg.wait(timeout=0.05)
    c1.complete()
    assert not wg.wait(timeout=0.05)
    c2.complete()
    assert wg.wait(timeout=1)
    assert c1.completed and c2.completed


def test_completion_callback_once():
    hits = []
    c = Completion(on_complete=lambda: hits.append(1))
    c.complete()
    c.complete()
    assert hits == [1]


# -------------------------------------------------------------------- revert

def test_revert_stack_lifo():
    order = []
    st = RevertStack()
    st.push(lambda: order.append("a"))
    st.push(lambda: order.append("b"))
    st.revert()
    assert order == ["b", "a"]
    st.revert()  # stack cleared
    assert order == ["b", "a"]


def test_revert_stack_error_propagates_but_all_run():
    order = []
    st = RevertStack()
    st.push(lambda: order.append("a"))

    def boom():
        order.append("boom")
        raise ValueError("x")

    st.push(boom)
    with pytest.raises(ValueError):
        st.revert()
    assert order == ["boom", "a"]


# ------------------------------------------------------------------- backoff

def test_backoff_growth_and_cap():
    b = Exponential(min_s=0.1, max_s=0.5, factor=2.0)
    assert b.duration(0) == pytest.approx(0.1)
    assert b.duration(1) == pytest.approx(0.2)
    assert b.duration(10) == pytest.approx(0.5)  # capped
    ev = threading.Event()
    ev.set()
    assert b.wait(ev) is False  # pre-set event interrupts immediately


# ------------------------------------------------------------------- options

def test_options_enable_pulls_requires():
    opts = IntOptions()
    changed = []
    n = opts.apply_validated({"ConntrackAccounting": 1},
                             changed=lambda k, v: changed.append((k, v)))
    # enabling accounting enables Conntrack too
    assert n == 2
    assert opts.is_enabled("Conntrack")
    assert opts.is_enabled("ConntrackAccounting")
    assert ("Conntrack", 1) in changed


def test_options_disable_cascades_dependents():
    opts = IntOptions()
    opts.apply_validated({"ConntrackAccounting": 1})
    n = opts.apply_validated({"Conntrack": 0})
    assert n == 2  # both disabled
    assert not opts.is_enabled("ConntrackAccounting")


def test_options_unknown_and_immutable_rejected():
    opts = IntOptions()
    with pytest.raises(KeyError):
        opts.apply_validated({"NoSuchOption": 1})
    lib = dict(DAEMON_OPTION_LIBRARY)
    lib["Frozen"] = OptionSpec("Frozen", immutable=True)
    opts2 = IntOptions(library=lib)
    with pytest.raises(ValueError):
        opts2.apply_validated({"Frozen": 1})


def test_options_fork_is_independent():
    parent = IntOptions(defaults={"Policy": 1})
    child = parent.fork()
    child.apply_validated({"Policy": 0})
    assert parent.is_enabled("Policy")
    assert not child.is_enabled("Policy")


def test_parse_option_value():
    assert parse_option_value("true") == OPTION_ENABLED
    assert parse_option_value("Disabled") == 0
    assert parse_option_value(True) == 1
    with pytest.raises(ValueError):
        parse_option_value("maybe")


# ------------------------------------------------------------------ spanstat

def test_spanstat_success_failure_split():
    s = SpanStat()
    with s:
        pass
    try:
        with s:
            raise RuntimeError()
    except RuntimeError:
        pass
    assert s.num_success == 1
    assert s.num_failure == 1
    assert s.seconds() >= 0


# ------------------------------------------------------------------- metrics

def test_metrics_counter_gauge_histogram_exposition():
    reg = Registry(namespace="t")
    c = reg.counter("hits", "hits")
    c.inc()
    c.inc(2, labels={"reason": "policy"})
    g = reg.gauge("eps")
    g.set(4)
    g.dec()
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.expose_text()
    assert 't_hits{reason="policy"} 2.0' in text
    assert "t_eps 3.0" in text
    assert 't_lat_bucket{le="0.1"} 1' in text
    assert 't_lat_bucket{le="+Inf"} 2' in text
    assert "# TYPE t_hits counter" in text
    assert c.value(labels={"reason": "policy"}) == 2.0
    # same-name registration returns the existing metric
    assert reg.counter("hits") is c


# --------------------------------------------- review-regression coverage

def test_options_cascade_respects_guards():
    # enabling A must fail atomically if a cascaded dep is immutable
    lib = {
        "A": OptionSpec("A", requires=["B"]),
        "B": OptionSpec("B", immutable=True),
    }
    opts = IntOptions(library=lib)
    with pytest.raises(ValueError):
        opts.apply_validated({"A": 1})
    assert not opts.is_enabled("A") and not opts.is_enabled("B")
    # unknown dep in the requires list also fails before mutation
    lib2 = {"A": OptionSpec("A", requires=["Missing"])}
    opts2 = IntOptions(library=lib2)
    with pytest.raises(KeyError):
        opts2.apply_validated({"A": 1})
    assert not opts2.is_enabled("A")


def test_completion_concurrent_complete_fires_once():
    hits = []
    c = Completion(on_complete=lambda: hits.append(1))
    threads = [threading.Thread(target=c.complete) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert hits == [1]


def test_metrics_label_escaping():
    reg = Registry(namespace="esc")
    c = reg.counter("drops")
    c.inc(labels={"reason": 'CT "invalid"\nstate\\x'})
    text = reg.expose_text()
    assert 'reason="CT \\"invalid\\"\\nstate\\\\x"' in text


def test_metrics_kind_collision_raises():
    reg = Registry(namespace="k")
    reg.counter("hits")
    with pytest.raises(ValueError):
        reg.gauge("hits")


def test_probe_features():
    """Runtime capability probing (bpf/run_probes.sh analog)."""
    from cilium_tpu.utils.platform import probe_features
    f = probe_features()
    assert f["backend"] == "cpu"          # conftest pins CPU
    assert f["on_accelerator"] is False
    assert f["device_count"] == 8          # virtual mesh
    assert "hash" in f["verdict_engines"]
    assert "bucket2choice" in f["verdict_engines"]
    if f["native_fastpath"]:
        assert "host-cache" in f["verdict_engines"]


def test_status_reports_features():
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.utils.option import DaemonConfig
    d = Daemon(config=DaemonConfig())
    try:
        st = d.status()
        assert st["features"]["backend"] == "cpu"
        assert "verdict_engines" in st["features"]
    finally:
        d.shutdown()
