#!/usr/bin/env python3
"""The benchmark of the served verdict path: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> \\
        --seconds <window> --trace <0|1>

Everything a cell is made of is found by name in ``BENCHMARK.json``:
its configuration file, its traffic mix (``traffic/<mix>.json``, data
for the one generator in ``traffic.py``), and one reader per metric
(``metrics/<metric>.py``): the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``.  The
configuration's ``kind`` brings the cell's roles (``byname.py``), each
resolved once per run: its deployment (``deployments/<kind>.py``), and
where the kind has a file of its own, its system (``systems/``, else
``sut.System``), its reference (``references/``, else
``reference.Reference``), its flows (``flows/``, else
``traffic.FlowSource``) and its events (``events/``).  Records enter
through the system's serving lane (``Datapath.serving().submit_records``
by default), configured as the daemon configures it, and come back
through their tickets.

Events: a mix with an ``"events"`` key (the schedule's parameters) has
its kind's ``events/<kind>.py`` build ``[(at_s, event), ...]`` at
set-up; a kind without that file stops there.  One thread,
``bench-events``, plays the events due inside the window, each at
``t0 + at_s`` through ``system.apply(event)``, stamps it ``(due,
start, done, error)`` on the host clock, and hands the stamps to the
reference's check.  Warm-up and pre-roll play none.  Only such a cell
gains the checks ``failed_events`` and ``events_played``.

The last stdout line is the result (``PERF.md`` §2); the last stderr
lines are each compared number beside its limit.  A run on anything but
a TPU, or with fewer chips than the cell asks for, prints no result and
exits 3.
"""

import time

T_PROCESS = time.perf_counter()
# the program's conntrack clock is whole seconds of time.time(); the
# reference reads the host clock as perf_counter() + this
CLOCK_OFFSET = time.time() - T_PROCESS

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)     # the program under test

import byname  # noqa: E402
import traffic  # noqa: E402
from traffic import FIELDS  # noqa: E402

MAX_BATCH = 1 << 15          # the serving lane's max_batch default
MIN_ROWS = 16                # its smallest bucket
GRACE_S = 60.0               # how long past the window an answer may take
TRACE_S = 3.0                # the traced part of a --trace 1 window
WARM_PEER = 0xC6120001       # 198.18.0.1: never a traffic peer


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: no result."""


def load_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise NoDevice(f"no TPU: JAX serves {platform}")
    if require_tpu and len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX has "
                       f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache():
    """JAX's persistent compile cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Compile spans (JAX's own monitoring events) by host time."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax.monitoring as mon
        self.spans = []
        self.cache_hits = 0

        def on_span(event, *args, **_kw):
            if event in self.EVENTS:
                self.spans.append(time.perf_counter())

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._listeners = (on_span, on_event)
        mon.register_event_duration_secs_listener(on_span)
        mon.register_event_listener(on_event)

    def between(self, t0, t1) -> int:
        return sum(t0 <= t <= t1 for t in self.spans)

    def close(self):
        import jax.monitoring as mon
        on_span, on_event = self._listeners
        mon.unregister_event_duration_listener(on_span)
        mon.unregister_event_listener(on_event)


# ------------------------------------------------------------ capture

class Log:
    """Frames sent and answered, and the answers of sampled records."""

    def __init__(self):
        self._mu = threading.Lock()
        self.frames = []      # (sent or due, submit, resolve, n, error)
        self.samples = []     # per frame: (record dict, meta, v, i, s, r)

    def frame(self, t_ref, t_sub, t_res, n, error):
        with self._mu:
            self.frames.append((t_ref, t_sub, t_res, n, error))

    def sample(self, rec, meta, value, t_sub, t_res):
        m = meta["sampled"]
        if value is None or not m.any():
            return
        rows = np.flatnonzero(m)
        v, i = value
        with self._mu:
            self.samples.append((
                {f: rec[f][rows] for f in FIELDS},
                {k: meta[k][rows] for k in ("flow", "k", "side")},
                np.asarray(v)[rows], np.asarray(i)[rows], t_sub, t_res))

    def records(self):
        """Every sampled record, as one dict of arrays."""
        out = {f: [] for f in FIELDS}
        for k in ("flow", "k", "side", "verdict", "identity", "submit",
                  "resolve"):
            out[k] = []
        for rec, meta, v, i, s, r in self.samples:
            for f in FIELDS:
                out[f].append(rec[f])
            for k in ("flow", "k", "side"):
                out[k].append(meta[k])
            out["verdict"].append(v)
            out["identity"].append(i)
            out["submit"].append(np.full(len(v), s))
            out["resolve"].append(np.full(len(v), r))
        return {k: np.concatenate(v) if v else np.zeros(0)
                for k, v in out.items()}


def _flow_meta(meta, stream):
    meta["flow"] = (np.int64(stream) << 40) | meta["idx"]
    return meta


def _serve(lane, rec, n, timeout=600):
    t0 = time.perf_counter()
    tk = lane.submit_records(rec, n)
    value = tk.result(timeout=timeout)
    return tk, value, t0, time.perf_counter()


# ------------------------------------------------------------- set-up

def buckets_for(mix, frame_records):
    """Every launch size the cell's traffic can make."""
    def bucket(n):
        rows = MIN_ROWS
        while rows < n:
            rows *= 2
        return rows
    if mix["loop"] == "closed":
        # whole frames, as many as every submitter's at once: the lane
        # merges what is pending whatever MAX_BATCH says
        sizes = {bucket(k * frame_records)
                 for k in range(1, mix["submitters"] + 1)}
    else:
        sizes = set()
        b = MIN_ROWS
        while b <= MAX_BATCH:
            sizes.add(b)
            b *= 2
    return sorted(sizes)


def warm(lane, dep, buckets, claim_every=4):
    """Launch every bucket ``claim_every`` times in a row, so both flow
    variants of the step (claim and no-claim) compile or load here.
    The warm-up record comes from a reserved address and is never
    checked."""
    for b in buckets:
        rec = {f: np.zeros(b, np.int32) for f in FIELDS}
        rec["endpoint"][:] = 0
        rec["saddr"][:] = np.uint32(WARM_PEER).view(np.int32)
        rec["daddr"][:] = np.int64(dep.local_addr[0]).astype(
            np.uint32).view(np.int32)
        rec["sport"][:] = 1
        rec["dport"][:] = 1
        rec["proto"][:] = 17
        rec["length"][:] = 64
        for _ in range(claim_every):
            tk, _v, _s, _r = _serve(lane, rec, b)
            if tk.error is not None:
                raise RuntimeError(f"warm-up of bucket {b}: {tk.error!r}")


# ------------------------------------------------------------- events

class Events:
    """The cell's events, played inside the window by one thread,
    ``bench-events``: each at ``t0 + at_s`` through
    ``system.apply(event)``.  ``played`` holds one stamp per event,
    ``(event, due, start, done, error)`` on the host clock; an event
    whose apply raised carries the exception's text and does not stop
    the ones after it."""

    def __init__(self, system, schedule):
        self.system, self.schedule = system, schedule
        self.played = []
        self.thread = None

    def start(self, t0):
        import jax

        def play():
            for at_s, event in self.schedule:
                due = t0 + at_s
                now = time.perf_counter()
                while now < due:
                    time.sleep(min(due - now, 0.001))
                    now = time.perf_counter()
                error = None
                with jax.profiler.TraceAnnotation("bench.event"):
                    start = time.perf_counter()
                    try:
                        self.system.apply(event)
                    except Exception as e:  # noqa: BLE001 — stamped, counted
                        error = repr(e)
                        traceback.print_exc()
                    done = time.perf_counter()
                self.played.append((event, due, start, done, error))

        self.thread = threading.Thread(target=play, name="bench-events",
                                       daemon=True)
        self.thread.start()

    def join(self, deadline):
        self.thread.join(timeout=max(0.0, deadline - time.perf_counter()))
        if self.thread.is_alive():
            raise RuntimeError("an event was still being applied past the "
                               "grace period")

    def apply_s(self):
        """(p50, max) of the seconds each event's apply took."""
        took = [done - start for _e, _d, start, done, _x in self.played]
        return [float(np.percentile(took, 50)), max(took)] if took else None


# ------------------------------------------------------------- window

def run_closed(system, pools, seconds, log, trace, events=None):
    """Closed loop: each submitter sends one frame (a round of its
    pool), waits for its answer, and sends the next; the next frame is
    made while the answer is awaited.  ``events`` (``Events``) are
    played from the window's start."""
    import jax
    lane = system.lane
    stop = threading.Event()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    busy = [0.0] * len(pools)

    def submitter(s, pool):
        rec, meta = pool.round()
        meta = _flow_meta(meta, s)
        while time.perf_counter() < t_end and not stop.is_set():
            n = len(rec["endpoint"])
            with jax.profiler.TraceAnnotation("bench.submit"):
                t_sub = time.perf_counter()
                tk = lane.submit_records(rec, n)
            g0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.generate"):
                nxt, nmeta = pool.round()
                nmeta = _flow_meta(nmeta, s)
            busy[s] += time.perf_counter() - g0
            with jax.profiler.TraceAnnotation("bench.wait"):
                value = tk.result(timeout=GRACE_S + seconds)
            t_res = time.perf_counter()
            log.frame(t_sub, t_sub, t_res, n, tk.error)
            log.sample(rec, meta, value, t_sub, t_res)
            rec, meta = nxt, nmeta

    threads = [threading.Thread(target=submitter, args=(s, p),
                                name=f"bench-submit-{s}", daemon=True)
               for s, p in enumerate(pools)]
    for t in threads:
        t.start()
    if events is not None:
        events.start(t0)
    snaps = trace.during(t0, seconds) if trace else None
    for t in threads:
        t.join(timeout=seconds + GRACE_S + 30)
    stop.set()
    alive = [t.name for t in threads if t.is_alive()]
    if alive:
        raise RuntimeError(f"submitters still waiting: {alive}")
    if events is not None:
        events.join(t_end + GRACE_S)
    return t0, t_end, {"generator_busy_share":
                       sum(busy) / (len(pools) * seconds)}, snaps


def run_open(system, frames, seconds, log, trace, events=None):
    """Open loop: every frame is sent when it is due, whatever is still
    in flight; its latency runs from when it was due.  ``events`` as in
    ``run_closed``."""
    import jax
    lane = system.lane
    due, mat, meta, lo, hi, sampled = frames
    nf = len(due)
    t_sub = np.zeros(nf)
    t_res = np.full(nf, np.nan)
    errs = np.zeros(nf, bool)
    values = {}
    t0 = time.perf_counter() + 0.05

    def callback(j):
        def cb(tk):
            t_res[j] = time.perf_counter()
            if tk.error is not None:
                errs[j] = True
            if sampled[j]:
                values[j] = tk.value
        return cb

    def submitter():
        for j in range(nf):
            d = t0 + due[j]
            now = time.perf_counter()
            while now < d:
                time.sleep(min(d - now, 0.001))
                now = time.perf_counter()
            a, b = lo[j], hi[j]
            with jax.profiler.TraceAnnotation("bench.submit"):
                soa = {f: mat[i, a:b] for i, f in enumerate(FIELDS)}
                t_sub[j] = time.perf_counter()
                tk = lane.submit_records(soa, b - a)
                tk.add_done_callback(callback(j))

    thread = threading.Thread(target=submitter, name="bench-submit",
                              daemon=True)
    thread.start()
    if events is not None:
        events.start(t0)
    snaps = trace.during(t0, seconds) if trace else None
    thread.join(timeout=seconds + GRACE_S + 30)
    deadline = time.perf_counter() + GRACE_S
    while np.isnan(t_res).any() and time.perf_counter() < deadline:
        time.sleep(0.01)
    if thread.is_alive():
        raise RuntimeError("the open-loop submitter fell behind its "
                           "schedule by more than the grace period")
    t_end = t0 + seconds
    if events is not None:
        events.join(t_end + GRACE_S)
    for j in range(nf):
        log.frame(t0 + due[j], t_sub[j], t_res[j], hi[j] - lo[j],
                  True if errs[j] else None)
        if j in values:
            rec = {f: mat[i, lo[j]:hi[j]] for i, f in enumerate(FIELDS)}
            mm = {k: v[lo[j]:hi[j]] for k, v in meta.items()}
            log.sample(rec, mm, values[j], t_sub[j], t_res[j])
    late = t_sub - (t0 + due)
    lat = (t_res - (t0 + due)) * 1e3
    fifth = due < seconds / 5
    last = due >= seconds * 4 / 5
    return t0, t_end, {"latency_p50_first_fifth_ms":
                       float(np.nanpercentile(lat[fifth], 50)),
                       "latency_p50_last_fifth_ms":
                       float(np.nanpercentile(lat[last], 50)),
                       "generator_late_p99_ms":
                       float(np.percentile(late, 99) * 1e3),
                       "generator_late_max_ms": float(late.max() * 1e3)}, \
        snaps


def open_frames(mix, seed, seconds, pool):
    """The whole open-loop schedule, made at set-up: frames cut from
    pool rounds (never across a round), in due order."""
    due, sizes = traffic.open_schedule(mix, seed, seconds)
    per_round = None
    recs, metas = [], []
    need = int(sizes.sum())
    total = 0
    while total < need:
        rec, meta = pool.round()
        per_round = per_round or len(rec["endpoint"])
        recs.append(rec)
        metas.append(_flow_meta(meta, 0))
        total += len(rec["endpoint"])
    mat = np.stack([np.concatenate([r[f] for r in recs]) for f in FIELDS])
    meta = {k: np.concatenate([m[k] for m in metas])
            for k in ("flow", "k", "side", "sampled")}
    lo = np.zeros(len(sizes), np.int64)
    hi = np.zeros(len(sizes), np.int64)
    off = 0
    for j, s in enumerate(sizes.tolist()):
        end = min(off + s, (off // per_round + 1) * per_round)
        lo[j], hi[j] = off, end
        off = end
    sampled = np.array([meta["sampled"][a:b].any() for a, b in
                        zip(lo.tolist(), hi.tolist())])
    return due, mat, meta, lo, hi, sampled


# -------------------------------------------------------------- trace

class Trace:
    """The profiler over the first ``TRACE_S`` of the window, with the
    dispatcher's counters read at its two ends."""

    def __init__(self, system):
        self.system = system
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def during(self, t0, seconds):
        import jax
        span = min(TRACE_S, seconds)
        while time.perf_counter() < t0:
            time.sleep(0.001)
        s0 = self.system.stats()
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(span)
        s1 = self.system.stats()
        return s0, s1

    def start(self):
        import jax
        # host tracer level 1: the benchmark's own annotations, not
        # every runtime event (level 2 slowed the host enough to
        # overflow the lane at rr's first rate)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self):
        import trace_reduce
        try:
            return trace_reduce.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ------------------------------------------------------------ metrics

def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


# --------------------------------------------------------------- main

def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, require_tpu=True, hook=None, overrides=None,
         parts=None):
    """One run; returns the result dict (also printed).  ``hook`` is
    called with the built system and the deployment before any traffic
    (the tests break the timed path there, and ``control.py`` puts the
    control in its place); ``overrides`` replaces configuration and mix
    keys (the tests' small sizes, a sweep of rates); ``parts`` are the
    kind's roles (``byname.kind_parts``), found by the configuration's
    ``kind`` when None (the tests bring kinds of their own)."""
    args = parse(argv)
    bench = load_bench()
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise KeyError(f"no workload {args.workload!r}")
    dev = device(cell["chips"], require_tpu)
    cache_dir = enable_cache()
    compiles = Compiles()
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    mix = traffic.load_mix(cell["traffic"])
    ov = overrides or {}
    cfg.update(ov.get("config", {}))
    mix.update(ov.get("traffic", {}))
    seed, seconds = args.seed, args.seconds

    if parts is None:
        parts = byname.kind_parts(cfg["kind"])
    if "events" in mix and parts.events is None:
        raise RuntimeError(f"the mix {cell['traffic']!r} has events and "
                           f"the kind {parts.kind!r} has no events file "
                           f"(events/{parts.kind}.py)")
    phases = {}
    t_ph = time.perf_counter()

    def phase(name):
        nonlocal t_ph
        now = time.perf_counter()
        phases[name] = now - t_ph
        t_ph = now

    phase("start")
    # the deployment is the configuration's own, the same in every run
    # (as a model's weights would be); traffic comes from --seed
    dep = parts.deployment.build(cfg, cfg["deployment_seed"])
    phase("deploy")
    system = parts.System(cfg, dep)
    phase("tables")
    if hook is not None:
        hook(system, dep)
    log = Log()
    closed = mix["loop"] == "closed"
    sample_mod = int(mix["check_one_in"])
    if closed:
        subs = mix["submitters"]
        share = mix["pool"] // subs
        pools = [traffic.Pool(parts.FlowSource(dep, mix, seed, s, subs),
                              share, sample_mod=sample_mod)
                 for s in range(subs)]
        # records per frame: one per local side of each flow
        frame_records = int((pools[0].fl["c_ep"] >= 0).sum() +
                            (pools[0].fl["s_ep"] >= 0).sum())
    else:
        prng = np.random.default_rng([seed, 5])
        pool = traffic.Pool(parts.FlowSource(dep, mix, seed, 0),
                            mix["pool"], perm=prng.permutation(mix["pool"]),
                            sample_mod=sample_mod)
        frame_records = 1
    buckets = buckets_for(mix, frame_records)
    warm(system.lane, dep, buckets)
    phase("warm")
    # pre-roll: each flow's first packets (the SYN wave) before the
    # window, through the same lane, answers checked like the rest
    for r in range(mix.get("preroll_rounds", 0)):
        for s, p in enumerate(pools if closed else [pool]):
            rec, meta = p.round()
            meta = _flow_meta(meta, s)
            n = len(rec["endpoint"])
            for a in range(0, n, 4096):
                part = {f: v[a:a + 4096] for f, v in rec.items()}
                pm = {k: v[a:a + 4096] for k, v in meta.items()}
                tk, value, ts, tr = _serve(system.lane, part,
                                           len(part["endpoint"]))
                log.sample(part, pm, value, ts, tr)
    phase("preroll")
    frames = None if closed else open_frames(mix, seed, seconds, pool)
    events = None
    if "events" in mix:
        due = parts.events.schedule(cfg, mix, dep, seed, seconds)
        events = Events(system, sorted(
            ((at, ev) for at, ev in due if 0 <= at < seconds),
            key=lambda p: p[0]))
    phase("schedule")
    trace = Trace(system) if args.trace else None
    if trace:
        trace.start()
    s_before = system.stats()
    st_before = system.stages()
    setup_s = time.perf_counter() - T_PROCESS
    if closed:
        t0, t_end, gen, snaps = run_closed(system, pools, seconds, log,
                                           trace, events)
    else:
        t0, t_end, gen, snaps = run_open(system, frames, seconds, log,
                                         trace, events)
    if trace:
        trace.stop()
    s_after = system.stats()
    st_after = system.stages()
    compiles_in_window = compiles.between(t0, t_end)
    compiles.close()
    import jax
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    sup = system.supervision()
    ct_entries = system.ct_entries()
    geometry = system.geometry()
    system.close()
    fail_static = int(sup["serving"]["supervisor"]["fail-static"]
                      ["batches"]) if sup.get("serving") and \
        sup["serving"].get("supervisor") else 0
    del system

    # -- the reference, once the window is closed and the state freed
    t_ref = time.perf_counter()
    recs = log.records()
    ref = parts.Reference(dep, CLOCK_OFFSET)
    played = {} if events is None else {"events": events.played}
    bad_v, bad_i, n_checked, ambiguous, examples = ref.check(
        recs, recs["verdict"].astype(np.int64),
        recs["identity"].astype(np.int64), **played)
    ref_s = time.perf_counter() - t_ref

    win = (t0, t_end, seconds)
    in_window = [f for f in log.frames if t0 <= f[0] < t_end]
    failed = sum(1 for f in in_window
                 if f[4] is not None or np.isnan(f[2]))
    checks = {"verdict_mismatches": [bad_v, "<=", 0],
              "identity_mismatches": [bad_i, "<=", 0],
              "fail_static_batches": [fail_static, "<=", 0],
              "failed_frames": [failed, "<=", 0],
              "records_checked": [n_checked, ">=", 1]}
    if events is not None:
        checks["failed_events"] = [
            sum(e[4] is not None for e in events.played), "<=", 0]
        checks["events_played"] = [len(events.played), ">=", 1]
    correct = all(v <= lim if op == "<=" else v >= lim
                  for v, op, lim in checks.values())
    device_out = dict(dev, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": len(in_window),
              "failed": failed, "metrics": {}, "device": device_out}
    ctx = {"window": win, "frames": log.frames, "setup_s": setup_s,
           "stats": (s_before, s_after), "trace_stats": snaps,
           "stages": (st_before, st_after), "device_kind": dev["kind"]}
    if args.trace:
        red = trace.reduce()
        device_out["busy_s"] = red["busy_s"]
        device_out["window_s"] = red["window_s"]
        with open(os.path.join(HERE, "peaks.json")) as f:
            ctx.update(trace=red, peaks=json.load(f))
        result["breakdown"] = red["breakdown"]
    for m in bench["per_layer" if args.trace else "end_to_end"]:
        if applies(m, cell["name"]):
            v = byname.module("metrics", m["name"]).read(ctx)
            if v is not None and v == v:    # a reading, not NaN
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    result["checks"] = checks
    info = {"cell": cell["name"], "seed": seed, "seconds": seconds,
            "setup_s": setup_s, "reference_s": ref_s,
            "buckets": buckets, "geometry": geometry,
            "entries": dep.entries(), "ipcache_prefixes": len(dep.prefixes),
            "ct_entries": ct_entries,
            "ct_occupancy": ct_entries / geometry["ct_slots"],
            "batches": s_after["batches"] - s_before["batches"],
            "records": s_after["items"] - s_before["items"],
            "compiles_in_window": compiles_in_window,
            "cache_hits": compiles.cache_hits, "cache_dir": cache_dir,
            "ambiguous_records": ambiguous,
            "closing_checked": ref.closing_checked,
            "examples": examples[:3],
            "setup_phases_s": phases, **gen}
    if events is not None:
        info.update(events_played=len(events.played),
                    event_apply_s=events.apply_s())
    print(json.dumps(info, default=str), flush=True)
    for name, (v, op, lim) in checks.items():
        print(f"check {name} {v} {op} {lim}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    rc = 0
    try:
        main()
    except NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        rc = 3
    except Exception:  # noqa: BLE001 — any failure: no result line
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the lane's and the supervisor's daemon threads must not hold the
    # exit
    os._exit(rc)
