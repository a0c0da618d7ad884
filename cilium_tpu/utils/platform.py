"""Device discovery, the compile cache, and the bench output contract.

Entry points that exist to run on the chip (``chip_smoke.py``,
``bench.py``, ``bench_suite.py``) call :func:`require_device` once and
fail when JAX finds no TPU: a measurement path never carries on on the
CPU under a device metric's name.  The one exception is a rehearsal
that asks for the CPU explicitly with ``JAX_PLATFORMS=cpu``.

Analog of the reference's runtime feature probing (bpf/run_probes.sh):
detect what the hardware supports before committing the datapath to it.
"""

import json
import os
import time as _time

# the repository checkout this package lives in
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def require_device():
    """``(platform, device_kind, count)`` of the devices JAX serves.

    Raises RuntimeError when the backend is not ``tpu``, unless the
    caller asked for the CPU explicitly (``JAX_PLATFORMS=cpu``, a
    rehearsal).  Every result line a chip entry point prints carries
    these three values."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise RuntimeError(
            f"no TPU: JAX serves {platform} devices {devices}; set "
            f"JAX_PLATFORMS=cpu to rehearse on the CPU")
    return platform, devices[0].device_kind, len(devices)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its dir.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own choice and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (git-ignored): the directory is part of the
    cache key, so it must not move between runs."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# Driver contract: the FINAL stdout line must parse as one JSON object
# and fit the driver's ~2000-byte tail capture: full results go to a
# BENCH_FULL_<ts>.json file and the final line is a compact digest.
MAX_FINAL_LINE = 1450


def save_full_result(parsed: dict) -> "str | None":
    """Persist the FULL bench result to BENCH_FULL_<ts>.json under
    ``<repo>/chiprun_out`` (or $CILIUM_TPU_BENCH_FULL_DIR); the compact
    final line points at it."""
    try:
        out_dir = os.environ.get("CILIUM_TPU_BENCH_FULL_DIR") \
            or os.path.join(REPO_ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        stamp = _time.strftime("%Y%m%d_%H%M%S", _time.gmtime())
        path = os.path.join(out_dir, f"BENCH_FULL_{stamp}.json")
        with open(path, "w") as f:
            json.dump({"captured_at_utc":
                       _time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      _time.gmtime()),
                       "result": parsed}, f, indent=1)
        return path
    except OSError:
        return None


def _suite_value(entry, key):
    # suite entries are full result dicts (current bench.py) or the
    # older compact {value, vs_baseline} form
    return entry.get(key) if isinstance(entry, dict) else None


def compact_bench_line(parsed: dict, full_file: "str | None" = None,
                       limit: int = MAX_FINAL_LINE) -> dict:
    """The <1.5KB driver-facing digest of a full bench result.

    Keeps: headline metric, backend/on_accel/device, both latency
    gates with the b256 p99 values, one {value, vs_baseline} pair per
    suite config (plus the engine tag that produced it), and the
    BENCH_FULL file carrying everything else.  Optional blocks are
    dropped largest-first if the rendered line would still exceed
    ``limit``; the device block always stays."""
    extra = parsed.get("extra") or {}
    out = {"metric": parsed.get("metric"), "value": parsed.get("value"),
           "unit": parsed.get("unit"),
           "vs_baseline": parsed.get("vs_baseline")}
    ex = {}
    for k in ("backend", "on_accel", "device", "engine", "smoke",
              "latency_under_50us_p99", "latency_under_35us_p99",
              # standalone suite-config lines keep their claim fields
              "at_reference_capacity", "endpoints", "policy_entries",
              "ipcache_entries", "entries_per_endpoint",
              "policy_build_seconds", "ipcache_build_seconds",
              "incremental_apply_us", "batch"):
        if k in extra:
            ex[k] = extra[k]
    sel = extra.get("engine_selection")
    if isinstance(sel, dict):
        ex["eng"] = sel.get("tag") or \
            (sel.get("combined") or {}).get("tag")
    sb = extra.get("small_batch_p99_us") or {}
    p99 = {}
    for src, dst in (("host_cache_p99_us_b256", "host"),
                     ("host_cache_pinned_p99_us_b256", "host_pinned"),
                     ("device_rt_p99_us_b256", "device_rt")):
        if isinstance(sb.get(src), (int, float)):
            p99[dst] = sb[src]
    if p99:
        ex["p99_b256_us"] = p99
    suite = extra.get("suite_configs")
    if isinstance(suite, dict):
        cs = {}
        for name, r in suite.items():
            if isinstance(r, dict):
                row = {"value": _suite_value(r, "value"),
                       "vs_baseline": _suite_value(r, "vs_baseline")}
                rex = r.get("extra") or {}
                sel = rex.get("engine_selection")
                if isinstance(sel, dict):
                    row["eng"] = sel.get("tag") or \
                        (sel.get("combined") or {}).get("tag")
                if "incremental_apply_us" in rex:
                    row["apply_us"] = rex["incremental_apply_us"]
                if rex.get("at_reference_capacity"):
                    row["at_reference_capacity"] = True
                if "overhead_pct" in rex:
                    # flows-overhead: the <=10% aggregation-cost claim
                    row["overhead_pct"] = rex["overhead_pct"]
                cs[name] = row
            else:
                cs[name] = str(r)[:60]
        ex["suite"] = cs
    if full_file:
        ex["full"] = os.path.basename(full_file)
    out["extra"] = ex
    # size guard, graduated: first shed row-level detail from the
    # suite block (per-config overhead_pct, then the engine tags the
    # contract doesn't pin — http-regex/fqdn keep theirs), THEN drop
    # whole optional blocks.  The suite {value, vs_baseline} pairs are
    # the last thing to go: they are the per-config record the driver
    # line exists to carry.
    suite_rows = ex.get("suite")
    if isinstance(suite_rows, dict):
        if len(json.dumps(out)) > limit:
            for row in suite_rows.values():
                if isinstance(row, dict):
                    row.pop("overhead_pct", None)
        if len(json.dumps(out)) > limit:
            for name, row in suite_rows.items():
                if isinstance(row, dict) and \
                        name not in ("http-regex", "fqdn"):
                    row.pop("eng", None)
    for drop in ("p99_b256_us", "suite"):
        if len(json.dumps(out)) <= limit:
            break
        ex.pop(drop, None)
    return out


def emit_result(parsed: dict) -> None:
    """Print one result line under the driver contract: a small line
    without a suite block as is; anything else persisted whole by
    :func:`save_full_result` and printed as its compact digest."""
    rendered = json.dumps(parsed)
    extra = parsed.get("extra") or {}
    if "suite_configs" not in extra and len(rendered) <= MAX_FINAL_LINE:
        print(rendered, flush=True)
        return
    full_path = save_full_result(parsed)
    print(json.dumps(compact_bench_line(parsed, full_path)), flush=True)


def _jax_backend_initialized():
    """True/False iff a jax backend does/doesn't already exist in this
    process (so reading it cannot trigger a fresh — potentially
    hanging — init); None when the detector itself is unavailable
    (jax moved the internal attribute) — callers surface that
    distinctly rather than silently reporting 'not initialized'."""
    try:
        import jax  # noqa: F401
        from jax._src import xla_bridge
    except Exception:  # noqa: BLE001
        return False
    if not hasattr(xla_bridge, "_backends"):
        return None  # detector broken: make it visible, don't guess
    return bool(xla_bridge._backends)


def probe_features(allow_init: bool = True,
                   native_fastpath: "bool | None" = None):
    """Runtime capability probing (bpf/run_probes.sh + bpf_features.h
    analog): what does THIS process's accelerator stack support?  The
    reference probes the kernel before committing the datapath to map
    types; here the probes gate engine/kernels choices and surface in
    `cilium status` so an operator can see what the node runs on.

    ``allow_init=False`` is the health-path contract: never trigger a
    fresh backend init from a status probe — if no backend exists yet,
    the jax block is reported deferred.  ``native_fastpath`` lets a caller that has
    already probed the native build (the daemon) pass the answer in,
    so the status path never runs a synchronous g++ compile.
    """
    feats = {"definitive": True}
    initialized = _jax_backend_initialized()
    if initialized is None and not allow_init:
        feats["backend"] = ("deferred: init-state detector unavailable "
                            "(jax internals changed)")
        feats["on_accelerator"] = False
        feats["definitive"] = False
    elif allow_init or initialized:
        try:
            import jax
            backend = jax.default_backend()
            devices = jax.devices()
            feats["backend"] = backend
            feats["device_count"] = len(devices)
            feats["device_kind"] = (
                getattr(devices[0], "device_kind", str(devices[0]))
                if devices else "none")
            feats["platform_version"] = getattr(jax, "__version__", "")
            feats["on_accelerator"] = backend != "cpu"
        except Exception as e:  # noqa: BLE001 — report, never raise
            feats["backend"] = f"unavailable: {e!r}"
            feats["on_accelerator"] = False
            feats["definitive"] = False
    else:
        feats["backend"] = "deferred: backend not initialized"
        feats["on_accelerator"] = False
        feats["definitive"] = False
    if native_fastpath is None:
        try:
            from ..native import load as _native_load
            _native_load()
            native_fastpath = True
        except Exception:  # noqa: BLE001
            native_fastpath = False
    feats["native_fastpath"] = bool(native_fastpath)
    feats["verdict_engines"] = ["hash", "dense"] + \
        (["dense-pallas"] if feats.get("backend") == "tpu" else []) + \
        ["bucket2choice"] + \
        (["host-cache"] if feats.get("native_fastpath") else [])
    return feats
