"""The chip entry points on the CPU: a rehearsal of ``chip_smoke.py``'s
phases at a tiny size, the refusal of every chip entry point to carry
on without a TPU, and the compile-cache placement."""

import os
import sys

import jax
import pytest

import chip_smoke
from cilium_tpu.utils import platform


@pytest.mark.parametrize("phase", ["agent", "node-share", "sharded"])
def test_chip_smoke_phase_rehearsal(phase):
    """Each phase runs in-process on the CPU at the tiny size, checks
    every verdict against its oracle and reads the supervisors."""
    if phase == "agent":
        line = chip_smoke.agent_phase(3, requests=3)
    elif phase == "node-share":
        line = chip_smoke.node_share_phase(4, chip_smoke.TINY)
    else:
        line = chip_smoke.sharded_phase(
            5, dict(chip_smoke.TINY, buckets=(256,)))
        assert len(set(line["shard_devices"])) == 4
    assert line["phase"] == phase
    assert line["records"] > 0
    for lane in line["lanes"]:
        assert lane["fail_static_batches"] == 0
        assert lane["breaker"] == "closed"


def test_check_supervision_refuses_fail_static():
    sup = {"fail-static": {"batches": 1}, "breaker": "closed",
           "last-fault": "boom"}
    status = {"mode": "ok", "serving": {
        "lane": "verdict", "batches": 3, "static-batches": 0,
        "errors": 0, "supervisor": sup}}
    with pytest.raises(chip_smoke.SmokeError, match="fail-static"):
        chip_smoke.check_supervision(status)
    sup["fail-static"]["batches"] = 0
    assert chip_smoke.check_supervision(status)[0]["batches"] == 3
    sup["breaker"] = "open"
    with pytest.raises(chip_smoke.SmokeError):
        chip_smoke.check_supervision(status)


def test_require_device_refuses_unrequested_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert platform.require_device() == ("cpu", "cpu",
                                         len(jax.devices()))
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no TPU"):
        platform.require_device()


@pytest.mark.parametrize("entry", ["chip_smoke", "bench", "bench_suite"])
def test_chip_entry_points_refuse_cpu(monkeypatch, entry):
    """Without a TPU every chip entry point raises before measuring;
    chip_smoke refuses even a CPU asked for explicitly."""
    if entry == "chip_smoke":
        with pytest.raises(chip_smoke.SmokeError, match="no TPU"):
            chip_smoke.main([])
        return
    monkeypatch.delenv("JAX_PLATFORMS")
    if entry == "bench":
        import bench
        monkeypatch.setattr(sys, "argv", ["bench.py"])
        run = bench.run_bench
    else:
        import bench_suite
        monkeypatch.setattr(sys, "argv", ["bench_suite.py", "fqdn"])
        run = bench_suite.run_suite
    with pytest.raises(RuntimeError, match="no TPU"):
        run()


@pytest.fixture
def cache_dir_restored():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert platform.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_repo_dir(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(platform.REPO_ROOT, ".jax_cache")
    assert platform.enable_compile_cache() == want
    assert platform.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(platform.REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
