"""The served path's programs compile for a TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed, is
given a described v5e topology (no device attached) and must accept
each program at its served shape — what it refuses here would
otherwise cost chip time.  The topology is described inside a module
fixture, never at import, so every xdist worker collects the same tests
and only the one given this file loads the TPU library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip lands in the persistent cache but
    # cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree``'s arrays, placed on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


@pytest.fixture(scope="module")
def engine():
    """The served engine at a small table size: 16 endpoints x 2,048
    entries, flow aggregation on (the daemon default)."""
    from chip_smoke import NodeShare
    from cilium_tpu.datapath.engine import Datapath
    share = NodeShare(np.random.default_rng(3), endpoints=16,
                      entries=2048, pods=4096, cidrs=64)
    dp = Datapath(ct_slots=1 << 16)
    dp.enable_flow_aggregation()
    dp.load_policy(share.states, revision=1,
                   ipcache_prefixes=share.prefixes)
    return dp


def test_served_v4_packed_step_compiles(one_chip, engine):
    from cilium_tpu.datapath.pipeline import PACKED_FIELDS
    packed = np.zeros((len(PACKED_FIELDS), 4096), np.int32)
    args = engine._lower_args_packed(packed)
    assert len(args) == 6, "the flows lane must be lowered too"
    compiled = engine._step_packed.lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0


def test_served_v6_step_compiles(one_chip, engine):
    from cilium_tpu.datapath.engine import make_full_batch6
    n = 4096
    batch = make_full_batch6(
        endpoint=np.zeros(n, np.int32),
        saddr=np.zeros((n, 4), np.int32), daddr=np.zeros((n, 4), np.int32),
        sport=np.zeros(n, np.int32), dport=np.zeros(n, np.int32))
    args = (engine._tbufs6, engine.ct6.state, engine._counters, batch,
            jnp.int32(1), engine.flows.state)
    engine._step6.lower(*_on(one_chip, args)).compile()


@pytest.mark.parametrize("n_entries", [1_000, 10_240])
def test_dense_pallas_kernel_compiles(one_chip, n_entries):
    import functools
    from cilium_tpu.ops.dense_verdict import (DenseTables,
                                              dense_verdict_pallas)
    b = 4096
    tables = DenseTables(*(np.zeros(n_entries, np.int32)
                           for _ in range(4)))
    pkts = tuple(np.zeros(b, np.int32) for _ in range(6))
    fn = jax.jit(functools.partial(dense_verdict_pallas, block_b=256))
    compiled = fn.lower(*_on(one_chip, (tables, *pkts))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_int8_stride_dfa_walk_compiles(one_chip):
    from cilium_tpu.compiler.regexc import compile_regex_set
    from cilium_tpu.ops.dfa_engine import DFAEngine, _packed_match
    eng = DFAEngine(compile_regex_set(["GET", "/public/.*", "/api/v[0-9]+/.*",
                                       ".*admin.*"]),
                    max_len=128, prefer="stride", dtype=np.int8,
                    on_accel=True)
    assert eng._flat.dtype == jnp.int8
    enc = eng.encode(np.full((2048, 128), ord("a"), np.int32))
    args = (eng._flat, eng._accept, eng._starts, enc.idx, enc.overlong)
    _packed_match.lower(eng._c1 ** eng.k,
                        *_on(one_chip, args)).compile()
