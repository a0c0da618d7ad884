"""Dense broadcast-compare verdict engine (+ Pallas TPU kernel).

The hash-probe engine (ops/hashtab_ops) implements the reference's map
semantics with K dependent gathers per stage — fine on CPU, but random
gathers are the one access pattern TPUs dislike. This module is the
TPU-first alternative: policy entries live as flat arrays [N] (one row
per real entry, not per hash slot), and a batch classifies by
broadcast-comparing packet keys against all entries — a [B, N] int32
compare on the VPU with per-stage priority selection, no gathers, no
data-dependent control flow. Per-entry packet/byte counters fall out as
column reductions of the effective-match matrix (the per-entry counter
layout of bpf/lib/policy.h:67, for free).

Semantics are identical to the 3-stage fallback of
bpf/lib/policy.h:46 __policy_can_access; parity with the hash engine
and the scalar oracle is enforced by tests.

The Pallas kernel runs a 2-D grid (packet blocks x entry tiles): the
entry axis streams through VMEM in TILE_N tiles while per-packet stage
accumulators stay VMEM-resident across the inner tile loop, so there is
no entry-count cap.  On CPU it runs in interpret mode.  Note the
compare is still O(B*N): at very large N (millions of entries) the
constant-probe bucket engine (ops/bucket_ops.py) is the right tool —
dense wins on small-to-mid rule sets where gathers dominate.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.policy_tables import pack_key, pack_meta
from ..policy.mapstate import PolicyMapState

from jax.experimental import pallas as pl

VERDICT_DROP = -1

# Entry axis padded to the TPU lane width.
LANE = 128
# Per-grid-step entry tile: [block_b, TILE_N] compare matrices must fit
# VMEM (~16 MB/core); 256x2048 int32 = 2 MB per live matrix.  The entry
# axis itself is unbounded — the kernel walks it in tiles (2-D grid),
# carrying per-packet stage accumulators in VMEM-resident output blocks.
TILE_N = 2048


class DenseTables(NamedTuple):
    """Flat policy entries across all endpoints, padded to LANE."""

    ep: jnp.ndarray      # [N] int32, -1 on padding rows
    key_a: jnp.ndarray   # [N] int32 identity word
    key_b: jnp.ndarray   # [N] int32 packed meta word
    value: jnp.ndarray   # [N] int32 proxy port


def compile_dense(map_states: Sequence[PolicyMapState]) -> DenseTables:
    """Stack every endpoint's entries into flat arrays.

    One row per real entry — the dense engine needs no hash slots, so
    its footprint is exactly sum(len(state)) rows (vs E*S slots)."""
    eps: List[int] = []
    kas: List[int] = []
    kbs: List[int] = []
    vals: List[int] = []
    for ep_idx, state in enumerate(map_states):
        for k, v in sorted(state.items(),
                           key=lambda kv: pack_key(kv[0])):
            ka, kb = pack_key(k)
            eps.append(ep_idx)
            kas.append(ka)
            kbs.append(kb)
            vals.append(v.proxy_port)
    n = len(eps)
    pad = (-n) % LANE
    if n == 0:
        pad = LANE
    eps += [-1] * pad
    kas += [0] * pad
    kbs += [0] * pad
    vals += [0] * pad
    as_i32 = lambda xs: jnp.asarray(
        np.array(xs, np.uint32).view(np.int32))
    return DenseTables(ep=jnp.asarray(np.array(eps, np.int32)),
                       key_a=as_i32(kas), key_b=as_i32(kbs),
                       value=jnp.asarray(np.array(vals, np.int32)))


# key_b packing: single lockstep definition (works elementwise on jnp
# arrays — pure bit ops)
_meta = pack_meta


def _classify_block(ep, ka, kb, val, pep, pid, pme, pml, plen):
    """Shared core: [B] packets vs [N] entries -> verdict + counter
    deltas. Pure jnp — used verbatim by the XLA path and inside the
    Pallas kernel (where the arrays are VMEM-resident)."""
    same_ep = pep[:, None] == ep[None, :]
    ident_eq = pid[:, None] == ka[None, :]
    m1 = same_ep & ident_eq & (pme[:, None] == kb[None, :])
    m2 = same_ep & ident_eq & (pml[:, None] == kb[None, :])
    m3 = same_ep & (ka[None, :] == 0) & (pme[:, None] == kb[None, :])
    i1 = m1.astype(jnp.int32)
    i3 = m3.astype(jnp.int32)
    hit1 = i1.sum(axis=1) > 0
    hit2 = m2.astype(jnp.int32).sum(axis=1) > 0
    hit3 = i3.sum(axis=1) > 0
    # unique keys per endpoint => at most one match per stage: sum works
    val1 = (i1 * val[None, :]).sum(axis=1)
    val3 = (i3 * val[None, :]).sum(axis=1)
    verdict = jnp.where(
        hit1, val1,
        jnp.where(hit2, jnp.int32(0),
                  jnp.where(hit3, val3, jnp.int32(VERDICT_DROP))))
    # effective match: the stage that decided each packet
    m_eff = m1 | (m2 & ~hit1[:, None]) | (m3 & ~(hit1 | hit2)[:, None])
    ieff = m_eff.astype(jnp.int32)
    d_packets = ieff.sum(axis=0)
    d_bytes = (ieff * plen[:, None]).sum(axis=0)
    return verdict, d_packets, d_bytes


def dense_verdict_step(tables: DenseTables, counters_packets: jnp.ndarray,
                       counters_bytes: jnp.ndarray, pkt_ep: jnp.ndarray,
                       pkt_ident: jnp.ndarray, pkt_dport: jnp.ndarray,
                       pkt_proto: jnp.ndarray, pkt_dir: jnp.ndarray,
                       pkt_len: jnp.ndarray):
    """Pure-jnp dense engine (XLA fuses the whole thing).

    Returns (verdict [B], counters_packets' [N], counters_bytes' [N]).
    """
    meta_exact = _meta(pkt_dport, pkt_proto, pkt_dir)
    meta_l3 = _meta(jnp.zeros_like(pkt_dport), jnp.zeros_like(pkt_proto),
                    pkt_dir)
    verdict, d_pk, d_by = _classify_block(
        tables.ep, tables.key_a, tables.key_b, tables.value,
        pkt_ep, pkt_ident, meta_exact, meta_l3, pkt_len)
    return (verdict, counters_packets + d_pk.astype(jnp.uint32),
            counters_bytes + d_by.astype(jnp.uint32))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _dense_tiled_kernel(ep_ref, ka_ref, kb_ref, val_ref, pep_ref, pid_ref,
                        pme_ref, pml_ref, h1_ref, v1_ref, i1_ref, h2_ref,
                        i2_ref, h3_ref, v3_ref, i3_ref, *, tile_n: int):
    """Grid step (i: packet block, j: entry tile; j fastest).

    Accumulates per-packet stage partials across entry tiles in the
    eight output blocks, which map to the same (0, i) block for every j
    — they stay VMEM-resident and survive across the inner j loop.
    Unique keys per endpoint mean at most ONE entry matches per stage
    across ALL tiles, so sums both accumulate and select.  Entry
    indices are stored +1 so 0 means "no match" (entry 0 is real).
    """
    j = pl.program_id(1)
    ep = ep_ref[0, :]
    ka = ka_ref[0, :]
    kb = kb_ref[0, :]
    val = val_ref[0, :]
    pep = pep_ref[0, :]
    pid = pid_ref[0, :]
    pme = pme_ref[0, :]
    pml = pml_ref[0, :]

    same_ep = pep[:, None] == ep[None, :]
    ident_eq = pid[:, None] == ka[None, :]
    m1 = same_ep & ident_eq & (pme[:, None] == kb[None, :])
    m2 = same_ep & ident_eq & (pml[:, None] == kb[None, :])
    m3 = same_ep & (ka[None, :] == 0) & (pme[:, None] == kb[None, :])
    i1 = m1.astype(jnp.int32)
    i2 = m2.astype(jnp.int32)
    i3 = m3.astype(jnp.int32)
    # global entry index of this tile's columns, +1 (0 = no match)
    gidx = (j * tile_n +
            jax.lax.broadcasted_iota(jnp.int32, m1.shape, 1) + 1)

    d_h1 = i1.sum(axis=1)
    d_v1 = (i1 * val[None, :]).sum(axis=1)
    d_i1 = (i1 * gidx).sum(axis=1)
    d_h2 = i2.sum(axis=1)
    d_i2 = (i2 * gidx).sum(axis=1)
    d_h3 = i3.sum(axis=1)
    d_v3 = (i3 * val[None, :]).sum(axis=1)
    d_i3 = (i3 * gidx).sum(axis=1)

    @pl.when(j == 0)
    def _zero():
        for ref in (h1_ref, v1_ref, i1_ref, h2_ref, i2_ref, h3_ref,
                    v3_ref, i3_ref):
            ref[0, :] = jnp.zeros_like(d_h1)

    h1_ref[0, :] += d_h1
    v1_ref[0, :] += d_v1
    i1_ref[0, :] += d_i1
    h2_ref[0, :] += d_h2
    i2_ref[0, :] += d_i2
    h3_ref[0, :] += d_h3
    v3_ref[0, :] += d_v3
    i3_ref[0, :] += d_i3


def dense_verdict_pallas(tables: DenseTables, pkt_ep, pkt_ident,
                         pkt_dport, pkt_proto, pkt_dir, pkt_len,
                         block_b: int = 256, tile_n: int = TILE_N,
                         interpret: bool = False):
    """Pallas dense engine, entry axis tiled through VMEM.

    Returns (verdict [B], counter deltas (packets [N], bytes [N])).
    No entry-count cap: the grid walks ceil(N / tile_n) tiles per
    packet block.  Requires B % block_b == 0.  ``interpret`` runs the
    kernel in the Pallas interpreter (CPU tests only).
    """
    n = tables.ep.shape[0]
    b = pkt_ep.shape[0]
    block_b = min(block_b, b)
    if b % block_b:
        raise ValueError(f"batch {b} not divisible by block {block_b}")
    tile_n = min(tile_n, max(LANE, n))
    pad = (-n) % tile_n
    ep_t, ka_t, kb_t, val_t = tables
    if pad:
        ep_t = jnp.concatenate(
            [ep_t, jnp.full(pad, -1, jnp.int32)])  # never matches
        zeros = jnp.zeros(pad, jnp.int32)
        ka_t = jnp.concatenate([ka_t, zeros])
        kb_t = jnp.concatenate([kb_t, zeros])
        val_t = jnp.concatenate([val_t, zeros])
    n_pad = n + pad
    n_tiles = n_pad // tile_n

    meta_exact = _meta(pkt_dport, pkt_proto, pkt_dir)
    meta_l3 = _meta(jnp.zeros_like(pkt_dport), jnp.zeros_like(pkt_proto),
                    pkt_dir)
    row = lambda x: x.reshape(1, -1)
    entry_spec = lambda: pl.BlockSpec((1, tile_n), lambda i, j: (0, j))
    pkt_spec = lambda: pl.BlockSpec((1, block_b), lambda i, j: (0, i))
    acc_spec = lambda: pl.BlockSpec((1, block_b), lambda i, j: (0, i))
    acc_shape = lambda: jax.ShapeDtypeStruct((1, b), jnp.int32)

    (h1, v1, i1, h2, i2, h3, v3, i3) = pl.pallas_call(
        functools.partial(_dense_tiled_kernel, tile_n=tile_n),
        grid=(b // block_b, n_tiles),
        in_specs=[entry_spec(), entry_spec(), entry_spec(), entry_spec(),
                  pkt_spec(), pkt_spec(), pkt_spec(), pkt_spec()],
        out_specs=[acc_spec() for _ in range(8)],
        out_shape=[acc_shape() for _ in range(8)],
        interpret=interpret,
    )(row(ep_t), row(ka_t), row(kb_t), row(val_t), row(pkt_ep),
      row(pkt_ident), row(meta_exact), row(meta_l3))
    h1, v1, i1, h2, i2, h3, v3, i3 = (x[0] for x in
                                      (h1, v1, i1, h2, i2, h3, v3, i3))
    hit1 = h1 > 0
    hit2 = h2 > 0
    hit3 = h3 > 0
    verdict = jnp.where(
        hit1, v1,
        jnp.where(hit2, jnp.int32(0),
                  jnp.where(hit3, v3, jnp.int32(VERDICT_DROP))))
    # counter scatter outside the kernel: each decided packet
    # increments its deciding entry (same m_eff semantics as the jnp
    # path); misses scatter weight 0 into entry 0
    win = jnp.where(hit1, i1, jnp.where(hit2, i2,
                                        jnp.where(hit3, i3, 0)))
    decided = win > 0
    idx = jnp.maximum(win - 1, 0)
    inc = decided.astype(jnp.int32)
    d_pk = jnp.zeros(n, jnp.int32).at[idx].add(inc)
    d_by = jnp.zeros(n, jnp.int32).at[idx].add(
        inc * pkt_len.astype(jnp.int32))
    return verdict, d_pk, d_by


# ---------------------------------------------------------------------------
# Dense LPM + fused raw-path step (gather-free flagship pipeline)
# ---------------------------------------------------------------------------

class DenseLPM(NamedTuple):
    """Flat LPM entries: addr-under-mask compare, longest-prefix wins."""

    net: jnp.ndarray    # [P] int32 network address (pre-masked)
    mask: jnp.ndarray   # [P] int32 netmask
    plen: jnp.ndarray   # [P] int32 prefix length + 1 (0 = padding row)
    value: jnp.ndarray  # [P] int32 identity


def compile_dense_lpm(prefixes) -> DenseLPM:
    """{cidr: identity} -> DenseLPM (pads to LANE)."""
    import ipaddress
    rows = []
    for cidr, ident in sorted(prefixes.items()):
        net = ipaddress.ip_network(cidr, strict=False)
        mask = int(net.netmask)
        rows.append((int(net.network_address) & mask, mask,
                     net.prefixlen + 1, ident))
    pad = (-len(rows)) % LANE
    if not rows:
        pad = LANE
    rows += [(0, 0xFFFFFFFF, 0, 0)] * pad  # plen 0 rows never win
    arr = np.array(rows, np.uint64)
    u = lambda col: jnp.asarray(arr[:, col].astype(np.uint32)
                                .view(np.int32))
    return DenseLPM(net=u(0), mask=u(1), plen=u(2), value=u(3))


def dense_lpm_lookup(lpm: DenseLPM, addr: jnp.ndarray):
    """[B] addr -> (found [B] bool, value [B] int32): longest matching
    prefix wins, as one [B, P] masked compare + two reductions."""
    match = (addr[:, None] & lpm.mask[None, :]) == lpm.net[None, :]
    score = jnp.where(match, lpm.plen[None, :], 0)
    best = score.max(axis=1)
    # exactly one prefix of a given length can contain an address,
    # so a masked sum selects the winner's value
    sel = match & (score == best[:, None]) & (best[:, None] > 0)
    value = (sel.astype(jnp.int32) * lpm.value[None, :]).sum(axis=1)
    return best > 0, value


# Identity assigned on ipcache miss (reference: world).
WORLD_IDENTITY = 2


def dense_datapath_step(tables: DenseTables, lpm: DenseLPM,
                        counters_packets, counters_bytes, pkt_ep,
                        pkt_src_addr, pkt_dport, pkt_proto, pkt_dir,
                        pkt_len):
    """Gather-free flagship step: dense ipcache LPM -> dense 3-stage
    verdict -> per-entry counters. Same contract as
    datapath.pipeline.datapath_step."""
    found, ident = dense_lpm_lookup(lpm, pkt_src_addr)
    identity = jnp.where(found, ident, jnp.int32(WORLD_IDENTITY))
    verdict, counters_packets, counters_bytes = dense_verdict_step(
        tables, counters_packets, counters_bytes, pkt_ep, identity,
        pkt_dport, pkt_proto, pkt_dir, pkt_len)
    return verdict, identity, counters_packets, counters_bytes


class DenseVerdictEngine:
    """Host wrapper: compile states, run batches, keep counters."""

    def __init__(self, map_states: Sequence[PolicyMapState],
                 use_pallas: bool = False, block_b: int = 256,
                 interpret: bool = False):
        self.tables = compile_dense(map_states)
        n = self.tables.ep.shape[0]
        # the tiled kernel has no entry cap (entry axis walks VMEM in
        # TILE_N tiles), so pallas is available at any N
        self.use_pallas = use_pallas
        self.block_b = block_b
        self.counters_packets = jnp.zeros(n, jnp.uint32)
        self.counters_bytes = jnp.zeros(n, jnp.uint32)
        self._jit_step = jax.jit(dense_verdict_step, donate_argnums=(1, 2))
        self._jit_pallas = jax.jit(functools.partial(
            dense_verdict_pallas, block_b=block_b, interpret=interpret))

    def __call__(self, pkt_ep, pkt_ident, pkt_dport, pkt_proto, pkt_dir,
                 pkt_len):
        arr = lambda x: jnp.asarray(np.asarray(x, np.int32))
        args = (arr(pkt_ep), arr(pkt_ident), arr(pkt_dport),
                arr(pkt_proto), arr(pkt_dir), arr(pkt_len))
        if self.use_pallas and args[0].shape[0] % self.block_b == 0:
            verdict, dpk, dby = self._jit_pallas(self.tables, *args)
            self.counters_packets = self.counters_packets + \
                dpk.astype(jnp.uint32)
            self.counters_bytes = self.counters_bytes + \
                dby.astype(jnp.uint32)
            return verdict
        verdict, self.counters_packets, self.counters_bytes = \
            self._jit_step(self.tables, self.counters_packets,
                           self.counters_bytes, *args)
        return verdict
