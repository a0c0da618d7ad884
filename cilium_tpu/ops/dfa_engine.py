"""Fused, quantized, depth-reduced DFA engines for the L7 hot loop.

``dfa_ops.dfa_match`` walks payloads one byte per dependent step over an
int32 table — O(L) sequential gathers, the bottleneck that kept
http-regex below its baseline on every recorded run.  This module
rebuilds that path around three composable optimizations, selected per
(table size, payload length, batch) at engine construction:

1. **Quantization** — transition tables are stored and gathered at the
   narrowest dtype the state count allows (int8 for S<=127, int16 for
   S<=32767) on accelerators, so the whole table set stays VMEM-
   resident instead of spilling to HBM.  On CPU the tables stay int32:
   XLA's CPU gathers widen narrow loads and measure slower, and the
   packed tables fit L2/L3 either way (selection is per-backend and
   reported, so artifacts stay attributable).

2. **Depth reduction** — the byte alphabet is collapsed into
   equivalence classes first (compiler/regexc.byte_equivalence_classes;
   policy rule sets typically produce 10-30 classes), then k
   consecutive per-class transition functions are precomposed into one
   stride table [S, (C+1)^k] at construction, so the walk takes
   ceil(L/k) dependent gathers instead of L.  When the table is too
   large to precompose, the same reduction runs on device per batch
   (dfa_parallel.dfa_scan_compose: k-1 parallel compose rounds, then an
   L/k walk), and ``lax.associative_scan`` (dfa_parallel) is the
   long-payload endpoint with O(log L) depth.

3. **Split/fused dispatch** — the class map + stride packing is cheap
   vectorized integer work, so it runs EITHER fused into the device
   program (``match``: one jitted program per (B, L) shape — the
   one-call path) OR on the host (``encode`` -> ``match_encoded``), the
   form the pipelined proxy uses: host-packing batch N+1 overlaps the
   device walk of batch N, and the device program shrinks to the
   ceil(L/k) carry walk alone.  The streaming ``scan`` variant donates
   the state carry so steady-state chunk loops allocate nothing new.

Every strategy and both dispatch forms are bit-identical to the
``dfa_match`` oracle (tests/test_dfa_engine.py), including the
padding-freeze and overlong semantics: negative bytes (-1 padding, -2
poison) map to an identity class, which composes as the identity
function, and the -2 row poison is masked at accept time exactly like
the oracle.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .dfa_parallel import dfa_match_compose, dfa_match_parallel, \
    dfa_parallel_scan, dfa_scan_compose

# Host-precomposed stride tables must stay resident in fast memory:
# VMEM (16MB/core) bounds the accelerator budget; CPU tables only need
# to stay inside L2/L3, so the budget is looser there.
STRIDE_BUDGET_ACCEL = 4 << 20
STRIDE_BUDGET_CPU = 16 << 20
# Packed-column bound: (C+1)^k columns; 2^16 keeps S * cols * state
# index arithmetic comfortably inside int32.
MAX_PACKED_COLS = 1 << 16
MAX_STRIDE = 8
# [B, L, S] transition-function materialization bound for the on-device
# strategies (compose/assoc).
DEVICE_F_BUDGET = 256 << 20
# Payload lengths below this never leave the stride path: the depth is
# already tiny and per-batch precompute cannot pay for itself.
SHORT_PAYLOAD = 64


def quantize_dtype(num_states: int) -> np.dtype:
    """Narrowest signed dtype that can index ``num_states`` states."""
    if num_states <= (1 << 7) - 1:
        return np.dtype(np.int8)
    if num_states <= (1 << 15) - 1:
        return np.dtype(np.int16)
    return np.dtype(np.int32)


@dataclass
class PackedBatch:
    """Host-encoded input for ``match_encoded``.

    For the stride strategy ``idx`` is the [B, G] packed class-group
    index block (G = ceil(L/k)); otherwise it is the raw [B, L] byte
    block and the device program does its own mapping.  ``overlong``
    is the -2 poison row mask, precomputed so the device never re-scans
    the bytes."""

    idx: np.ndarray
    overlong: np.ndarray
    rows: int
    packed: bool


@functools.partial(jax.jit, static_argnums=(0, 1))
def _stride_scan(k: int, c1: int, flat_tab, class_map, states, data):
    """Fused form: class map + packing + ceil(L/k) dependent gathers.

    flat_tab: [S * c1**k] stride table; class_map: [258] int32 (byte+2
    -> class, both negative bytes mapped to the identity class c1-1);
    states: [B, R] int32; data: [B, L] int32 bytes.
    """
    b, l = data.shape
    cls = class_map[data + jnp.int32(2)]            # [B, L]
    pad = (-l) % k
    if pad:
        cls = jnp.concatenate(
            [cls, jnp.full((b, pad), c1 - 1, jnp.int32)], axis=1)
    g = cls.reshape(b, -1, k)
    idx = g[:, :, 0]
    for j in range(1, k):                           # earlier byte = high digit
        idx = idx * jnp.int32(c1) + g[:, :, j]      # [B, G]
    return _packed_walk(c1 ** k, flat_tab, states, idx)


def _packed_walk(w: int, flat_tab, states, idx):
    """The dependent-gather carry walk shared by both dispatch forms."""
    def step(st, col):                              # col: [B]; st: [B, R]
        nxt = flat_tab[st * jnp.int32(w) + col[:, None]]
        return nxt.astype(jnp.int32), None

    final, _ = lax.scan(step, states, idx.T)
    return final


_stride_scan_donated = jax.jit(
    _stride_scan.__wrapped__, static_argnums=(0, 1), donate_argnums=(4,))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _stride_match(k: int, c1: int, flat_tab, class_map, accept, starts,
                  data):
    b = data.shape[0]
    states = jnp.broadcast_to(starts[None, :],
                              (b, starts.shape[0])).astype(jnp.int32)
    final = _stride_scan.__wrapped__(k, c1, flat_tab, class_map, states,
                                     data)
    ok = accept[final]
    overlong = jnp.any(data == -2, axis=1)
    return ok & ~overlong[:, None]


@functools.partial(jax.jit, static_argnums=(0,))
def _packed_match(w: int, flat_tab, accept, starts, idx, overlong):
    """Split form: the device program is the carry walk alone — the
    class map/packing already happened on the host (PackedBatch)."""
    b = idx.shape[0]
    states = jnp.broadcast_to(starts[None, :],
                              (b, starts.shape[0])).astype(jnp.int32)
    final = _packed_walk(w, flat_tab, states, idx)
    return accept[final] & ~overlong[:, None]


_assoc_match = jax.jit(dfa_match_parallel)
_assoc_scan = jax.jit(dfa_parallel_scan)


class DFAEngine:
    """One compiled regex set, matched by the best strategy for its
    (table size, payload length, batch) point.

    Strategies:
      - ``stride``  — host-precomposed k-class stride table; the
                      default whenever the packed table fits budget
                      (k=1 degenerates to a class-compressed serial
                      walk).
      - ``compose`` — device-side k-group composition then an L/k walk;
                      for tables too big to precompose but payloads
                      long enough that depth dominates.
      - ``assoc``   — ``lax.associative_scan``, O(log L) depth; the
                      long-payload endpoint on accelerators.
    """

    def __init__(self, compiled, max_len: int, batch_hint: int = 2048,
                 prefer: Optional[str] = None,
                 stride_budget: Optional[int] = None,
                 dtype: Optional[np.dtype] = None,
                 on_accel: Optional[bool] = None):
        from ..observability.jitstats import jit_telemetry
        jit_telemetry.attach()   # its match programs' compiles count
        self.compiled = compiled
        self.max_len = int(max_len)
        self.batch_hint = int(batch_hint)
        s = int(compiled.num_states)
        if on_accel is None:
            on_accel = jax.default_backend() != "cpu"
        self.on_accel = bool(on_accel)
        # quantize for VMEM residency on accelerators; int32 on CPU
        # (narrow gathers measure slower there and cache still fits)
        self._dtype = np.dtype(dtype) if dtype is not None else (
            quantize_dtype(s) if self.on_accel else np.dtype(np.int32))
        if np.iinfo(self._dtype).max < s - 1:
            raise ValueError(f"dtype {self._dtype} cannot hold {s} states")
        itemsize = self._dtype.itemsize
        if stride_budget is None:
            stride_budget = STRIDE_BUDGET_ACCEL if self.on_accel \
                else STRIDE_BUDGET_CPU
        class_of, class_tab = compiled.byte_classes()
        self.num_classes = int(class_tab.shape[1])
        self._c1 = self.num_classes + 1             # + identity class

        # largest stride whose precomposed table stays in budget
        k = 1
        while (k < MAX_STRIDE and self._c1 ** (k + 1) <= MAX_PACKED_COLS
               and s * self._c1 ** (k + 1) * itemsize <= stride_budget):
            k += 1
        device_f_bytes = self.batch_hint * self.max_len * s * itemsize
        if prefer is not None:
            if prefer not in ("stride", "compose", "assoc"):
                raise ValueError(f"unknown DFA strategy {prefer!r}")
            strategy = prefer
        elif (self.on_accel and self.max_len >= 256
              and (self.max_len + k - 1) // k > 64
              and device_f_bytes <= DEVICE_F_BUDGET):
            # stride can't get the depth down on-accel: go log-depth
            strategy = "assoc"
        elif (k == 1 and self.max_len >= SHORT_PAYLOAD
              and device_f_bytes <= DEVICE_F_BUDGET):
            # class alphabet too rich to precompose: reduce depth on
            # device instead
            strategy = "compose"
        else:
            strategy = "stride"
        self.strategy = strategy
        self.k = k if strategy == "stride" else \
            (4 if strategy == "compose" else 1)

        self._accept = jnp.asarray(compiled.accept)
        self._starts = jnp.asarray(compiled.starts)
        self._flat = None
        self._map = None
        self._map_np = None
        self._table_q = None
        if strategy == "stride":
            tab_c = np.concatenate(
                [class_tab, np.arange(s, dtype=np.int32)[:, None]],
                axis=1)                             # [S, C+1]
            t = tab_c
            for _ in range(self.k - 1):
                # T'[s, i*C1 + c] = tab_c[T[s, i], c]: one more byte of
                # lookahead folded into every column
                t = tab_c[t].reshape(s, -1)
            self._packed_bytes = int(t.size * itemsize)
            self._flat = jnp.asarray(
                np.ascontiguousarray(t.astype(self._dtype)).reshape(-1))
            map258 = np.full(258, self.num_classes, np.int32)
            map258[2:] = class_of                   # byte b at index b+2
            self._map_np = map258
            self._map = jnp.asarray(map258)
        else:
            self._packed_bytes = int(s * 256 * itemsize)
            self._table_q = jnp.asarray(compiled.table.astype(self._dtype))

    # ----------------------------------------------------- host encode

    def encode(self, data: np.ndarray) -> PackedBatch:
        """Host stage of the split dispatch: class-map and stride-pack a
        [B, L] byte block (vectorized numpy), so the device program is
        the carry walk alone.  In a pipelined caller this overlaps the
        previous batch's device walk.  Non-stride strategies pass the
        bytes through (their mapping is part of the device program)."""
        data = np.asarray(data)
        overlong = (data == -2).any(axis=1)
        if self.strategy != "stride":
            return PackedBatch(idx=data, overlong=overlong,
                               rows=data.shape[0], packed=False)
        b, l = data.shape
        cls = self._map_np[data + 2]
        pad = (-l) % self.k
        if pad:
            cls = np.concatenate(
                [cls, np.full((b, pad), self.num_classes, np.int32)],
                axis=1)
        g = cls.reshape(b, -1, self.k)
        idx = g[:, :, 0].astype(np.int32)
        for j in range(1, self.k):
            idx = idx * self._c1 + g[:, :, j]
        return PackedBatch(idx=idx, overlong=overlong, rows=b,
                           packed=True)

    # ------------------------------------------------------------ match

    def match(self, data) -> jnp.ndarray:
        """Anchored match, [B, R] bool on device — the dfa_match
        contract (padding freeze, -2 poison), no synchronization.
        Accepts a raw byte block or a :class:`PackedBatch`."""
        if isinstance(data, PackedBatch):
            return self.match_encoded(data)
        data = jnp.asarray(data)
        if self.strategy == "stride":
            out = _stride_match(self.k, self._c1, self._flat,
                                self._map, self._accept, self._starts,
                                data)
        elif self.strategy == "compose":
            out = dfa_match_compose(self._table_q, self._accept,
                                    self._starts, data, self.k)
        else:
            out = _assoc_match(self._table_q, self._accept,
                               self._starts, data)
        return out

    def match_encoded(self, packed: PackedBatch) -> jnp.ndarray:
        """Device half of the split dispatch (see :meth:`encode`)."""
        if not packed.packed:
            data = jnp.asarray(packed.idx)
            if self.strategy == "compose":
                return dfa_match_compose(self._table_q, self._accept,
                                         self._starts, data, self.k)
            if self.strategy == "assoc":
                return _assoc_match(self._table_q, self._accept,
                                    self._starts, data)
            return _stride_match(self.k, self._c1, self._flat, self._map,
                                 self._accept, self._starts, data)
        return _packed_match(self._c1 ** self.k, self._flat,
                             self._accept, self._starts,
                             jnp.asarray(packed.idx),
                             jnp.asarray(packed.overlong))

    def scan(self, states, data, donate: bool = False) -> jnp.ndarray:
        """Streaming chunk scan: advance [B, R] carried states over a
        [B, L] chunk (dfa_scan contract).  With ``donate=True`` the
        carry buffer is donated to the jitted program, so a steady-state
        chunk loop reuses one buffer instead of allocating per chunk."""
        data = jnp.asarray(data)
        states = jnp.asarray(states, dtype=jnp.int32)
        if self.strategy == "stride":
            fn = _stride_scan_donated if donate else _stride_scan
            return fn(self.k, self._c1, self._flat, self._map, states,
                      data)
        if self.strategy == "compose":
            return dfa_scan_compose(self._table_q, states, data, self.k)
        return _assoc_scan(self._table_q, states, data).astype(jnp.int32)

    # ------------------------------------------------------------ report

    def depth(self, length: Optional[int] = None) -> int:
        """Dependent-step count for a payload of ``length`` bytes."""
        ln = self.max_len if length is None else int(length)
        if self.strategy == "assoc":
            return max(1, int(np.ceil(np.log2(max(ln, 2)))))
        return (ln + self.k - 1) // self.k

    def describe(self) -> dict:
        """Engine-selection report for bench extras / status surfaces."""
        dt = self._dtype.name
        return {"strategy": self.strategy, "k": self.k, "dtype": dt,
                "states": int(self.compiled.num_states),
                "classes": self.num_classes,
                "depth_at_max_len": self.depth(),
                "byte_table_bytes": int(self.compiled.table.nbytes),
                "resident_bytes": self._packed_bytes,
                "on_accel": self.on_accel,
                "tag": f"{self.strategy}{self.k}-{dt}-C{self.num_classes}"}
