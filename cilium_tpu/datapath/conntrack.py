"""Batched connection tracking: device-resident 5-tuple CT table.

Semantics follow the reference's eBPF conntrack (bpf/lib/conntrack.h):
  * lifetimes: TCP 21600s / non-TCP 60s / SYN 60s / close 10s
    (conntrack.h:31-34);
  * verdict states CT_NEW / CT_ESTABLISHED / CT_REPLY / CT_RELATED, with
    the reverse-tuple lookup first so REPLY/RELATED take precedence
    (conntrack.h:467-480 comment);
  * RST/FIN flips the closing bit and shortens the lifetime to the close
    timeout (conntrack.h:266-277);
  * accumulated TCP-flag tracking per direction (conntrack.h:125).

TPU re-design: the per-packet kernel hash-map update becomes a batched
functional step over stacked arrays — lookup is K gathers; updates and
inserts are scatters into a table with one extra *sentinel slot* that
absorbs no-op writes (so guard writes can never corrupt a live slot).
Within-batch races (two different new flows claiming one empty slot, or
interleaved flag accumulation) lose at most one write and self-heal on
the next packet of the flow — the same class of benign race the
reference documents for concurrent per-CPU updates (conntrack.h:155-170).
GC is a host-driven sweep (pkg/maps/ctmap ctmap.go:240 doGC analog)
implemented as a device scan.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.hashtab_ops import hash_mix_jnp

# Lifetimes (reference: conntrack.h:31-34).
CT_LIFETIME_TCP = 21600
CT_LIFETIME_NONTCP = 60
CT_SYN_TIMEOUT = 60
CT_CLOSE_TIMEOUT = 10
CT_REPORT_INTERVAL = 5

# Verdict states (reference: conntrack.h CT_* enum order).
CT_NEW = 0
CT_ESTABLISHED = 1
CT_REPLY = 2
CT_RELATED = 3

# Direction (reference: CT_INGRESS/CT_EGRESS).
CT_INGRESS = 0
CT_EGRESS = 1

# TCP flag bits (standard wire order, lower byte).
TCP_FIN = 0x01
TCP_SYN = 0x02
TCP_RST = 0x04
TCP_ACK = 0x10

# Entry flag bits packed in the state word.
_RX_CLOSING = 1 << 0
_TX_CLOSING = 1 << 1
_RELATED = 1 << 2


class CTState(NamedTuple):
    """Device CT table: 4-word keys + entry fields, all [N+1] int32
    (last slot is the no-op sentinel)."""

    k0: jnp.ndarray       # saddr
    k1: jnp.ndarray       # daddr
    k2: jnp.ndarray       # sport<<16 | dport
    k3: jnp.ndarray       # proto<<8 | dir<<1 | 1   (0 == empty slot)
    expires: jnp.ndarray  # absolute seconds
    state: jnp.ndarray    # closing/related bits | rx_flags<<8 | tx_flags<<16
    rev_nat: jnp.ndarray  # rev-NAT index for LB'd flows
    proxy_port: jnp.ndarray  # L7 redirect port for the flow (0 = none)


# Field indices shared by both CT representations: the classic CTState
# pytree (8 leaves) indexes its fields numerically exactly like the
# packed form indexes its rows, so every read below is
# representation-agnostic (the dispatch-floor packing; parallel/packing).
_K0, _K1, _K2, _K3, _EXPIRES, _STATE, _REV_NAT, _PROXY = range(8)


class CTPack(NamedTuple):
    """The packed CT representation: THREE stacked int32 buffers —
    three jitted-step leaves instead of eight, donated as a unit.

    The split follows XLA's copy-insertion boundaries, not field kind:

    - ``keys`` [4, N+1] (k0..k3) has a strictly linear read -> write ->
      read -> write chain through the create rounds, so its buffer
      updates in place copy-free under donation;
    - ``es`` [2, N+1] (expires, state) is written in the update phase
      while its ORIGINAL contents still feed later reads (round free-
      slot checks, flag accumulation), forcing XLA to preserve a
      pre-write copy;
    - ``rp`` [2, N+1] (rev_nat, proxy_port) is written only at create
      but read from the original for the verdict outputs — its own
      smaller preserved copy.

    One monolithic [8, N+1] pack would widen every one of those
    unavoidable copies to the whole table (measured: ~+300 us/step on
    CPU at 2^16 slots); this split keeps the copied bytes at parity
    with the classic per-leaf form while dispatching 3 leaves."""

    keys: jnp.ndarray   # [4, N+1]: k0, k1, k2, k3
    es: jnp.ndarray     # [2, N+1]: expires, state
    rp: jnp.ndarray     # [2, N+1]: rev_nat, proxy_port


def make_ct_pack(slots: int) -> CTPack:
    z = lambda rows: jnp.zeros((rows, slots + 1), jnp.int32)
    return CTPack(keys=z(4), es=z(2), rp=z(2))


def _pack_sub(field: int):
    """(CTPack field name, row) for a CTState field index."""
    if field < 4:
        return "keys", field
    if field < 6:
        return "es", field - 4
    return "rp", field - 6


def ct_host_fields(state) -> Dict[str, "np.ndarray"]:
    """{field name: host array} for either CT representation (one
    device->host transfer per pack buffer)."""
    if isinstance(state, CTState):
        return {f: np.asarray(getattr(state, f))
                for f in CTState._fields}
    host = {name: np.asarray(buf) for name, buf
            in zip(CTPack._fields, state)}
    out = {}
    for i, f in enumerate(CTState._fields):
        name, row = _pack_sub(i)
        out[f] = host[name][row]
    return out


def _g(st, field: int, idx):
    """One field gather on either representation.  The pack branch
    indexes the 2D buffer directly (``buf[row, idx]``) so XLA emits
    one fused gather — ``buf[row][idx]`` would materialize the whole
    row as a slice first, a hidden per-read copy of the table."""
    if isinstance(st, CTState):
        return st[field][idx]
    name, row = _pack_sub(field)
    return getattr(st, name)[row, idx]


def _scatter(st, field: int, idx, val, op: str = "set",
             mode: Optional[str] = None):
    """One field scatter on either representation: a leaf `.at[idx]`
    update for CTState, a row `.at[row, idx]` update for the pack
    (identical indices and values — bit-exact across representations;
    the chained pack scatters stay in place under donation)."""
    kw = {} if mode is None else {"mode": mode}
    if isinstance(st, CTState):
        arr = getattr(st[field].at[idx], op)(val, **kw)
        return st._replace(**{CTState._fields[field]: arr})
    name, row = _pack_sub(field)
    buf = getattr(getattr(st, name).at[row, idx], op)(val, **kw)
    return st._replace(**{name: buf})


class CTBatch(NamedTuple):
    """Per-packet tuples, all [B] int32."""

    saddr: jnp.ndarray
    daddr: jnp.ndarray
    sport: jnp.ndarray
    dport: jnp.ndarray
    proto: jnp.ndarray
    direction: jnp.ndarray  # CT_INGRESS / CT_EGRESS
    tcp_flags: jnp.ndarray  # lower TCP flag byte (0 for non-TCP)
    related: jnp.ndarray    # ICMP error -> related lookup (bool int32)


def make_ct_state(slots: int) -> CTState:
    # Distinct buffers per field: aliased arrays break donation (the whole
    # CTState is donated each step).
    z = lambda: jnp.zeros(slots + 1, jnp.int32)
    return CTState(k0=z(), k1=z(), k2=z(), k3=z(), expires=z(), state=z(),
                   rev_nat=z(), proxy_port=z())


def _pack_k2(sport, dport):
    return ((sport & 0xFFFF) << 16) | (dport & 0xFFFF)


def _pack_k3(proto, direction):
    return ((proto & 0xFF) << 8) | ((direction & 1) << 1) | 1


def _ct_hash(k0, k1, k2, k3):
    return hash_mix_jnp(hash_mix_jnp(k0, k1), hash_mix_jnp(k2, k3))


def _probe_idx(k0, k1, k2, k3, slots: int, max_probe: int):
    h = _ct_hash(k0, k1, k2, k3) & jnp.int32(slots - 1)
    return (h[:, None] + jnp.arange(max_probe, dtype=jnp.int32)[None, :]) \
        & jnp.int32(slots - 1)


def _lookup(ct, k0, k1, k2, k3, now, slots: int, max_probe: int):
    """Returns (found [B], slot [B]) for live (unexpired) entries.
    ``ct`` is either representation (numeric field reads)."""
    idx = _probe_idx(k0, k1, k2, k3, slots, max_probe)       # [B, K]
    hit = (_g(ct, _K0, idx) == k0[:, None]) & \
        (_g(ct, _K1, idx) == k1[:, None]) & \
        (_g(ct, _K2, idx) == k2[:, None]) & \
        (_g(ct, _K3, idx) == k3[:, None]) & \
        (_g(ct, _K3, idx) != 0) & (_g(ct, _EXPIRES, idx) > now)
    found = jnp.any(hit, axis=1)
    slot = jnp.sum(jnp.where(hit, idx, jnp.int32(0)), axis=1)
    return found, slot


def _lifetime(proto, tcp_flags):
    is_tcp = proto == 6
    syn_only = (tcp_flags & (TCP_SYN | TCP_ACK)) == TCP_SYN
    return jnp.where(is_tcp,
                     jnp.where(syn_only, jnp.int32(CT_SYN_TIMEOUT),
                               jnp.int32(CT_LIFETIME_TCP)),
                     jnp.int32(CT_LIFETIME_NONTCP))


def ct_step(ct, batch: CTBatch, now: jnp.ndarray,
            create_mask: jnp.ndarray,
            update_mask: Optional[jnp.ndarray] = None,
            rev_nat_in: Optional[jnp.ndarray] = None,
            proxy_port_in: Optional[jnp.ndarray] = None,
            *, slots: int, max_probe: int
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, "CTState"]:
    """One batched CT pass.

    ``ct`` is either representation — the CTState pytree or the packed
    [8, N+1] matrix (make_ct_pack); the returned ct' matches the input.
    The math is identical either way: same gathers, same scatters with
    the same indices, resolved at trace time.

    ``create_mask`` [B] bool gates CT_NEW entry creation (the policy
    verdict gate — reference bpf_lxc.c:545 creates only after the
    verdict allows). ``update_mask`` [B] bool additionally gates
    hit-entry updates (prefilter-dropped packets must not refresh or
    tear down live entries). ``rev_nat_in``/``proxy_port_in`` [B] are
    stored into newly created entries (the reference stores
    rev_nat_index and proxy_port in ct_state at create —
    conntrack.h ct_create4, proxy redirect path).

    Returns (ct_verdict [B] in CT_*, rev_nat [B], proxy_port [B], ct').
    """
    # Masked writes target one-past-the-end and are DROPPED by the
    # scatter (mode="drop") — nothing lands in the table, so the
    # sentinel slot stays zero without per-round clear passes.  The
    # probe index mask (& slots-1) keeps slot N invisible to lookups
    # either way; dropping beats writing-then-clearing because the
    # clear chains were the last thing forcing XLA to materialize
    # whole-table copies on the donated buffers.
    oob = jnp.int32(slots + 1)
    b = batch.saddr.shape[0]
    if update_mask is None:
        update_mask = jnp.ones(b, bool)
    if rev_nat_in is None:
        rev_nat_in = jnp.zeros(b, jnp.int32)
    if proxy_port_in is None:
        proxy_port_in = jnp.zeros(b, jnp.int32)

    fwd_k0, fwd_k1 = batch.saddr, batch.daddr
    fwd_k2 = _pack_k2(batch.sport, batch.dport)
    fwd_k3 = _pack_k3(batch.proto, batch.direction)
    # Reverse tuple: swapped addrs/ports, flipped direction
    # (conntrack.h:287 ipv4_ct_tuple_reverse + flags flip).
    rev_k0, rev_k1 = batch.daddr, batch.saddr
    rev_k2 = _pack_k2(batch.dport, batch.sport)
    rev_k3 = _pack_k3(batch.proto, 1 - batch.direction)

    # Reverse first: REPLY/RELATED precedence (conntrack.h:468-471).
    rfound, rslot = _lookup(ct, rev_k0, rev_k1, rev_k2, rev_k3, now,
                            slots, max_probe)
    ffound, fslot = _lookup(ct, fwd_k0, fwd_k1, fwd_k2, fwd_k3, now,
                            slots, max_probe)

    hit = rfound | ffound
    slot = jnp.where(rfound, rslot, fslot)

    # --- update hit entries -------------------------------------------------
    closing = ((batch.tcp_flags & (TCP_FIN | TCP_RST)) != 0) & \
        (batch.proto == 6)
    life = jnp.where(closing, jnp.int32(CT_CLOSE_TIMEOUT),
                     _lifetime(batch.proto, batch.tcp_flags))
    new_exp = now + life
    dir_is_in = batch.direction == CT_INGRESS
    flag_bits = jnp.where(dir_is_in,
                          (batch.tcp_flags & 0xFF) << 8,
                          (batch.tcp_flags & 0xFF) << 16)
    close_bit = jnp.where(closing,
                          jnp.where(dir_is_in, jnp.int32(_RX_CLOSING),
                                    jnp.int32(_TX_CLOSING)),
                          jnp.int32(0))

    upd_slot = jnp.where(hit & update_mask.astype(bool), slot, oob)
    # Last-write-wins scatter for expiry (close shortens, activity extends;
    # duplicate-slot ordering is unspecified — benign, self-correcting).
    ct2 = _scatter(ct, _EXPIRES, upd_slot, new_exp, mode="drop")
    # Flag accumulation via max of (old | new): with in-batch duplicates the
    # larger OR wins; dropped bits are re-OR'd by the flow's next packet
    # (the reference documents the identical race as self-correcting).
    # (The state value reads ct2 — identical to the pre-update table,
    # since the expires write touches no state row — so every gather
    # past this point stays on the donation chain: XLA never needs a
    # preserved pre-write copy of the table.)
    ct2 = _scatter(ct2, _STATE, upd_slot,
                   _g(ct2, _STATE, slot) | flag_bits | close_bit,
                   op="max", mode="drop")

    # --- create new entries -------------------------------------------------
    create = (~hit) & create_mask.astype(bool) & update_mask.astype(bool)
    new_state = flag_bits | jnp.where(batch.related != 0,
                                      jnp.int32(_RELATED), jnp.int32(0))
    new_life = now + _lifetime(batch.proto, batch.tcp_flags)
    # Two rounds: flows that lose a same-batch race for an empty slot
    # re-probe against the updated table and take the next free slot.
    # Residual losses after round 2 are ~(collisions^2 / slots) — the
    # flow's next packet re-creates it (benign, like the reference's
    # documented concurrent-update races).
    for _ in range(2):
        still = create & ~_lookup(ct2, fwd_k0, fwd_k1, fwd_k2, fwd_k3,
                                  now, slots, max_probe)[0]
        cidx = _probe_idx(fwd_k0, fwd_k1, fwd_k2, fwd_k3, slots, max_probe)
        free = (_g(ct2, _K3, cidx) == 0) | \
            (_g(ct2, _EXPIRES, cidx) <= now)                  # [B, K]
        first_free = free & (jnp.cumsum(free.astype(jnp.int32), axis=1) == 1)
        has_free = jnp.any(free, axis=1) & still
        cslot = jnp.sum(jnp.where(first_free, cidx, jnp.int32(0)), axis=1)
        tgt = jnp.where(has_free, cslot, oob)
        for f, val in ((_K0, fwd_k0), (_K1, fwd_k1), (_K2, fwd_k2),
                       (_K3, fwd_k3), (_EXPIRES, new_life),
                       (_STATE, new_state), (_REV_NAT, rev_nat_in),
                       (_PROXY, proxy_port_in)):
            ct2 = _scatter(ct2, f, tgt, val, mode="drop")

    # --- verdict outputs, read from the FINAL table -------------------------
    # Bit-exact with pre-write reads: creates touch only free slots
    # (disjoint from live hit slots), the flag max only ADDS bits so
    # the _RELATED bit is stable, and non-hit rows are masked.  Reading
    # the latest buffers keeps every gather on the donation chain —
    # stale-version reads would force XLA to preserve whole pre-write
    # table copies per step (measured ~2.5 MB/step at 2^16 slots).
    entry_related = rfound & ((_g(ct2, _STATE, rslot) & _RELATED) != 0)
    verdict = jnp.where(
        rfound,
        jnp.where(entry_related | (batch.related != 0),
                  jnp.int32(CT_RELATED), jnp.int32(CT_REPLY)),
        jnp.where(ffound, jnp.int32(CT_ESTABLISHED), jnp.int32(CT_NEW)))
    rev_nat = jnp.where(hit, _g(ct2, _REV_NAT, slot), jnp.int32(0))
    # Established flows keep redirecting through their recorded proxy
    # port (the reference keeps ct_state.proxy_port so L7 enforcement
    # covers the whole connection, not just its first packet).
    proxy_port = jnp.where(ffound, _g(ct2, _PROXY, fslot),
                           jnp.int32(0))
    return verdict, rev_nat, proxy_port, ct2


def ct_set_rev_nat(ct, batch: CTBatch, rev_nat_idx: jnp.ndarray,
                   now: jnp.ndarray, *, slots: int, max_probe: int):
    """Stamp rev-NAT indices onto existing forward entries (LB path —
    reference: ct_create4 stores ct_state->rev_nat_index).  Either CT
    representation; masked rows scatter out of bounds and drop."""
    k2 = _pack_k2(batch.sport, batch.dport)
    k3 = _pack_k3(batch.proto, batch.direction)
    found, slot = _lookup(ct, batch.saddr, batch.daddr, k2, k3, now,
                          slots, max_probe)
    tgt = jnp.where(found & (rev_nat_idx != 0), slot,
                    jnp.int32(slots + 1))
    return _scatter(ct, _REV_NAT, tgt, rev_nat_idx, mode="drop")


def _row(st, field: int):
    """One field's full row on either representation (control-plane
    reads: gc sweep, occupancy)."""
    if isinstance(st, CTState):
        return st[field]
    name, row = _pack_sub(field)
    return getattr(st, name)[row]


def ct_gc(ct, now: jnp.ndarray):
    """Sweep expired entries (ctmap.go:240 doGC analog). Returns
    (ct', n_deleted).  Either CT representation."""
    dead = (_row(ct, _K3) != 0) & (_row(ct, _EXPIRES) <= now)
    n = jnp.sum(dead.astype(jnp.int32))
    if isinstance(ct, CTState):
        clear = lambda x: jnp.where(dead, jnp.int32(0), x)
        return CTState(*(clear(a) for a in ct)), n
    return CTPack(*(jnp.where(dead[None, :], jnp.int32(0), b)
                    for b in ct)), n


class ConntrackTable:
    """Host wrapper owning the device CT state (pkg/maps/ctmap analog).

    ``packed=True`` keeps the state in the single [8, N+1] buffer
    (make_ct_pack) — the dispatch-floor representation the engine
    dispatches; snapshots keep the identical per-field npz layout
    either way, so checkpoints restore across representations."""

    def __init__(self, slots: int = 1 << 16, max_probe: int = 8,
                 packed: bool = False):
        assert slots & (slots - 1) == 0
        self.slots = slots
        self.max_probe = max_probe
        self.packed = packed
        self.state = make_ct_pack(slots) if packed \
            else make_ct_state(slots)
        self._step = jax.jit(functools.partial(
            ct_step, slots=slots, max_probe=max_probe),
            donate_argnums=(0,))
        self._gc = jax.jit(ct_gc, donate_argnums=(0,))
        self._set_rev_nat = jax.jit(functools.partial(
            ct_set_rev_nat, slots=slots, max_probe=max_probe),
            donate_argnums=(0,))

    def step(self, batch: CTBatch, now: int,
             create_mask=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        b = batch.saddr.shape[0]
        if create_mask is None:
            create_mask = jnp.ones(b, bool)
        verdict, rev_nat, _proxy, self.state = self._step(
            self.state, batch, jnp.int32(now), create_mask)
        return verdict, rev_nat

    def stamp_rev_nat(self, batch: CTBatch, rev_nat_idx, now: int) -> None:
        self.state = self._set_rev_nat(self.state, batch,
                                       rev_nat_idx, jnp.int32(now))

    def gc(self, now: int) -> int:
        self.state, n = self._gc(self.state, jnp.int32(now))
        return int(n)

    def entry_count(self) -> int:
        return int((np.asarray(_row(self.state, _K3)[:-1]) != 0).sum())

    def snapshot(self) -> Dict[str, "np.ndarray"]:
        """Host copy of every CT field — the pinned-ctmap analog: the
        reference's conntrack survives agent restarts because the bpf
        map stays pinned; here the state is checkpointed and restored
        so established flows keep their verdicts across a restart.
        Same per-field layout for both representations."""
        out = ct_host_fields(self.state)
        out["slots"] = np.array([self.slots], np.int64)
        return out

    def prepare_snapshot(self, arrays: Dict[str, "np.ndarray"]
                         ) -> CTState:
        """Validate + build a CTState from a snapshot WITHOUT mutating
        the table — callers prepare every table first, then assign, so
        a bad snapshot can never leave half-restored state.  Slot
        positions encode the hash placement, so a geometry change
        invalidates the snapshot (ValueError; callers start cold —
        exactly what cilium-map-migrate refuses to carry across
        incompatible layouts)."""
        slots = int(np.asarray(arrays["slots"])[0])
        if slots != self.slots:
            raise ValueError(
                f"CT snapshot geometry {slots} != table {self.slots}")
        if self.packed:
            stack = lambda fields: jnp.asarray(np.stack(
                [np.asarray(arrays[f], np.int32) for f in fields]))
            return CTPack(keys=stack(CTState._fields[:4]),
                          es=stack(CTState._fields[4:6]),
                          rp=stack(CTState._fields[6:]))
        return CTState(**{
            f: jnp.asarray(np.asarray(arrays[f], np.int32))
            for f in CTState._fields})

    def restore_snapshot(self, arrays: Dict[str, "np.ndarray"]) -> int:
        """prepare_snapshot + assign; returns live entries restored."""
        self.state = self.prepare_snapshot(arrays)
        return self.entry_count()
