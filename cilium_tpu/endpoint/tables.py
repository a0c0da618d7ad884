"""Device-resident stacked policy tables with incremental row updates.

The analog of the reference's per-endpoint pinned BPF policy maps
(pkg/maps/policymap) plus the incremental sync (pkg/endpoint/bpf.go:607
syncPolicyMap): per-endpoint verdict tables live stacked in one [E, S]
device tensor; syncing one endpoint's policy rewrites only that
endpoint's row (three [S] int32 transfers), not the whole stack. Growth
(more endpoints / bigger tables / longer probe chains) falls back to a
full rebuild + swap — the double-buffered "generation" path.
"""

from __future__ import annotations

import threading

from ..utils.lock import RMutex
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.hashtab import HashTable, _next_pow2, build_hash_table
from ..compiler.policy_tables import pack_key
from ..observability.jitstats import jit_telemetry
from ..observability.stages import record_stage
from ..policy.mapstate import PolicyMapState

MIN_SLOTS = 64


def _build_endpoint_table(state: PolicyMapState, slots: Optional[int],
                          max_load: float = 0.5) -> HashTable:
    entries = {pack_key(k): v.proxy_port for k, v in state.items()}
    if slots is None:
        return build_hash_table(entries, min_slots=MIN_SLOTS,
                                max_load=max_load)
    t = build_hash_table(entries, min_slots=slots, max_load=1.0)
    if t.slots != slots:
        raise _NeedsGrow(t.slots)
    return t


class _NeedsGrow(Exception):
    def __init__(self, slots_needed: int):
        self.slots_needed = slots_needed


@jax.jit
def _set_row(arr: jnp.ndarray, row: jnp.ndarray,
             slot: jnp.ndarray) -> jnp.ndarray:
    return arr.at[slot].set(row)


class DeviceTableManager:
    """Owns the stacked device policy tensors and endpoint row slots.

    ``sync_endpoint`` is the hot path: one endpoint's new PolicyMapState
    becomes one row rewrite. The manager keeps a host numpy mirror so a
    full rebuild never round-trips through the device.
    """

    def __init__(self, initial_endpoints: int = 8,
                 initial_slots: int = MIN_SLOTS, max_load: float = 0.5):
        self._lock = RMutex("table-manager")
        self.max_load = max_load
        # hash tables are always pow2-sized; normalize up front so row
        # rebuilds land on exactly self.slots
        initial_slots = _next_pow2(max(initial_slots, 8))
        self.slots = initial_slots
        self.capacity = initial_endpoints
        self.generation = 0           # bumps on every full swap
        self.revision = 0             # policy revision last synced
        self.max_probe = 1
        self._row_probe: Dict[int, int] = {}
        # rows written since the last drain: the engine's packed-buffer
        # write-through (refresh_policy fast path) realizes exactly
        # these as row scatters instead of repacking the whole stack
        self._dirty_slots: set = set()
        self._free: List[int] = list(range(initial_endpoints))
        self._slot_of: Dict[int, int] = {}   # endpoint id -> row
        self._state_of: Dict[int, PolicyMapState] = {}
        # host mirrors
        self._h_key_id = np.zeros((initial_endpoints, initial_slots),
                                  np.int32)
        self._h_key_meta = np.zeros_like(self._h_key_id)
        self._h_value = np.zeros_like(self._h_key_id)
        # device tensors
        self.key_id = jnp.asarray(self._h_key_id)
        self.key_meta = jnp.asarray(self._h_key_meta)
        self.value = jnp.asarray(self._h_value)

    # ------------------------------------------------------------- slots

    def attach(self, endpoint_id: int) -> int:
        """Assign a table row to an endpoint (grows the stack 2x when
        full — the full-swap path)."""
        with self._lock:
            if endpoint_id in self._slot_of:
                return self._slot_of[endpoint_id]
            if not self._free:
                self._grow(capacity=self.capacity * 2)
            slot = self._free.pop(0)
            self._slot_of[endpoint_id] = slot
            self._state_of[endpoint_id] = PolicyMapState()
            return slot

    def detach(self, endpoint_id: int) -> None:
        """Release an endpoint's row and zero it on device."""
        with self._lock:
            slot = self._slot_of.pop(endpoint_id, None)
            if slot is None:
                return
            self._state_of.pop(endpoint_id, None)
            self._row_probe.pop(slot, None)
            self._free.append(slot)
            zero = np.zeros(self.slots, np.int32)
            self._write_row(slot, zero, zero, zero, probe=1)

    def slot_of(self, endpoint_id: int) -> Optional[int]:
        with self._lock:
            return self._slot_of.get(endpoint_id)

    # -------------------------------------------------------------- sync

    def sync_endpoint(self, endpoint_id: int, state: PolicyMapState,
                      revision: int) -> Dict:
        """Realize ``state`` for the endpoint on device.

        Returns sync stats: {"full_swap": bool, "slots": S,
        "entries": N, "generation": G}. Raises KeyError for an
        unattached endpoint.
        """
        import time as _time
        t0 = _time.perf_counter()
        with self._lock:
            slot = self._slot_of[endpoint_id]
            full_swap = False
            try:
                table = _build_endpoint_table(state, self.slots,
                                              self.max_load)
                # guard against load creeping past the bound in-place
                if table.load > self.max_load:
                    raise _NeedsGrow(self.slots * 2)
            except _NeedsGrow as g:
                self._state_of[endpoint_id] = PolicyMapState(state)
                self._grow(slots=max(g.slots_needed, self.slots * 2))
                full_swap = True
                table = None
            if not full_swap:
                self._state_of[endpoint_id] = PolicyMapState(state)
                self._write_row(slot, table.key_a, table.key_b,
                                table.value, probe=table.max_probe)
            self.revision = max(self.revision, revision)
            out = {"full_swap": full_swap, "slots": self.slots,
                   "entries": len(state),
                   "generation": self.generation,
                   "max_probe": self.max_probe}
            nbytes = int(self._h_key_id.nbytes * 3)
        # device-apply telemetry (observability/): the row sync IS the
        # syncPolicyMap hot path, the full swap its slow fallback
        record_stage("device-tables",
                     "full-swap" if full_swap else "row-sync",
                     _time.perf_counter() - t0)
        jit_telemetry.set_device_bytes("policy-tables", nbytes)
        return out

    def _write_row(self, slot: int, key_a: np.ndarray, key_b: np.ndarray,
                   value: np.ndarray, probe: int) -> None:
        self._h_key_id[slot] = key_a
        self._h_key_meta[slot] = key_b
        self._h_value[slot] = value
        self._dirty_slots.add(slot)
        self._row_probe[slot] = probe
        new_probe = max([1] + list(self._row_probe.values()))
        s = jnp.int32(slot)
        self.key_id = _set_row(self.key_id, jnp.asarray(key_a), s)
        self.key_meta = _set_row(self.key_meta, jnp.asarray(key_b), s)
        self.value = _set_row(self.value, jnp.asarray(value), s)
        self.max_probe = new_probe

    def _grow(self, capacity: Optional[int] = None,
              slots: Optional[int] = None) -> None:
        """Full rebuild at a bigger geometry + device swap (the
        double-buffered generation bump)."""
        new_cap = capacity or self.capacity
        new_slots = _next_pow2(slots or self.slots)
        # some endpoint's state may need more slots than requested;
        # find the real bound before touching any manager state
        while True:
            try:
                rebuilt = {
                    ep_id: _build_endpoint_table(self._state_of[ep_id],
                                                 new_slots, max_load=1.0)
                    for ep_id in self._slot_of}
                break
            except _NeedsGrow as g:
                new_slots = _next_pow2(max(g.slots_needed, new_slots * 2))
        h_id = np.zeros((new_cap, new_slots), np.int32)
        h_meta = np.zeros_like(h_id)
        h_val = np.zeros_like(h_id)
        self._row_probe = {}
        for ep_id, slot in self._slot_of.items():
            table = rebuilt[ep_id]
            h_id[slot] = table.key_a
            h_meta[slot] = table.key_b
            h_val[slot] = table.value
            self._row_probe[slot] = table.max_probe
        used = set(self._slot_of.values())
        self._free = [i for i in range(new_cap) if i not in used]
        self.capacity, self.slots = new_cap, new_slots
        self._h_key_id, self._h_key_meta, self._h_value = h_id, h_meta, h_val
        self.key_id = jnp.asarray(h_id)
        self.key_meta = jnp.asarray(h_meta)
        self.value = jnp.asarray(h_val)
        self.max_probe = max([1] + list(self._row_probe.values()))
        self.generation += 1

    # ------------------------------------------------------------- views

    def tensors(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        with self._lock:
            return self.key_id, self.key_meta, self.value

    def snapshot(self):
        """Atomic (geometry, tensors) pair under one lock acquisition.

        Consumers that first read geometry and then fetch tensors in a
        second call can interleave with a concurrent sync_endpoint that
        lengthens a probe chain in-place (no generation bump) or a grow
        that reshapes the stack — installing tensors under a step jitted
        for stale geometry.  geometry = (capacity, slots, max_probe,
        generation).
        """
        with self._lock:
            return ((self.capacity, self.slots, self.max_probe,
                     self.generation),
                    (self.key_id, self.key_meta, self.value))

    def drain_dirty(self) -> Dict[int, Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]]:
        """{slot: (key_id row, key_meta row, value row)} for every row
        written since the last drain, from the host mirror (always the
        newest content), clearing the dirty set.  The engine's packed
        write-through consumes this on the refresh_policy fast path;
        rows are idempotent to re-apply, so draining after a full
        rebuild only costs a redundant scatter, never staleness."""
        with self._lock:
            out = {}
            for slot in sorted(self._dirty_slots):
                if slot >= self._h_key_id.shape[0]:
                    continue
                out[slot] = (self._h_key_id[slot].copy(),
                             self._h_key_meta[slot].copy(),
                             self._h_value[slot].copy())
            self._dirty_slots.clear()
            return out

    def host_mirror(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        with self._lock:
            return (self._h_key_id.copy(), self._h_key_meta.copy(),
                    self._h_value.copy())

    def states_by_slot(self) -> Dict[int, PolicyMapState]:
        """{table row slot: stored PolicyMapState} — the host-of-record
        the fail-static oracle (datapath/supervisor.py) enforces while
        the device lane is degraded, and the source the recovery path
        rebuilds device tensors from.

        The states are not copied.  Each is the manager's own copy,
        made at ``attach``/``sync_endpoint`` and replaced there, never
        mutated in place, so a caller that keeps this dict keeps
        exactly the states as of the call (the oracle's last-known-good
        view).  Callers must not mutate them."""
        with self._lock:
            return {slot: self._state_of[ep_id]
                    for ep_id, slot in self._slot_of.items()}

    def stats(self) -> Dict:
        with self._lock:
            return {"capacity": self.capacity, "slots": self.slots,
                    "endpoints": len(self._slot_of),
                    "generation": self.generation,
                    "max_probe": self.max_probe,
                    "revision": self.revision,
                    "nbytes": int(self._h_key_id.nbytes * 3)}
