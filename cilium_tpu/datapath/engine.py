"""The full datapath engine: host orchestrator over compiled tables.

Owns one generation of every device table (policy, ipcache LPM, LB,
prefilter) plus the mutable conntrack state and counters, and exposes a
single ``process(batch)`` call — the complete per-packet path of the
reference (bpf_lxc.c egress/ingress) as one jitted program.
"""

from __future__ import annotations

import threading

from ..utils.lock import Mutex
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.lpm import (CompiledLPM, CompiledLPM6, compile_lpm,
                            compile_lpm6)
from ..compiler.policy_tables import CompiledPolicy, compile_endpoints
from ..observability.jitstats import jit_telemetry
from ..observability.pressure import compute_pressure
from ..observability.stages import record_stage
from ..policy.mapstate import PolicyMapState
from ..utils.metrics import count_policy_verdicts
from .conntrack import ConntrackTable, ct_host_fields
from .lb import (CompiledLB, CompiledLB6, LoadBalancer, Service,
                 Service6, compile_lb, compile_lb6)
from .pipeline import (DatapathTables, FullPacketBatch, FullPacketBatch6,
                       FullTables, FullTables6, build_tables,
                       full_datapath_step, full_datapath_step6,
                       full_datapath_step_packed, lpm6_tables)
from .events import format_rule
from .prefilter import PreFilter
from .verdict import (Counters, Provenance, _explain_jit,
                      make_counter_pack, make_packet_batch)


class Datapath:
    """One device-resident datapath generation + mutable flow state.

    Swap-on-regenerate: the agent compiles a new generation from the
    policy repository and calls ``load_policy`` — conntrack state and
    counters survive the swap when shapes allow (the analog of pinned
    BPF maps surviving agent restart, daemon/state.go).
    """

    def __init__(self, ct_slots: int = 1 << 16, ct_probe: int = 8):
        # process/gc/_rebuild all touch donated CT buffers; without
        # mutual exclusion the periodic GC controller can donate the
        # state out from under an in-flight process() (deleted-array
        # crash)
        self._lock = Mutex("datapath")
        self.prefilter = PreFilter()
        self.lb = LoadBalancer()
        # packed CT representation ([8, N+1] buffers): ONE jitted-step
        # leaf per family instead of eight (the dispatch floor fix —
        # parallel/packing.py); snapshots keep the per-field layout
        self.ct = ConntrackTable(slots=ct_slots, max_probe=ct_probe,
                                 packed=True)
        # separate v6 CT table (the reference keeps ct6 apart from ct4)
        self.ct6 = ConntrackTable(slots=ct_slots, max_probe=ct_probe,
                                  packed=True)
        self.compiled_policy: Optional[CompiledPolicy] = None
        self.compiled_ipcache: Optional[CompiledLPM] = None
        self.compiled_ipcache6: Optional[CompiledLPM6] = None
        # host mirrors of what's compiled into the device LPMs (for
        # the map-dump surface; the reference reads pinned maps back)
        self.ipcache_prefixes: Dict[str, int] = {}
        self.ipcache_prefixes6: Dict[str, int] = {}
        # v6 service registry (lb6): (vip words, port, proto) -> Service6
        self.lb6_services: Dict[tuple, Service6] = {}
        self.compiled_lb6: Optional[CompiledLB6] = None
        # monotonic across deletes: freed rev-NAT indices stay retired
        # (live CT entries may still carry them)
        self._lb6_next_rev = 1
        # tunnel map (pkg/maps/tunnel): pod CIDR -> tunnel endpoint u32,
        # programmed by the NodeManager on node add/delete
        self.tunnel_prefixes: Dict[str, int] = {}
        self.compiled_tunnel: Optional[CompiledLPM] = None
        # endpoint slot -> the endpoint's own security identity (the
        # per-endpoint SECLABEL the encap stage stamps into tunnel keys)
        self._ep_identity = np.zeros(8, np.int32)
        # packed per-entry counters ([2, E*S] uint32; verdict.py
        # make_counter_pack) — read through the ``counters`` property
        self._counters = None
        self.revision = 0
        self._step = None
        self._step_packed = None
        self._step_packed_nc = None
        self._tables: Optional[FullTables] = None
        self._step6 = None
        self._tables6: Optional[FullTables6] = None
        # the dispatch-floor packing (parallel/packing.py): the table
        # leaf zoo concatenated into a handful of grouped flat device
        # buffers, cached across steps and dispatched instead of the
        # ~30 FullTables leaves; re-packed only on table generation
        # change (delta-applies write through to the packed slices)
        self._manifest4 = None
        self._manifest6 = None
        self._tbufs4 = None
        self._tbufs6 = None
        self._rw4 = None           # (jitted row writer, group index)
        self._rw6 = None
        self._statics4: Dict = {}  # the jitted steps' static kwargs —
        self._statics6: Dict = {}  # exposed for the legacy-pytree
        #                            bench/parity twins
        self._pack_stats = {"full-packs": 0, "row-writes": 0,
                            "leaf-writes": 0}
        # the node's v6 router IP words (icmp6.h ROUTER_IP): the
        # address whose NS/echo the datapath answers itself
        self._router_ip6 = None
        # incremental mode: policy tensors owned by a DeviceTableManager
        # (endpoint/tables.py); row syncs swap tensors without re-jit
        self._table_mgr = None
        self._mgr_geometry = None  # (capacity, slots, max_probe, gen)
        # Hubble on-device flow aggregation (hubble/aggregation.py):
        # when enabled, both family steps scatter per-flow counters
        # into this device table inside the same compiled program
        self.flows = None
        # runtime self-telemetry (observability/): stage slices,
        # verdict-outcome counters, and the revision-served hook the
        # policy-propagation tracker uses to close the import->first-
        # verdict loop.  One flag gates all of it so the bench can
        # prove the disabled path costs ~0.
        self.telemetry_enabled = True
        self.on_revision_served = None  # callable(revision)
        self._served_revision = 0
        # deferred verdict-outcome accounting has its OWN lock: the
        # force-flush can block on a device transfer, and that must
        # never happen while holding the device dispatch lock
        self._verdict_lock = threading.Lock()
        self._pending_verdicts: List = []
        # per-second device timestamp cache: steady-state dispatch
        # reuses the same jnp scalar instead of a fresh H2D per batch
        self._ts_cache: Optional[Tuple[int, object]] = None
        # compiles are counted from JAX's own compile events
        jit_telemetry.attach()
        # the shared continuous micro-batching dispatcher
        # (datapath/serving.py), created on first use
        self._serving = None
        self._serving_lane_name = "verdict"
        # mesh placement (parallel/): when set, every device table this
        # engine owns is resident on the given (dp, 1) submesh — one
        # shard's column of the dataplane mesh — and packed batches are
        # sharded across its dp axis.  None = single-device (default).
        self._placement = None
        self._batch_sharding = None
        self._replicated_sharding = None
        self.shard_index: Optional[int] = None
        # host-of-record policy states (load_policy mode) — what the
        # fail-static oracle and the recovery gate answer from when no
        # DeviceTableManager owns the tensors
        self._host_states: Optional[Sequence[PolicyMapState]] = None
        # dataplane supervision knobs (datapath/supervisor.py): the
        # serving lane wraps launches in a DeviceSupervisor unless
        # disabled; enable_supervision=False gives the exact
        # pre-supervision dispatch path (and the compiled program is
        # byte-identical either way — supervision is host-side only)
        self._supervision_cfg: Dict = {"enabled": True}
        # verdict provenance (datapath/verdict.py Provenance): when
        # enabled, both family steps additionally emit the matched
        # policymap slot + decision tier per packet; the last batch's
        # pair is kept for the observability consumers.  Disabled =
        # the exact pre-provenance compiled program (one static flag).
        self.provenance_enabled = False
        self.last_provenance: Optional[Provenance] = None
        self._replay_probe = 1
        self._prov_decode_cache = None
        # on-device L7 fast verdicts (l7/fast.L7FastPrograms): when
        # set, both family steps fuse the fast-verdict stage — the
        # per-slot classification + fused DFA tables join the packed
        # dispatch buffers and the steps take a [B, W] payload lane.
        # None = the exact pre-fast compiled program.
        self._l7_fast = None
        self._l7_rw4 = None        # (jitted l7_prog row writer, gidx)
        self._l7_rw6 = None
        # cached absent-payload staging (all -1 = not decidable ->
        # redirect) per batch size, so payload-less callers of an
        # L7-enabled engine pay no per-batch allocation
        self._absent_payloads: Dict[int, np.ndarray] = {}
        # inline threat scoring (threat/): when set, both family steps
        # fuse the per-packet anomaly scorer — the quantized model
        # joins the packed dispatch as its own "threat-model" group
        # and the steps thread the shard-local ThreatState buffer
        # (token buckets + claim-window aggregates).  None = the exact
        # pre-threat compiled program.
        self._threat = None               # threat/model.ThreatModel
        self.threat_state = None          # threat/stage.ThreatState
        self.last_threat = None           # last batch's threat_out [B]
        self._threat_buckets = 1024
        self._threat_window_s = 8
        # window-aggregate update stripe (threat/stage.py): 1-in-N
        # sampled scatters, the flow table's ls_stripe precedent
        self._threat_stripe = 4
        # device-resident traffic analytics (analytics/): when on,
        # both family steps fuse the sketch/register stage over the
        # shard-local AnalyticsState buffer (two A/B epoch sections +
        # the control row — a pure engine-owned state leaf like the
        # threat state, no table leaves join the pack).  Off = the
        # exact pre-analytics compiled program.
        self._analytics_on = False
        self.analytics_state = None   # analytics/stage.AnalyticsState
        self._analytics_width = 1 << 12
        self._analytics_depth = 2
        self._analytics_lanes = 4
        self._analytics_stripe = 16

    @property
    def counters(self) -> Optional[Counters]:
        """Counters view over the packed [2, E*S] buffer (row slices;
        the observability/test surface — dispatch uses the pack)."""
        c = self._counters
        if c is None:
            return None
        return Counters(packets=c[0], bytes=c[1])

    def enable_flow_aggregation(self, slots: int = 1 << 12,
                                max_probe: int = 8,
                                claim_every: int = 4) -> None:
        """Turn on Hubble's device-resident flow table: the jitted v4
        and v6 steps gain a fused scatter-add tail keyed by (src
        identity, dst identity, dport, proto, event).  Both families
        share one table — flow keys are identity-based, like the
        policy tables.

        ``claim_every`` is the flow-birth admission stripe: only every
        N-th batch runs the claim machinery (the static
        claim_budget=0 variant of the step handles the rest), so the
        steady-state hot path pays for the reduction alone while new
        flows are admitted within N batches — the same
        bounded-admission idea as the per-batch claim budget."""
        from ..hubble.aggregation import FlowTable
        with self._lock:
            if self.flows is not None and self.flows.slots == slots:
                return
            self.flows = FlowTable(slots=slots, max_probe=max_probe)
            self._flow_claim_every = max(1, claim_every)
            self._flow_tick = 0
            if self._step is not None:
                self._rebuild()

    def disable_flow_aggregation(self) -> None:
        with self._lock:
            if self.flows is None:
                return
            self.flows = None
            if self._step is not None:
                self._rebuild()

    def enable_provenance(self) -> None:
        """Turn on per-packet verdict provenance: the jitted family
        steps additionally emit (matched policymap slot, decision
        tier) — see datapath/events.py TIER_*.  Re-jits the steps;
        the compiled program gains two [B] int32 outputs."""
        with self._lock:
            if self.provenance_enabled:
                return
            self.provenance_enabled = True
            if self._step is not None:
                self._rebuild()

    def disable_provenance(self) -> None:
        with self._lock:
            if not self.provenance_enabled:
                return
            self.provenance_enabled = False
            self.last_provenance = None
            if self._step is not None:
                self._rebuild()

    def enable_l7_fast(self, programs) -> None:
        """Turn on the on-device L7 fast-verdict stage: both family
        steps gain the fused DFA walk over a [B, W] payload lane,
        deciding first-bytes-decidable redirects inline (allow /
        DROP_POLICY_L7) and falling back to redirect-to-proxy for
        truncated/absent payloads or redirect-needing rules.

        ``programs`` is an l7/fast.L7FastPrograms (built from the
        eligible redirects by l7/fast.programs_from_redirects or
        build_fast_programs).  Re-jits the steps; the per-slot
        classification and DFA tables join the packed dispatch."""
        with self._lock:
            self._l7_fast = programs
            self._absent_payloads = {}
            if self._step is not None:
                self._rebuild()

    def disable_l7_fast(self) -> None:
        """Back to the exact pre-fast compiled program: every L7 rule
        redirects to its proxy port again."""
        with self._lock:
            if self._l7_fast is None:
                return
            self._l7_fast = None
            self._absent_payloads = {}
            if self._step is not None:
                self._rebuild()

    def l7_fast_report(self) -> Optional[Dict]:
        """Program-set report (bench extras / status surfaces)."""
        with self._lock:
            progs = self._l7_fast
        return None if progs is None else progs.describe()

    # -- inline threat scoring (threat/) -------------------------------------

    def enable_threat(self, model, buckets: int = 1024,
                      window_s: int = 8, stripe: int = 4) -> None:
        """Turn on the inline threat-scoring stage: both family steps
        fuse the quantized per-packet anomaly scorer (threat/stage.py)
        over the flow-table probe + the shard-local ThreatState
        buffer.  ``model`` is a threat/model.ThreatModel; its config
        (thresholds, shadow/enforce) is traced as VALUES, so later
        flips go through set_threat_config without a re-jit."""
        from ..threat.stage import make_threat_state
        with self._lock:
            self._threat = model
            self._threat_buckets = buckets
            self._threat_window_s = window_s
            self._threat_stripe = stripe
            self.threat_state = make_threat_state(buckets)
            if self._replicated_sharding is not None:
                self.threat_state = jax.device_put(
                    self.threat_state, self._replicated_sharding)
            if self._step is not None:
                self._rebuild()

    def disable_threat(self) -> None:
        """Back to the exact pre-threat compiled program."""
        with self._lock:
            if self._threat is None:
                return
            self._threat = None
            self.threat_state = None
            self.last_threat = None
            if self._step is not None:
                self._rebuild()

    def set_threat_config(self, config) -> None:
        """Swap the policy-controlled threshold/mode vector (a
        threat/model.ThreatConfig): ONE region write into the live
        threat-model group buffer — a shadow<->enforce flip or a
        threshold change never repacks and never re-jits."""
        with self._lock:
            if self._threat is None:
                raise RuntimeError("threat scoring not enabled")
            self._threat = self._threat.with_config(config)
            cfg = jnp.asarray(self._threat.config.encode())
            if self._tables is not None:
                self._tables = self._tables._replace(tm_cfg=cfg)
                if self._tables6 is not None:
                    self._tables6 = self._tables6._replace(tm_cfg=cfg)
                self._write_leaf_locked("tm_cfg", cfg)

    def apply_threat_weights(self, model) -> bool:
        """Hot-swap the scorer weights (a trained ThreatModel):
        same-geometry pushes are region writes into the threat-model
        group — zero repacks, no serving pause (the delta-apply
        write-through path).  A geometry change (different hidden
        width) rebuilds.  Returns True when the fast path applied."""
        with self._lock:
            if self._threat is None:
                raise RuntimeError("threat scoring not enabled")
            fast = model.geometry == self._threat.geometry and \
                self._tables is not None
            self._threat = model
            if not fast:
                if self._step is not None:
                    self._rebuild()
                return False
            leaves = {k: jnp.asarray(v)
                      for k, v in model.tables().items()}
            self._tables = self._tables._replace(**leaves)
            if self._tables6 is not None:
                self._tables6 = self._tables6._replace(**leaves)
            for path, arr in leaves.items():
                self._write_leaf_locked(path, arr)
            return True

    def threat_report(self) -> Optional[Dict]:
        """Model + state report (status surfaces; None = disabled)."""
        with self._lock:
            model = self._threat
            state = self.threat_state
            buckets = self._threat_buckets
            window_s = self._threat_window_s
        if model is None:
            return None
        out = dict(model.describe())
        out.update({"buckets": buckets, "window-s": window_s,
                    "shard": self.shard_index})
        if state is not None:
            from ..threat.stage import COL_WIN_TS
            st = np.asarray(state.state)
            out["active-buckets"] = int(
                (st[:-1, COL_WIN_TS] != 0).sum())
        return out

    # -- device-resident traffic analytics (analytics/) ----------------------

    def enable_analytics(self, width: int = 1 << 12, depth: int = 2,
                         lanes: int = 4, stripe: int = 16) -> None:
        """Turn on the fused traffic-analytics stage: both family
        steps fold every batch's final verdicts into the shard-local
        AnalyticsState buffer (count-min heavy-hitter sketches,
        candidate key tables, distinct-flow cardinality registers —
        analytics/stage.py).  ``width`` is the per-row column count
        (power of 2); ``stripe`` the 1-in-N update sampling.  The
        fused cost is scatter-element-bound and scales with the
        sampled fraction, so ``stripe`` IS the overhead budget: the
        1-in-16 default holds the fused step within the serving
        overhead gate (bench ``analytics-overhead``); stripe=1 folds
        every row when exactness beats throughput."""
        from ..analytics.stage import make_analytics_state
        with self._lock:
            self._analytics_on = True
            self._analytics_width = width
            self._analytics_depth = depth
            self._analytics_lanes = lanes
            self._analytics_stripe = stripe
            self.analytics_state = make_analytics_state(width, depth,
                                                        lanes)
            if self._replicated_sharding is not None:
                self.analytics_state = jax.device_put(
                    self.analytics_state, self._replicated_sharding)
            if self._step is not None:
                self._rebuild()

    def disable_analytics(self) -> None:
        """Back to the exact pre-analytics compiled program."""
        with self._lock:
            if not self._analytics_on:
                return
            self._analytics_on = False
            self.analytics_state = None
            if self._step is not None:
                self._rebuild()

    def swap_analytics_epoch(self) -> int:
        """Flip the A/B epoch: zero the section about to be written,
        then name it in the control cell.  The fused stage reads the
        cell dynamically, so the flip is a state swap under the engine
        lock — never a re-jit, never a serving pause.  Returns the
        newly quiesced epoch index (what decode should read)."""
        from ..analytics.stage import CTRL_COL, ctrl_row, epoch_rows
        with self._lock:
            if self.analytics_state is None:
                raise RuntimeError("analytics not enabled")
            depth = self._analytics_depth
            lanes = self._analytics_lanes
            st = self.analytics_state.state
            er = epoch_rows(depth, lanes)
            cr = ctrl_row(depth, lanes)
            cur = int(np.array(st[cr, CTRL_COL]))
            nxt = 1 - cur
            st = st.at[nxt * er:(nxt + 1) * er, :].set(jnp.int32(0))
            st = st.at[cr, CTRL_COL].set(jnp.int32(nxt))
            if self._replicated_sharding is not None:
                st = jax.device_put(st, self._replicated_sharding)
            self.analytics_state = \
                self.analytics_state._replace(state=st)
            return cur

    def analytics_snapshot(self) -> Optional[np.ndarray]:
        """Host copy of the full analytics buffer (None = disabled).
        The decode layer (analytics/decode.py) reads the quiesced
        epoch section of this snapshot; a drain cycle is
        swap_analytics_epoch() followed by one snapshot."""
        with self._lock:
            st = self.analytics_state
        if st is None:
            return None
        return np.array(st.state)

    def analytics_report(self) -> Optional[Dict]:
        """Geometry + epoch report (status surfaces; None =
        disabled)."""
        from ..analytics.stage import CTRL_COL, ctrl_row
        with self._lock:
            if not self._analytics_on:
                return None
            depth = self._analytics_depth
            lanes = self._analytics_lanes
            out = {"width": self._analytics_width, "depth": depth,
                   "lanes": lanes, "stripe": self._analytics_stripe,
                   "shard": self.shard_index}
            st = self.analytics_state
        # a lost device buffer degrades the report, never crashes it
        # (the sharded merge keeps reporting the healthy shards)
        out["write-epoch"] = None if st is None else int(np.array(
            st.state[ctrl_row(depth, lanes), CTRL_COL]))
        return out

    def l7_fast_window(self) -> int:
        """The payload window W callers must encode to (0 = fast
        verdicts disabled; payloads are ignored then).  Read per
        serving launch — lock-free on purpose (the reference is
        swapped atomically by enable/disable; a racy read costs one
        absent-payload batch, never a wrong verdict)."""
        progs = self._l7_fast
        return 0 if progs is None else progs.window

    def l7_fast_protocol_of(self):
        """Slot -> protocol tag decoder for the l7_fast_verdicts_total
        metric (monitor.MonitorHub.ingest_batch l7_proto_of): maps a
        provenance match slot to the decided program's protocol via
        the live value tensor (the slot's proxy port)."""
        with self._lock:
            progs = self._l7_fast
        if progs is None:
            return None
        decode = self.rule_decoder()

        def proto_of(slot) -> str:
            entry = decode(slot)
            if entry is None:
                return ""
            return progs.protocol_of_port(entry.get("proxy-port", 0))
        return proto_of

    def flow_snapshot(self, max_entries: int = 4096):
        """Decoded per-flow aggregates ([] when disabled).  Snapshot
        refs are taken under the lock; decode happens lock-free on the
        immutable arrays (map_dump convention)."""
        with self._lock:
            flows = self.flows
        return [] if flows is None else flows.snapshot(max_entries)

    def flow_stats(self):
        with self._lock:
            flows = self.flows
            claim_every = getattr(self, "_flow_claim_every", 1)
        if flows is None:
            return None
        return {**flows.stats(), "claim-every": claim_every}

    def set_router_ip6(self, ip: str) -> None:
        """Program the v6 router address the ICMPv6/NDP responder
        stage answers for (datapath init writes ROUTER_IP into the
        generated header; bpf/lib/icmp6.h reads it back)."""
        from ..compiler.lpm import ipv6_to_words
        with self._lock:
            # words are unsigned u32; the device tables carry them as
            # bit-identical int32 (same convention as addr6 batches)
            self._router_ip6 = jnp.asarray(
                np.asarray(ipv6_to_words(ip), np.uint32)
                .view(np.int32))
            if self._tables6 is not None:
                self._tables6 = self._tables6._replace(
                    router_ip6=self._router_ip6)
                self._write_leaf_locked("router_ip6", self._router_ip6,
                                        families=("6",))

    def icmp6_echo_reply_bytes(self, requester_ip6: str,
                               ident: int = 0, seq: int = 0) -> bytes:
        """The responder's wire output for an answered echo
        (icmp6.h __icmp6_send_echo_reply): the reply is built from
        THIS datapath's programmed router address — the consumer can
        verify the answer really came from the address it probed."""
        from .icmp6 import echo_reply
        from ..compiler.lpm import ipv6_to_words
        with self._lock:
            if self._router_ip6 is None:
                raise RuntimeError("router ip6 not programmed")
            words = [int(w) for w in
                     np.asarray(self._router_ip6).view(np.uint32)]
        return echo_reply(words, ipv6_to_words(requester_ip6),
                          ident=ident, seq=seq)

    def set_mesh_placement(self, submesh, shard: Optional[int] = None,
                           lane: Optional[str] = None) -> None:
        """Pin this engine's device state to a (dp, ep=1) submesh — one
        shard column of the dataplane mesh (parallel/mesh.ep_submesh).

        Tables/CT/counters/flows are device_put replicated across the
        column's dp devices; packed serving batches are sharded across
        dp (pjit follows the committed input shardings), so the shard's
        compiled program spans exactly its own devices — its fault
        domain.  Must be called before tables are loaded or it re-jits.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import DP_AXIS
        with self._lock:
            self._placement = submesh
            self._batch_sharding = NamedSharding(submesh,
                                                 P(None, DP_AXIS))
            self._replicated_sharding = NamedSharding(submesh, P())
            self.shard_index = shard
            if lane is not None:
                self._serving_lane_name = lane
            elif shard is not None:
                self._serving_lane_name = f"verdict-s{shard}"
            self._place_state_locked()
            if self._step is not None:
                self._rebuild()

    def _place_state_locked(self) -> None:
        """device_put the mutable per-shard state (CT, flows) onto the
        placement submesh (lock held).  Async transfers; donation keeps
        subsequent step outputs resident there."""
        rep = self._replicated_sharding
        if rep is None:
            return
        self.ct.state = jax.device_put(self.ct.state, rep)
        self.ct6.state = jax.device_put(self.ct6.state, rep)
        if self.flows is not None:
            self.flows.state = jax.device_put(self.flows.state, rep)
        if self._counters is not None:
            self._counters = jax.device_put(self._counters, rep)
        if self.threat_state is not None:
            self.threat_state = jax.device_put(self.threat_state, rep)
        if self.analytics_state is not None:
            self.analytics_state = jax.device_put(self.analytics_state,
                                                  rep)

    # -- table loading -------------------------------------------------------

    def load_policy(self, map_states: Sequence[PolicyMapState],
                    revision: int,
                    ipcache_prefixes: Optional[Dict[str, int]] = None
                    ) -> None:
        with self._lock:
            self._table_mgr = None
            # host-of-record for the fail-static oracle: slot i serves
            # map_states[i] (the exact states the tables compile from)
            self._host_states = list(map_states)
            self.compiled_policy = compile_endpoints(map_states,
                                                     revision=revision)
            if ipcache_prefixes is not None or \
                    self.compiled_ipcache is None:
                # keep the host mirror in lockstep with the compiled
                # LPM (map_dump + the fail-static oracle read it)
                self.ipcache_prefixes = dict(ipcache_prefixes or {})
                self.compiled_ipcache = compile_lpm(ipcache_prefixes or {})
            self.revision = revision
            self._rebuild()

    def use_table_manager(self, mgr,
                          ipcache_prefixes: Optional[Dict[str, int]]
                          = None) -> None:
        """Switch policy tensors to a DeviceTableManager (incremental
        mode): per-endpoint syncs become row writes realized by
        refresh_policy(); only geometry changes (capacity/slot growth,
        longer probe chains) re-jit the step."""
        with self._lock:
            self._table_mgr = mgr
            if ipcache_prefixes is not None or \
                    self.compiled_ipcache is None:
                self.ipcache_prefixes = dict(ipcache_prefixes or {})
                self.compiled_ipcache = compile_lpm(ipcache_prefixes or {})
            self._rebuild()

    def refresh_policy(self, revision: Optional[int] = None,
                       force_rebuild: bool = False) -> bool:
        """Realize the table manager's current tensors (the syncPolicyMap
        fast path: no recompile when geometry is unchanged). Returns
        True when a full re-jit happened.  On the fast path the
        manager's dirty rows are written through to the packed dispatch
        buffers (row scatters — a single-rule delta never repacks the
        table stack).  ``force_rebuild`` forces the full rebuild +
        repack (the supervisor's recovery path: corrupted device
        buffers must be rebuilt from the host-of-record even when
        geometry is unchanged)."""
        with self._lock:
            if self._table_mgr is None:
                raise RuntimeError("not in table-manager mode")
            if revision is not None:
                self.revision = max(self.revision, revision)
            # one atomic (geometry, tensors) snapshot: a concurrent
            # sync_endpoint can lengthen probe chains in-place and a
            # grow can reshape the stack between separate reads
            geometry, tensors = self._table_mgr.snapshot()
            if force_rebuild or geometry != self._mgr_geometry \
                    or self._step is None:
                self._rebuild(mgr_snapshot=(geometry, tensors))
                return True
            key_id, key_meta, value = tensors
            if self._placement is not None:
                key_id, key_meta, value = jax.device_put(
                    (key_id, key_meta, value),
                    self._replicated_sharding)
            dp = self._tables.datapath._replace(
                key_id=key_id, key_meta=key_meta, value=value)
            self._tables = self._tables._replace(datapath=dp)
            if self._tables6 is not None:
                self._tables6 = self._tables6._replace(
                    key_id=key_id, key_meta=key_meta, value=value)
            self._apply_dirty_rows_locked()
            return False

    def _apply_dirty_rows_locked(self) -> None:
        """Delta-apply write-through: scatter the table manager's dirty
        endpoint rows into the packed policy slices of BOTH family
        packs (v6 shares the policy tensors).  Lock held."""
        mgr = self._table_mgr
        if mgr is None or self._tbufs4 is None:
            return
        dirty = mgr.drain_dirty()
        if not dirty:
            return
        telem = self.telemetry_enabled
        t0 = time.perf_counter() if telem else 0.0
        slots = jnp.asarray(np.fromiter(dirty, np.int32,
                                        count=len(dirty)))
        kid = jnp.asarray(np.stack([r[0] for r in dirty.values()]))
        kmeta = jnp.asarray(np.stack([r[1] for r in dirty.values()]))
        kval_np = np.stack([r[2] for r in dirty.values()])
        kval = jnp.asarray(kval_np)
        for attr, rw in (("_tbufs4", self._rw4), ("_tbufs6", self._rw6)):
            bufs = getattr(self, attr)
            if bufs is None or rw is None:
                continue
            writer, gidx = rw
            out = list(bufs)
            out[gidx] = writer(out[gidx], slots, kid, kmeta, kval)
            setattr(self, attr, tuple(out))
        if self._l7_fast is not None:
            # L7 classification write-through: the dirty rows' proxy
            # ports re-derive their per-slot program ids, scattered
            # into both family packs (and the unpacked tables view the
            # replay surface reads) — an L7 rule change on the fast
            # path stays a row write, never a repack
            l7rows = jnp.asarray(
                self._l7_fast.progs_for_values(kval_np))
            for attr, rw in (("_tbufs4", self._l7_rw4),
                             ("_tbufs6", self._l7_rw6)):
                bufs = getattr(self, attr)
                if bufs is None or rw is None:
                    continue
                writer, gidx = rw
                out = list(bufs)
                out[gidx] = writer(out[gidx], slots, l7rows)
                setattr(self, attr, tuple(out))
            if self._tables is not None and \
                    self._tables.l7_prog is not None:
                lp = self._tables.l7_prog.at[slots].set(l7rows)
                self._tables = self._tables._replace(l7_prog=lp)
                if self._tables6 is not None:
                    self._tables6 = self._tables6._replace(l7_prog=lp)
        self._pack_stats["row-writes"] += len(dirty)
        if telem:
            record_stage("engine", "flatten",
                         time.perf_counter() - t0)

    def load_ipcache(self, prefixes: Dict[str, int],
                     prefixes6: Optional[Dict[str, int]] = None) -> None:
        with self._lock:
            self.ipcache_prefixes = dict(prefixes)
            self.compiled_ipcache = compile_lpm(prefixes)
            if prefixes6 is not None:
                self.ipcache_prefixes6 = dict(prefixes6)
                self.compiled_ipcache6 = compile_lpm6(prefixes6)
            self._rebuild()

    def load_ipcache6(self, prefixes6: Dict[str, int]) -> None:
        with self._lock:
            self.ipcache_prefixes6 = dict(prefixes6)
            self.compiled_ipcache6 = compile_lpm6(prefixes6)
            self._rebuild()

    def upsert_service6(self, svc: Service6) -> None:
        """Program a v6 service (lb6 family).  rev_nat_index stability
        matches the v4 LoadBalancer: replacing a service keeps its
        index so live CT entries keep resolving the same VIP."""
        key = (tuple(svc.vip), svc.port, svc.proto)
        with self._lock:
            old = self.lb6_services.get(key)
            if svc.rev_nat_index <= 0:
                if old is not None:
                    svc.rev_nat_index = old.rev_nat_index
                else:
                    svc.rev_nat_index = self._lb6_next_rev
            self._lb6_next_rev = max(self._lb6_next_rev,
                                     svc.rev_nat_index + 1)
            self.lb6_services[key] = svc
            self.compiled_lb6 = compile_lb6(
                list(self.lb6_services.values()))
            self._rebuild()

    def delete_service6(self, vip: tuple, port: int,
                        proto: int = 6) -> bool:
        with self._lock:
            if self.lb6_services.pop((tuple(vip), port, proto),
                                     None) is None:
                return False
            self.compiled_lb6 = compile_lb6(
                list(self.lb6_services.values())) \
                if self.lb6_services else None
            self._rebuild()
            return True

    def load_tunnel(self, prefixes: Dict[str, int]) -> None:
        """Program the tunnel map: pod CIDR -> tunnel endpoint node IP
        (u32).  Reference: pkg/maps/tunnel SetTunnelEndpoint, consumed
        by encap.h encap_and_redirect."""
        # node IPs above 2^31 must be stored as their int32
        # bit-pattern (the LPM value lanes are int32)
        normalized = {cidr: int(np.uint32(ip).view(np.int32))
                      for cidr, ip in prefixes.items()}
        with self._lock:
            if normalized == self.tunnel_prefixes:
                return  # idempotent node refresh: skip the re-jit
            self.tunnel_prefixes = normalized
            self.compiled_tunnel = compile_lpm(self.tunnel_prefixes) \
                if self.tunnel_prefixes else None
            self._rebuild()

    def set_endpoint_identity(self, slot: int, identity: int) -> None:
        """Record a local endpoint slot's own security identity (the
        compile-time SECLABEL of the reference's per-endpoint program);
        the encap stage stamps it into the tunnel key."""
        with self._lock:
            if slot >= self._ep_identity.shape[0]:
                grown = np.zeros(max(slot + 1,
                                     2 * self._ep_identity.shape[0]),
                                 np.int32)
                grown[:self._ep_identity.shape[0]] = self._ep_identity
                self._ep_identity = grown
            self._ep_identity[slot] = identity
            ep_ident = jnp.asarray(self._ep_identity)
            if self._tables is not None:
                self._tables = self._tables._replace(
                    ep_identity=ep_ident)
            if self._tables6 is not None:
                self._tables6 = self._tables6._replace(
                    ep_identity=ep_ident)
            self._write_leaf_locked("ep_identity", ep_ident)

    def _write_leaf_locked(self, path: str, arr,
                           families: Tuple[str, ...] = ("4", "6")
                           ) -> None:
        """Write one table leaf through to the packed dispatch buffers
        (region writes; lock held).  A shape change — or the leaf being
        absent from a target family's manifest (it just came into
        existence) — means the packing manifest and therefore the
        jitted program changed: full rebuild."""
        if self._tbufs4 is None:
            return
        from ..parallel import packing
        updates = {}
        for fam in families:
            manifest = self._manifest4 if fam == "4" else self._manifest6
            bufs = self._tbufs4 if fam == "4" else self._tbufs6
            if manifest is None or bufs is None:
                continue
            new = packing.write_leaf(manifest, bufs, path, arr)
            if new is None:
                self._rebuild()  # manifest change: re-pack + re-jit
                return
            updates[fam] = new
        if "4" in updates:
            self._tbufs4 = updates["4"]
        if "6" in updates:
            self._tbufs6 = updates["6"]
        if updates:
            self._pack_stats["leaf-writes"] += 1

    def reload_services(self) -> None:
        with self._lock:
            self._rebuild()

    def reload_prefilter(self) -> None:
        with self._lock:
            self._rebuild()

    def _rebuild(self, mgr_snapshot=None) -> None:
        if self._table_mgr is None and self.compiled_policy is None:
            return
        t0 = time.perf_counter() if self.telemetry_enabled else 0.0
        self._rebuild_body(mgr_snapshot)
        if self.telemetry_enabled:
            record_stage("engine", "table-build",
                         time.perf_counter() - t0)
            nbytes = 0
            for tables in (self._tables, self._tables6,
                           self._tbufs4, self._tbufs6):
                for leaf in jax.tree_util.tree_leaves(tables):
                    nbytes += int(getattr(leaf, "nbytes", 0))
            jit_telemetry.set_device_bytes("engine-tables", nbytes)

    def _rebuild_body(self, mgr_snapshot=None) -> None:
        if self.lb.compiled is None:
            self.lb._recompile()
        if self._table_mgr is not None:
            if mgr_snapshot is None:
                mgr_snapshot = self._table_mgr.snapshot()
            geometry, (key_id, key_meta, value) = mgr_snapshot
            capacity, slots, max_probe, _gen = geometry
            if self.compiled_ipcache is None:
                self.compiled_ipcache = compile_lpm({})
            lpm = self.compiled_ipcache
            dp = DatapathTables(
                key_id=key_id, key_meta=key_meta, value=value,
                lpm_masks=jnp.asarray(lpm.masks),
                lpm_key_a=jnp.asarray(lpm.key_a),
                lpm_key_b=jnp.asarray(lpm.key_b),
                lpm_value=jnp.asarray(lpm.value),
                lpm_plens=jnp.asarray(lpm.prefix_lens))
            policy_probe = max(1, max_probe)
            n = max(1, capacity * slots)
            self._mgr_geometry = geometry
        else:
            dp = build_tables(self.compiled_policy, self.compiled_ipcache)
            policy_probe = self.compiled_policy.max_probe
            n = max(1, self.compiled_policy.num_endpoints *
                    self.compiled_policy.slots)
        pf = self.prefilter._compiled
        if pf is None or pf.entry_count() == 0:
            pf = compile_lpm({})
        tun = self.compiled_tunnel
        tun_kwargs = {}
        tun_probe = 0
        if tun is not None and tun.entry_count() > 0:
            tun_probe = max(1, tun.max_probe)
            tun_kwargs = dict(
                tun_masks=jnp.asarray(tun.masks),
                tun_key_a=jnp.asarray(tun.key_a),
                tun_key_b=jnp.asarray(tun.key_b),
                tun_value=jnp.asarray(tun.value),
                tun_plens=jnp.asarray(tun.prefix_lens))
        # the slot->identity table serves both the encap stage and the
        # flow-aggregation key, so it is always device-resident
        ep_ident = jnp.asarray(self._ep_identity)
        # L7 fast-verdict tables (l7/fast.py): the per-slot program
        # classification derives from the live value tensor (slot
        # proxy port -> program id), so it recompiles with every
        # table generation; omitted entirely when fast verdicts are
        # off, keeping the no-L7 program byte-identical
        l7_kwargs = {}
        l7_static = {}
        if self._l7_fast is not None:
            progs = self._l7_fast
            vals_np = np.asarray(dp.value)
            l7_kwargs = dict(
                l7_prog=jnp.asarray(progs.progs_for_values(vals_np)),
                l7_flat=jnp.asarray(progs.flat),
                l7_map=jnp.asarray(progs.cmap),
                l7_accept=jnp.asarray(progs.accept),
                l7_starts=jnp.asarray(progs.starts),
                l7_pmask=jnp.asarray(progs.pmask))
            l7_static = dict(with_l7_fast=1, l7_k=progs.k,
                             l7_c1=progs.c1)
        # inline threat scoring: the quantized model leaves join both
        # family tables (their own threat-model pack group); omitted
        # entirely when disabled so the pre-threat program stays
        # byte-identical
        threat_kwargs = {}
        threat_static = {}
        if self._threat is not None:
            threat_kwargs = {k: jnp.asarray(v)
                             for k, v in self._threat.tables().items()}
            threat_static = dict(with_threat=1,
                                 threat_window_s=self._threat_window_s,
                                 threat_stripe=self._threat_stripe)
            if self.threat_state is None:
                from ..threat.stage import make_threat_state
                self.threat_state = make_threat_state(
                    self._threat_buckets)
        # fused traffic analytics: a pure engine-owned state buffer
        # like the threat state — no table leaves join the pack;
        # omitted entirely when disabled so the pre-analytics program
        # stays byte-identical
        analytics_static = {}
        if self._analytics_on:
            analytics_static = dict(
                with_analytics=1,
                analytics_depth=self._analytics_depth,
                analytics_lanes=self._analytics_lanes,
                analytics_stripe=self._analytics_stripe)
            if self.analytics_state is None:
                from ..analytics.stage import make_analytics_state
                self.analytics_state = make_analytics_state(
                    self._analytics_width, self._analytics_depth,
                    self._analytics_lanes)
        self._tables = FullTables(
            datapath=dp, lb=self.lb.compiled.tables,
            pf_masks=jnp.asarray(pf.masks), pf_key_a=jnp.asarray(pf.key_a),
            pf_key_b=jnp.asarray(pf.key_b), pf_value=jnp.asarray(pf.value),
            pf_plens=jnp.asarray(pf.prefix_lens),
            ep_identity=ep_ident, **tun_kwargs, **l7_kwargs,
            **threat_kwargs)
        if self._counters is None or self._counters.shape[1] != n:
            self._counters = make_counter_pack(n)
        flow_kwargs = {}
        if self.flows is not None:
            flow_kwargs = dict(flow_slots=self.flows.slots,
                               flow_probe=self.flows.max_probe)
            # the flows arg is deliberately NOT donated: donation of
            # the scatter-updated flow buffers measurably degrades the
            # whole fused program on the CPU backend (XLA copies the
            # donated buffers out of line), and the table is ~1MB —
            # double-buffering it costs nothing
        # omitting the kwarg entirely when provenance is off keeps the
        # disabled partial byte-identical to the pre-provenance one
        if self.provenance_enabled:
            flow_kwargs = dict(flow_kwargs, with_provenance=1)
        # replay runs verdict_explain over the live policy tensors;
        # it needs the same probe depth the hot path compiled with
        self._replay_probe = policy_probe
        self._prov_decode_cache = None
        v4_static = dict(
            policy_probe=policy_probe,
            lpm_probe=max(1, self.compiled_ipcache.max_probe),
            pf_probe=max(1, pf.max_probe),
            lb_probe=self.lb.compiled.max_probe,
            ct_slots=self.ct.slots, ct_probe=self.ct.max_probe,
            tun_probe=tun_probe)
        self._statics4 = {**v4_static, **flow_kwargs, **l7_static,
                          **threat_static, **analytics_static}

        # v6 twin: shares the (family-agnostic) policy tensors, runs
        # the 4-word LPMs for prefilter/ipcache and its own CT table.
        ipc6 = self.compiled_ipcache6 if self.compiled_ipcache6 \
            is not None else compile_lpm6({})
        pf6 = self.prefilter._compiled6
        if pf6 is None or pf6.entry_count() == 0:
            pf6 = compile_lpm6({})
        lb6 = self.compiled_lb6
        self._tables6 = FullTables6(
            key_id=dp.key_id, key_meta=dp.key_meta, value=dp.value,
            ipcache6=lpm6_tables(ipc6), pf6=lpm6_tables(pf6),
            lb6=lb6.tables if lb6 is not None else None,
            router_ip6=self._router_ip6, ep_identity=ep_ident,
            **l7_kwargs, **threat_kwargs)
        v6_static = dict(
            policy_probe=policy_probe,
            lpm6_probe=max(1, ipc6.max_probe),
            pf6_probe=max(1, pf6.max_probe),
            ct_slots=self.ct6.slots, ct_probe=self.ct6.max_probe,
            lb6_probe=lb6.max_probe if lb6 is not None else 0)
        self._statics6 = {**v6_static, **flow_kwargs, **l7_static,
                          **threat_static, **analytics_static}

        # mesh placement: commit every table onto this shard's column
        # submesh so the jitted steps compile as submesh-resident SPMD
        # programs (the batch axis shards across dp at dispatch time)
        if self._placement is not None:
            rep = self._replicated_sharding
            self._tables = jax.device_put(self._tables, rep)
            self._tables6 = jax.device_put(self._tables6, rep)
            self._counters = jax.device_put(self._counters, rep)
            if self.threat_state is not None:
                self.threat_state = jax.device_put(self.threat_state,
                                                   rep)
            if self.analytics_state is not None:
                self.analytics_state = jax.device_put(
                    self.analytics_state, rep)

        # pack the table leaf zoo into the grouped dispatch buffers
        # (the dispatch-floor fix): every jitted step below takes the
        # handful of flat buffers instead of the ~30-leaf pytree, with
        # the per-leaf views rebuilt INSIDE the compiled program
        self._refresh_packs_locked()

        def grouped(step_fn, unpack, statics):
            def g(tbufs, ct, counters, batch, now, flows=None,
                  payload=None, threat=None, analytics=None):
                tables = unpack(tbufs)
                if flows is None and payload is None and \
                        threat is None and analytics is None:
                    return step_fn(tables, ct, counters, batch, now,
                                   **statics)
                return step_fn(tables, ct, counters, batch, now,
                               flows, payload, threat, analytics,
                               **statics)
            # the program (and its compile events) carry the step's name
            g.__name__ = g.__qualname__ = step_fn.__name__
            return jax.jit(g, donate_argnums=(1, 2))

        from ..parallel import packing
        unpack4 = packing.unpacker(self._manifest4)
        unpack6 = packing.unpacker(self._manifest6)
        nc4 = dict(self._statics4, flow_claim_budget=0)
        nc6 = dict(self._statics6, flow_claim_budget=0)
        self._step = grouped(full_datapath_step, unpack4,
                             self._statics4)
        # the claim-free (admission-striped) variants; compiled lazily
        # on first use like every jitted step
        self._step_nc = None if self.flows is None else grouped(
            full_datapath_step, unpack4, nc4)
        # the serving path's packed twins: same program over a single
        # [10, B] field matrix (one H2D per batch instead of ten)
        self._step_packed = grouped(full_datapath_step_packed, unpack4,
                                    self._statics4)
        self._step_packed_nc = None if self.flows is None else grouped(
            full_datapath_step_packed, unpack4, nc4)
        self._step6 = grouped(full_datapath_step6, unpack6,
                              self._statics6)
        self._step6_nc = None if self.flows is None else grouped(
            full_datapath_step6, unpack6, nc6)

    def _refresh_packs_locked(self) -> None:
        """(Re)build the packed dispatch buffers from the live tables
        (lock held): manifest from the canonical PartitionSpec registry,
        one device concat per group.  Paid per table generation — never
        per batch; the per-batch flatten cost this kills is recorded
        here as the non-blocking ``flatten`` stage."""
        from ..parallel import packing
        telem = self.telemetry_enabled
        t0 = time.perf_counter() if telem else 0.0
        self._manifest4 = packing.build_manifest(self._tables)
        self._manifest6 = packing.build_manifest(self._tables6)
        bufs4 = packing.pack_groups(self._tables, self._manifest4)
        bufs6 = packing.pack_groups(self._tables6, self._manifest6)
        if self._placement is not None:
            rep = self._replicated_sharding
            bufs4 = tuple(jax.device_put(b, rep) for b in bufs4)
            bufs6 = tuple(jax.device_put(b, rep) for b in bufs6)
        self._tbufs4, self._tbufs6 = bufs4, bufs6
        self._rw4 = packing.make_policy_row_writer(self._manifest4)
        self._rw6 = packing.make_policy_row_writer(self._manifest6)
        self._l7_rw4 = packing.make_l7_prog_row_writer(self._manifest4)
        self._l7_rw6 = packing.make_l7_prog_row_writer(self._manifest6)
        self._pack_stats["full-packs"] += 1
        if telem:
            record_stage("engine", "flatten",
                         time.perf_counter() - t0)

    def pack_stats(self) -> Dict:
        """Packing accounting: full group repacks vs delta row/leaf
        write-throughs, plus the group layout."""
        with self._lock:
            out = dict(self._pack_stats)
            if self._manifest4 is not None:
                out["groups4"] = list(self._manifest4.group_names())
                out["groups6"] = list(self._manifest6.group_names())
        return out

    def dispatch_leaf_counts(self) -> Dict[str, int]:
        """Flattened jitted-step argument leaf counts: what the packed
        dispatch actually marshals per batch vs what the legacy pytree
        form would — the sharding lint pins the ceiling so new leaves
        can't silently regrow the dispatch floor."""
        from jax.tree_util import tree_leaves
        with self._lock:
            if self._step_packed is None:
                raise RuntimeError("no policy loaded")
            flows = () if self.flows is None else (self.flows.state,)
            payload = () if self._l7_fast is None else (
                np.zeros((1, self._l7_fast.window), np.int32),)
            threat = () if self._threat is None else \
                (self.threat_state,)
            analytics = () if not self._analytics_on else \
                (self.analytics_state,)
            packed_args = (self._tbufs4, self.ct.state, self._counters,
                           np.zeros((10, 1), np.int32), 0) + flows \
                + payload + threat + analytics
            n_packed = len(tree_leaves(packed_args))
            # v6 keeps the per-field packet batch (10 leaves) but the
            # same grouped tables/state
            n_v6 = (len(tree_leaves((self._tbufs6, self.ct6.state,
                                     self._counters))) + 10 + 1
                    + len(tree_leaves(flows))
                    + len(tree_leaves(payload))
                    + len(tree_leaves(threat))
                    + len(tree_leaves(analytics)))
            # the legacy-pytree equivalent: raw table leaves + per-leaf
            # CT state + per-leaf counters + batch + timestamp
            n_legacy = (len(tree_leaves(self._tables)) + 8 + 2 + 1 + 1
                        + len(tree_leaves(flows))
                        + len(tree_leaves(payload))
                        + len(tree_leaves(threat))
                        + len(tree_leaves(analytics)))
            return {"packed-step": n_packed,
                    "v6-step": n_v6,
                    "legacy-step": n_legacy,
                    "reduction": round(n_legacy / n_packed, 2)}

    def _lower_args_packed(self, packed, now: int = 1):
        """The exact argument tuple ``_step_packed`` dispatches —
        the jit-lowering/introspection surface for tests.  A flows-on
        engine's step takes the flow table, an L7-enabled one the
        payload lane too (absent matrix stands in, as for payload-less
        dispatch)."""
        args = (self._tbufs4, self.ct.state, self._counters, packed,
                jnp.int32(now))
        flows = None if self.flows is None else self.flows.state
        pl = None
        if self._l7_fast is not None:
            pl = jnp.asarray(
                self._payload_in(None, int(packed.shape[1])))
        if self._analytics_on:
            return args + (flows, pl, self.threat_state,
                           self.analytics_state)
        if self._threat is not None:
            return args + (flows, pl, self.threat_state)
        if pl is not None:
            return args + (flows, pl)
        if flows is not None:
            return args + (flows,)
        return args

    # -- the hot path --------------------------------------------------------

    def _flow_step_variant(self, step, step_nc):
        """Claim-admission striping: every ``claim_every``-th batch
        runs the claiming step; the rest run the statically claim-free
        variant (callers hold the engine lock)."""
        tick = self._flow_tick
        self._flow_tick = tick + 1
        if tick % self._flow_claim_every == 0:
            return step
        return step_nc

    def _timestamp(self, now: Optional[int]):
        """Device scalar for the batch timestamp, cached per value:
        wall-clock `now` changes once a second, so steady-state
        dispatch reuses one device scalar instead of paying a fresh
        H2D transfer (and allocation) per batch."""
        val = int(now if now is not None else time.time())
        cache = self._ts_cache
        if cache is not None and cache[0] == val:
            return cache[1]
        ts = jnp.int32(val)
        self._ts_cache = (val, ts)
        return ts

    def _payload_in(self, payload, rows: int):
        """The payload lane for one dispatch (lock held): the caller's
        [rows, W] block when L7 fast verdicts are on, a cached
        all-(-1) absent matrix when the caller carried none (absent =
        not decidable = redirect, the exact pre-fast verdicts), and
        None when the fast stage is disabled (the payload is never
        traced, keeping the compiled program byte-identical)."""
        if self._l7_fast is None:
            return None
        if payload is not None:
            return payload
        cached = self._absent_payloads.get(rows)
        if cached is None:
            cached = np.full((rows, self._l7_fast.window), -1, np.int32)
            self._absent_payloads[rows] = cached
        return cached

    def _dispatch_locked(self, step, tbufs, ct_state, batch, ts,
                         flows_in, payload, threat=None,
                         analytics=None):
        """One jitted-step call with the optional flows/payload/threat/
        analytics lanes threaded positionally (lock held).  Call shapes
        stay stable per configuration, so the jit cache sees one
        entry."""
        if analytics is not None:
            return step(tbufs, ct_state, self._counters, batch, ts,
                        flows_in, payload, threat, analytics)
        if threat is not None:
            return step(tbufs, ct_state, self._counters, batch, ts,
                        flows_in, payload, threat)
        if payload is not None:
            return step(tbufs, ct_state, self._counters, batch, ts,
                        flows_in, payload)
        if flows_in is not None:
            return step(tbufs, ct_state, self._counters, batch, ts,
                        flows_in)
        return step(tbufs, ct_state, self._counters, batch, ts)

    def process(self, pkt: FullPacketBatch, now: Optional[int] = None,
                payload=None):
        """Classify a batch. Returns (verdict, event, identity, nat) —
        nat carries the DNAT'd forward tuple and rev-NAT'd reply tuple.

        Dispatch is asynchronous: the returned arrays are in-flight
        device values; nothing here blocks on device compute, and the
        engine lock covers ONLY the dispatch + state swap (timestamp
        upload happens before it, telemetry accounting after).

        ``payload`` is the optional [B, W] L7 payload lane (int32
        match-string bytes, l7/fast.encode_payloads) consumed by the
        fast-verdict stage when enabled; ignored otherwise."""
        telem = self.telemetry_enabled
        t0 = time.perf_counter() if telem else 0.0
        ts = self._timestamp(now)
        with self._lock:
            if self._step is None:
                raise RuntimeError("no policy loaded")
            t_lock = time.perf_counter() if telem else 0.0
            pl = self._payload_in(payload, int(pkt.endpoint.shape[0]))
            if self.flows is not None:
                step = self._flow_step_variant(self._step,
                                               self._step_nc)
                flows_in = self.flows.state
            else:
                step = self._step
                flows_in = None
            outs = self._dispatch_locked(step, self._tbufs4,
                                         self.ct.state, pkt, ts,
                                         flows_in, pl,
                                         self.threat_state,
                                         self.analytics_state)
            verdict, event, identity, nat = outs[:4]
            self.ct.state, self._counters = outs[4], outs[5]
            tail = 6
            if self.flows is not None:
                self.flows.state = outs[tail]
                tail += 1
            if self._threat is not None:
                self.threat_state = outs[tail]
                self.last_threat = outs[tail + 1]
                tail += 2
            if self._analytics_on:
                self.analytics_state = outs[tail]
                tail += 1
            if self.provenance_enabled:
                self.last_provenance = Provenance(outs[tail],
                                                  outs[tail + 1])
            served = self._revision_newly_served_locked()
        if telem:
            self._account_dispatch("engine-v4", t0, t_lock, verdict)
        if served:
            self._notify_revision_served(served)
        return verdict, event, identity, nat

    def process6(self, pkt: FullPacketBatch6,
                 now: Optional[int] = None, payload=None):
        """Classify a v6 batch (bpf_lxc.c:745 ipv6_policy path).
        Returns (verdict, event, identity, nat6).  Same async-dispatch,
        narrow-lock and payload-lane contract as process()."""
        telem = self.telemetry_enabled
        t0 = time.perf_counter() if telem else 0.0
        ts = self._timestamp(now)
        with self._lock:
            if self._step6 is None:
                raise RuntimeError("no policy loaded")
            t_lock = time.perf_counter() if telem else 0.0
            pl = self._payload_in(payload, int(pkt.sport.shape[0]))
            if self.flows is not None:
                step = self._flow_step_variant(self._step6,
                                               self._step6_nc)
                flows_in = self.flows.state
            else:
                step = self._step6
                flows_in = None
            outs = self._dispatch_locked(step, self._tbufs6,
                                         self.ct6.state, pkt, ts,
                                         flows_in, pl,
                                         self.threat_state,
                                         self.analytics_state)
            verdict, event, identity, nat = outs[:4]
            self.ct6.state, self._counters = outs[4], outs[5]
            tail = 6
            if self.flows is not None:
                self.flows.state = outs[tail]
                tail += 1
            if self._threat is not None:
                self.threat_state = outs[tail]
                self.last_threat = outs[tail + 1]
                tail += 2
            if self._analytics_on:
                self.analytics_state = outs[tail]
                tail += 1
            if self.provenance_enabled:
                self.last_provenance = Provenance(outs[tail],
                                                  outs[tail + 1])
            served = self._revision_newly_served_locked()
        if telem:
            self._account_dispatch("engine-v6", t0, t_lock, verdict)
        if served:
            self._notify_revision_served(served)
        return verdict, event, identity, nat

    def process_packed(self, packed, now: Optional[int] = None,
                       payload=None):
        """Classify a v4 batch given as ONE [10, B] int32 field matrix
        (pipeline.PACKED_FIELDS order) — the serving dispatcher's hot
        entry: a single H2D transfer per batch instead of ten, with
        the per-field unpack fused into the compiled program.  Same
        verdict/event/identity/nat outputs, same async-dispatch and
        narrow-lock contract as process().  ``policy_verdicts_total``
        is left to the caller, which copies the verdicts to the host
        anyway (the serving lane counts them there).

        ``payload`` is the optional [B, W] L7 payload lane riding
        beside the field matrix (its own H2D) when the fast-verdict
        stage is enabled; payload-less batches get the cached absent
        matrix (every L7 rule redirects, the pre-fast behavior)."""
        telem = self.telemetry_enabled
        t0 = time.perf_counter() if telem else 0.0
        ts = self._timestamp(now)
        if self._placement is not None and \
                packed.shape[1] % self._placement.devices.shape[0] == 0:
            # shard the batch axis across the submesh's dp devices
            # (async H2D; the jitted step follows committed shardings)
            packed = jax.device_put(packed, self._batch_sharding)
        with self._lock:
            if self._step_packed is None:
                raise RuntimeError("no policy loaded")
            t_lock = time.perf_counter() if telem else 0.0
            pl = self._payload_in(payload, int(packed.shape[1]))
            if self.flows is not None:
                step = self._flow_step_variant(self._step_packed,
                                               self._step_packed_nc)
                flows_in = self.flows.state
            else:
                step = self._step_packed
                flows_in = None
            outs = self._dispatch_locked(step, self._tbufs4,
                                         self.ct.state, packed, ts,
                                         flows_in, pl,
                                         self.threat_state,
                                         self.analytics_state)
            verdict, event, identity, nat = outs[:4]
            self.ct.state, self._counters = outs[4], outs[5]
            tail = 6
            if self.flows is not None:
                self.flows.state = outs[tail]
                tail += 1
            if self._threat is not None:
                self.threat_state = outs[tail]
                self.last_threat = outs[tail + 1]
                tail += 2
            if self._analytics_on:
                self.analytics_state = outs[tail]
                tail += 1
            if self.provenance_enabled:
                self.last_provenance = Provenance(outs[tail],
                                                  outs[tail + 1])
            served = self._revision_newly_served_locked()
        if telem:
            self._account_dispatch("engine-v4", t0, t_lock)
        if served:
            self._notify_revision_served(served)
        return verdict, event, identity, nat

    # -- the latency-tier serving path (datapath/serving.py) -----------------

    def configure_supervision(self, enabled: bool = True,
                              **knobs) -> None:
        """Set the serving lane's supervision config BEFORE first use
        of serving().  Knobs: watchdog_s, failure_threshold, reset_s,
        max_reset_s, new_flow_policy, recovery_gate, oracle_refresh_s
        (DeviceSupervisor kwargs) plus max_pending/default_deadline
        (admission control).  ``enabled=False`` restores the exact
        pre-supervision dispatch path."""
        with self._lock:
            if self._serving is not None:
                raise RuntimeError(
                    "serving lane already created; configure "
                    "supervision before first serving() use")
            self._supervision_cfg = {"enabled": enabled, **knobs}

    def serving(self):
        """THE shared continuous micro-batching dispatcher for this
        engine (created on first use): the verdict service, L7 plane
        and direct callers submit record chunks here so concurrent
        endpoints coalesce into one device launch instead of
        serializing pack+dispatch+sync on the engine lock.  Unless
        supervision is disabled, launches run under a DeviceSupervisor
        (datapath/supervisor.py): overload admission control, device-
        fault circuit breaking with fail-static host fallback, and
        breaker-gated recovery."""
        with self._lock:
            if self._serving is None:
                from .serving import VerdictDispatcher
                cfg = dict(self._supervision_cfg)
                supervisor = None
                admission = {
                    "max_pending": cfg.pop("max_pending", None),
                    "default_deadline": cfg.pop("default_deadline",
                                                None)}
                if cfg.pop("enabled", True):
                    from .supervisor import DeviceSupervisor
                    supervisor = DeviceSupervisor(self, **cfg)
                self._serving = VerdictDispatcher(
                    self, supervisor=supervisor,
                    lane=self._serving_lane_name, **admission)
            return self._serving

    def supervision_status(self) -> Dict:
        """The dataplane block of the agent status path: serving mode
        (ok/degraded/recovering), breaker state, shed/fail-static
        accounting.  Never CREATES the serving lane — a status probe
        must not spin up dispatcher threads."""
        with self._lock:
            serving = self._serving
        if serving is None:
            return {"mode": "ok", "serving": None,
                    "supervised": self._supervision_cfg.get(
                        "enabled", True)}
        sup = serving.supervisor
        out = {"mode": sup.mode if sup is not None else "ok",
               "supervised": sup is not None,
               "serving": serving.stats()}
        return out

    def host_policy_states(self) -> Dict[int, PolicyMapState]:
        """{table slot: host-of-record PolicyMapState} — what the
        fail-static oracle enforces and the recovery gate replays
        against.  Sourced from the DeviceTableManager in incremental
        mode, from the states load_policy compiled otherwise."""
        with self._lock:
            mgr = self._table_mgr
            states = self._host_states
        if mgr is not None:
            return mgr.states_by_slot()
        if states is None:
            return {}
        return {slot: st for slot, st in enumerate(states)}

    # -- self-telemetry (observability/) -------------------------------------

    def _account_dispatch(self, family: str, t0: float, t_lock: float,
                          verdict=None) -> None:
        """Stage slices + deferred verdict-outcome accounting for one
        dispatch (``verdict`` None: the caller counts the outcomes).
        Runs AFTER the engine lock is released — accounting (and the
        occasional force-flush device read) must never extend the lock
        hold."""
        record_stage(family, "lock-wait", t_lock - t0)
        record_stage(family, "dispatch", time.perf_counter() - t_lock)
        if verdict is None:
            return
        with self._verdict_lock:
            self._pending_verdicts.append(verdict)
            self._flush_verdict_counts(
                force=len(self._pending_verdicts) > 8)

    def _flush_verdict_counts(self, force: bool = False) -> None:
        """Count verdict outcomes from completed batches (verdict lock
        held).  Dispatch is async, so the just-dispatched batch is
        usually not ready — it gets counted on a later call (or
        force-synced once the pending window fills), never blocking
        the hot path."""
        remaining = []
        for arr in self._pending_verdicts:
            ready = force
            if not ready:
                checker = getattr(arr, "is_ready", None)
                try:
                    ready = checker() if checker is not None else True
                except Exception:  # noqa: BLE001 — deleted/donated
                    continue
            if not ready:
                remaining.append(arr)
                continue
            try:
                v = np.asarray(arr)  # sync-ok: is_ready-gated (or a bounded force-flush outside the device lock)
            except Exception:  # noqa: BLE001 — deleted buffer
                continue
            count_policy_verdicts(v)
        self._pending_verdicts = remaining

    def flush_telemetry(self) -> None:
        """Drain deferred verdict accounting (metrics-scrape path).
        Takes only the verdict lock — a scrape never stalls dispatch."""
        with self._verdict_lock:
            self._flush_verdict_counts(force=True)

    def _revision_newly_served_locked(self) -> int:
        """First dispatch at a new policy revision (lock held).
        Returns the revision to report, or 0."""
        if self.on_revision_served is None or \
                self.revision <= self._served_revision:
            return 0
        self._served_revision = self.revision
        return self.revision

    def _notify_revision_served(self, revision: int) -> None:
        try:
            self.on_revision_served(revision)
        except Exception:  # noqa: BLE001 — telemetry must never
            pass           # poison the verdict path

    # -- verdict provenance (replay + slot decode) ---------------------------

    def rule_decoder(self):
        """Host decoder for provenance match slots: a closure mapping
        a flat [E*S] slot to the compiled PolicyKey words at that slot
        of the LIVE device policy tensors (None for -1/empty/out of
        range).  The tensor->numpy transfer is cached per tensor
        generation, so decoding many sampled slots costs one read."""
        with self._lock:
            if self._tables is None:
                return lambda slot: None
            key_id = self._tables.datapath.key_id
            key_meta = self._tables.datapath.key_meta
            value = self._tables.datapath.value
            cache = self._prov_decode_cache
        if cache is None or cache[0] is not key_id:
            arrays = (np.asarray(key_id).reshape(-1),
                      np.asarray(key_meta).reshape(-1),
                      np.asarray(value).reshape(-1),
                      int(key_id.shape[-1]))
            with self._lock:
                self._prov_decode_cache = (key_id, arrays)
        else:
            arrays = cache[1]
        flat_id, flat_meta, flat_value, slots = arrays

        def decode(slot) -> Optional[Dict]:
            slot = int(slot)
            if slot < 0 or slot >= flat_meta.shape[0]:
                return None
            meta = int(flat_meta[slot])
            if meta == 0:
                return None  # slot emptied since the batch ran
            return {"endpoint-slot": slot // slots,
                    "slot": slot % slots,
                    "identity": int(np.uint32(flat_id[slot])),
                    "dport": (meta >> 16) & 0xFFFF,
                    "proto": (meta >> 8) & 0xFF,
                    "direction": (meta >> 1) & 1,
                    "proxy-port": int(flat_value[slot])}
        return decode

    def provenance_rule_of(self):
        """String form of rule_decoder for the monitor/hubble surfaces
        ('' for unmatched slots)."""
        decode = self.rule_decoder()

        def rule_of(slot) -> str:
            return format_rule(decode(slot))
        return rule_of

    def policy_replay(self, endpoints, identities, dports, protos,
                      directions) -> List[Dict]:
        """Run a synthesized header batch through the REAL compiled
        policy tensors serving traffic right now (`cilium policy
        trace --replay` / the drift audit's device side).  Pure read:
        no counters, no CT, no flow table — verdict_explain shares
        the hot path's stage lookups, so the verdicts are bit-exact
        with what `process()` would decide for a new flow.

        Args are equal-length sequences: endpoint TABLE SLOTS (not
        endpoint ids), identities, dports, protos, directions.
        Returns one dict per row with the final verdict/tier/slot,
        the decoded matched key, and each stage's outcome."""
        from .events import tier_name
        with self._lock:
            if self._tables is None:
                raise RuntimeError("no policy loaded")
            key_id = self._tables.datapath.key_id
            key_meta = self._tables.datapath.key_meta
            value = self._tables.datapath.value
            probe = self._replay_probe
        pkt = make_packet_batch(endpoints, identities, dports, protos,
                                directions)
        res = _explain_jit(key_id, key_meta, value, pkt,
                           max_probe=probe)
        res = jax.tree_util.tree_map(np.asarray, res)
        decode = self.rule_decoder()
        eps, ids, dps, prs, dirs = (np.asarray(a) for a in (
            endpoints, identities, dports, protos, directions))
        out: List[Dict] = []
        for i in range(eps.shape[0]):
            stages = {}
            for name in ("exact", "l3", "l4_wildcard"):
                st = res[name]
                found = bool(st["found"][i])
                stages[name] = {
                    "found": found,
                    "value": int(st["value"][i]),
                    "key": decode(st["slot"][i]) if found else None}
            slot = int(res["slot"][i])
            out.append({
                "endpoint-slot": int(eps[i]),
                "identity": int(ids[i]),
                "dport": int(dps[i]),
                "proto": int(prs[i]),
                "direction": int(dirs[i]),
                "verdict": int(res["verdict"][i]),
                "tier": int(res["tier"][i]),
                "tier-name": tier_name(int(res["tier"][i])),
                "slot": slot,
                "matched": decode(slot) if slot >= 0 else None,
                "stages": stages})
        return out

    def map_pressure(self, warn_threshold: float = 0.9) -> Dict:
        """Map-pressure report over the live device tables (updates
        the map_pressure/map_entries gauges as a side effect)."""
        return compute_pressure(self.map_inventory(), warn_threshold)

    def lb6_service_list(self):
        """Snapshot of the v6 service registry under the engine lock —
        the threaded REST server must not iterate the live dict while
        an upsert mutates it."""
        with self._lock:
            return list(self.lb6_services.values())

    def ct_entries(self) -> Tuple[int, int]:
        """(v4, v6) live CT entry counts, serialized against the gc
        controller's buffer donation (an unlocked entry_count can read
        a deleted array mid-gc)."""
        with self._lock:
            return self.ct.entry_count(), self.ct6.entry_count()

    def snapshot_ct(self):
        """(v4, v6) CT snapshots, serialized against process/gc — the
        gc step DONATES the state buffers, so an unlocked read can see
        a deleted array."""
        with self._lock:
            return self.ct.snapshot(), self.ct6.snapshot()

    def restore_ct_snapshots(self, v4, v6) -> int:
        """Validate + swap in both CT snapshots atomically (both
        prepared before either is assigned); returns entries restored.
        Raises ValueError/KeyError on a bad snapshot — callers treat
        that as a cold start."""
        with self._lock:
            st4 = self.ct.prepare_snapshot(v4)
            st6 = self.ct6.prepare_snapshot(v6)
            self.ct.state = st4
            self.ct6.state = st6
            return self.ct.entry_count() + self.ct6.entry_count()

    # -- map dump surface (cilium bpf */list analogs) -----------------------

    def map_inventory(self) -> Dict[str, Dict]:
        """Per-map geometry + occupancy (cilium map list / bpf map
        show): what state is device-resident right now."""
        with self._lock:
            out: Dict[str, Dict] = {}
            if self._table_mgr is not None:
                geom, _t = self._table_mgr.snapshot()
                cap, slots, probe, gen = geom
                out["policy"] = {"endpoints": cap, "slots": slots,
                                 "max-probe": probe, "generation": gen,
                                 "attached":
                                 self._table_mgr.stats()["endpoints"]}
            elif self.compiled_policy is not None:
                out["policy"] = {
                    "endpoints": self.compiled_policy.num_endpoints,
                    "slots": self.compiled_policy.slots,
                    "max-probe": self.compiled_policy.max_probe,
                    "entries": self.compiled_policy.entry_count()}
            out["ipcache"] = {"entries": len(self.ipcache_prefixes)}
            out["ipcache6"] = {"entries": len(self.ipcache_prefixes6)}
            for name, tbl in (("ct", self.ct), ("ct6", self.ct6)):
                out[name] = {"slots": tbl.slots,
                             "occupied": tbl.entry_count(),
                             "max-probe": tbl.max_probe}
            out["lb"] = {"services": len(self.lb)}
            out["lb6"] = {"services": len(self.lb6_services)}
            out["tunnel"] = {"entries": len(self.tunnel_prefixes)}
            if self.flows is not None:
                out["hubble-flows"] = self.flows.stats()
            pf = self.prefilter._compiled
            pf6 = self.prefilter._compiled6
            out["prefilter"] = {
                "v4-entries": pf.entry_count() if pf else 0,
                "v6-entries": pf6.entry_count() if pf6 else 0}
            return out

    def map_dump(self, name: str, max_entries: int = 4096):
        """Entries of one device map (cilium bpf ipcache/ct/tunnel/lb
        list).  CT dumps decode the LIVE device arrays — the exact
        state the verdict path consults."""
        # snapshot references under the lock, decode AFTER releasing
        # it: the jax arrays are immutable, and holding the datapath
        # lock through device->host transfers plus a Python decode
        # loop would stall every concurrent process() call
        with self._lock:
            if name == "ipcache":
                return dict(sorted(self.ipcache_prefixes.items())
                            [:max_entries])
            if name == "ipcache6":
                return dict(sorted(self.ipcache_prefixes6.items())
                            [:max_entries])
            if name == "tunnel":
                return {cidr: int(np.uint32(ip & 0xFFFFFFFF))
                        for cidr, ip in
                        sorted(self.tunnel_prefixes.items())
                        [:max_entries]}
            if name == "hubble-flows":
                flows = self.flows
                if flows is None:
                    return []
                # immutable device arrays: decode outside the lock,
                # same convention as the CT dump below
            elif name in ("ct", "ct6"):
                st = (self.ct if name == "ct" else self.ct6).state
            elif name == "lb":
                svcs = self.lb.services()[:max_entries]
            elif name == "lb6":
                svcs6 = list(self.lb6_services.values())[:max_entries]
            elif name == "prefilter":
                cidrs, rev = self.prefilter.dump()
                return {"cidrs": cidrs[:max_entries], "revision": rev}
            else:
                raise KeyError(name)
        if name == "hubble-flows":
            return flows.snapshot(max_entries)
        if name in ("ct", "ct6"):
            flds = ct_host_fields(st)
            k3 = flds["k3"]
            # exclude the sentinel slot (the last row absorbs no-op
            # scatters; entry_count has the same exclusion)
            idx = np.flatnonzero(k3[:-1])[:max_entries]
            k0 = flds["k0"].astype(np.uint32)
            k1 = flds["k1"].astype(np.uint32)
            k2 = flds["k2"].astype(np.uint32)
            exp = flds["expires"]
            rn = flds["rev_nat"]
            pp = flds["proxy_port"]
            return [{
                "saddr": int(k0[i]), "daddr": int(k1[i]),
                "sport": int(k2[i] >> 16),
                "dport": int(k2[i] & 0xFFFF),
                "proto": int((k3[i] >> 8) & 0xFF),
                "ingress": not bool((k3[i] >> 1) & 1),
                "expires": int(exp[i]),
                "rev-nat": int(rn[i]),
                "proxy-port": int(pp[i])} for i in idx.tolist()]
        if name == "lb":
            return [{"vip": int(np.uint32(s.vip & 0xFFFFFFFF)),
                     "port": s.port, "proto": s.proto,
                     "backends": len(s.backends),
                     "rev-nat": s.rev_nat_index} for s in svcs]
        return [{"vip": list(s.vip), "port": s.port,
                 "proto": s.proto, "backends": len(s.backends),
                 "rev-nat": s.rev_nat_index} for s in svcs6]

    # -- maintenance ---------------------------------------------------------

    def gc(self, now: Optional[int] = None) -> int:
        with self._lock:
            ts = now if now is not None else int(time.time())
            return self.ct.gc(ts) + self.ct6.gc(ts)


def make_full_batch(endpoint, saddr, daddr, sport, dport, proto=None,
                    direction=None, tcp_flags=None, length=None,
                    is_fragment=None, from_overlay=None,
                    tunnel_id=None, mark_identity=None
                    ) -> FullPacketBatch:
    n = len(np.asarray(endpoint))
    arr = lambda x, d: jnp.asarray(np.asarray(
        x if x is not None else np.full(n, d), np.int32))
    import numpy as _np

    def addr(x):
        a = _np.asarray(x)
        if a.dtype.kind in ("U", "S", "O"):  # dotted-quad strings
            from ..compiler.lpm import ipv4_to_u32
            a = _np.array([ipv4_to_u32(str(s)) for s in a.ravel()],
                          _np.uint32).reshape(a.shape)
        if a.dtype == _np.uint32:
            a = a.view(_np.int32)
        return jnp.asarray(a.astype(_np.int32) if a.dtype != _np.int32 else a)

    overlay_fields = {}
    if from_overlay is not None or tunnel_id is not None:
        overlay_fields = dict(from_overlay=arr(from_overlay, 0),
                              tunnel_id=arr(tunnel_id, 0))
    if mark_identity is not None:
        overlay_fields["mark_identity"] = arr(mark_identity, 0)
    return FullPacketBatch(
        endpoint=arr(endpoint, 0), saddr=addr(saddr), daddr=addr(daddr),
        sport=arr(sport, 0), dport=arr(dport, 0), proto=arr(proto, 6),
        direction=arr(direction, 1), tcp_flags=arr(tcp_flags, 0x02),
        length=arr(length, 100), is_fragment=arr(is_fragment, 0),
        **overlay_fields)


def make_full_batch6(endpoint, saddr, daddr, sport, dport, proto=None,
                     direction=None, tcp_flags=None, length=None,
                     is_fragment=None, from_overlay=None,
                     tunnel_id=None, mark_identity=None,
                     icmp_type=None, nd_target=None
                     ) -> FullPacketBatch6:
    """v6 batch builder: saddr/daddr accept v6 strings or [B, 4] int32
    word arrays; icmp_type/nd_target feed the ICMPv6/NDP responder
    stage (nd_target accepts strings or [B, 4] words too)."""
    n = len(np.asarray(endpoint))
    arr = lambda x, d: jnp.asarray(np.asarray(
        x if x is not None else np.full(n, d), np.int32))

    def addr6(x):
        a = np.asarray(x)
        if a.dtype.kind in ("U", "S", "O"):
            from ..compiler.lpm import ipv6_batch_words
            return jnp.asarray(ipv6_batch_words([str(s)
                                                 for s in a.ravel()]))
        if a.dtype == np.uint32:
            a = a.view(np.int32)
        assert a.ndim == 2 and a.shape[1] == 4, "v6 addrs are [B, 4]"
        return jnp.asarray(a.astype(np.int32)
                           if a.dtype != np.int32 else a)

    overlay_fields = {}
    if from_overlay is not None or tunnel_id is not None:
        overlay_fields = dict(from_overlay=arr(from_overlay, 0),
                              tunnel_id=arr(tunnel_id, 0))
    if mark_identity is not None:
        overlay_fields["mark_identity"] = arr(mark_identity, 0)
    if icmp_type is not None or nd_target is not None:
        overlay_fields["icmp_type"] = arr(icmp_type, 0)
        overlay_fields["nd_target"] = addr6(nd_target) \
            if nd_target is not None else jnp.zeros((n, 4), jnp.int32)
    return FullPacketBatch6(
        endpoint=arr(endpoint, 0), saddr=addr6(saddr),
        daddr=addr6(daddr), sport=arr(sport, 0), dport=arr(dport, 0),
        proto=arr(proto, 6), direction=arr(direction, 1),
        tcp_flags=arr(tcp_flags, 0x02), length=arr(length, 100),
        is_fragment=arr(is_fragment, 0), **overlay_fields)
