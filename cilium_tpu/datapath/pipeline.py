"""Fused datapath step: ipcache LPM resolve + 3-stage policy verdict.

This is the flagship "model" of the framework: the batched equivalent of
the reference's per-packet path (bpf_lxc.c handle_ipv4_from_lxc →
ipcache lookup → policy_can_egress → counters), expressed as one jitted
tensor program so XLA fuses the whole thing into a handful of gathers.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.lpm import CompiledLPM
from ..compiler.policy_tables import CompiledPolicy
from ..ops.hashtab_ops import batched_lookup
from ..ops.lpm_ops import lpm_lookup
from .lb import CompiledLB, LBTables
from .verdict import Counters, PacketBatch, verdict_step

# Identity assigned when the ipcache has no entry for the address
# (reference: world; bpf derives WORLD_ID when ipcache misses).
WORLD_IDENTITY = 2


class DatapathTables(NamedTuple):
    """All device-resident state for the fused step (one generation)."""

    key_id: jnp.ndarray     # [E, S] policy tables
    key_meta: jnp.ndarray
    value: jnp.ndarray
    lpm_masks: jnp.ndarray  # [P] ipcache LPM
    lpm_key_a: jnp.ndarray  # [P, S2]
    lpm_key_b: jnp.ndarray
    lpm_value: jnp.ndarray
    lpm_plens: jnp.ndarray


class RawPacketBatch(NamedTuple):
    """Pre-identity packet metadata: addresses instead of identities."""

    endpoint: jnp.ndarray    # [B] int32 endpoint slot
    src_addr: jnp.ndarray    # [B] int32 (uint32 IPv4)
    dport: jnp.ndarray       # [B] int32
    proto: jnp.ndarray       # [B] int32
    direction: jnp.ndarray   # [B] int32
    length: jnp.ndarray      # [B] int32
    is_fragment: jnp.ndarray  # [B] int32


def datapath_step(tables: DatapathTables, counters: Counters,
                  pkt: RawPacketBatch, *, policy_probe: int,
                  lpm_probe: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                           Counters]:
    """addr -> identity (LPM) -> verdict (3-stage) -> counters.

    Returns (verdict [B], identity [B], counters')."""
    found, ident = lpm_lookup(tables.lpm_masks, tables.lpm_key_a,
                              tables.lpm_key_b, tables.lpm_value,
                              tables.lpm_plens, pkt.src_addr, lpm_probe)
    identity = jnp.where(found, ident, jnp.int32(WORLD_IDENTITY))
    vb = PacketBatch(endpoint=pkt.endpoint, identity=identity,
                     dport=pkt.dport, proto=pkt.proto,
                     direction=pkt.direction, length=pkt.length,
                     is_fragment=pkt.is_fragment)
    verdict, counters = verdict_step(tables.key_id, tables.key_meta,
                                     tables.value, counters, vb,
                                     policy_probe)
    return verdict, identity, counters


def build_tables(compiled_policy: CompiledPolicy,
                 compiled_lpm: CompiledLPM, device=None) -> DatapathTables:
    put = (lambda x: jax.device_put(x, device)) if device else jnp.asarray
    return DatapathTables(
        key_id=put(compiled_policy.key_id),
        key_meta=put(compiled_policy.key_meta),
        value=put(compiled_policy.value),
        lpm_masks=put(compiled_lpm.masks),
        lpm_key_a=put(compiled_lpm.key_a),
        lpm_key_b=put(compiled_lpm.key_b),
        lpm_value=put(compiled_lpm.value),
        lpm_plens=put(compiled_lpm.prefix_lens))


def make_step(compiled_policy: CompiledPolicy, compiled_lpm: CompiledLPM):
    """(jitted step fn, tables, fresh counters)."""
    tables = build_tables(compiled_policy, compiled_lpm)
    n = max(1, compiled_policy.num_endpoints * compiled_policy.slots)
    counters = Counters(packets=jnp.zeros(n, jnp.uint32),
                        bytes=jnp.zeros(n, jnp.uint32))
    step = jax.jit(functools.partial(
        datapath_step, policy_probe=compiled_policy.max_probe,
        lpm_probe=compiled_lpm.max_probe), donate_argnums=(1,))
    return step, tables, counters


# ---------------------------------------------------------------------------
# Full datapath: prefilter -> LB -> conntrack -> ipcache -> policy -> create
# ---------------------------------------------------------------------------

class FullPacketBatch(NamedTuple):
    """Wire-level metadata for the full path, all [B] int32.

    ``from_overlay``/``tunnel_id`` model the tunnel header of packets
    that arrived encapsulated from a peer node (bpf_overlay.c:151
    from-overlay + skb_get_tunnel_key): where ``from_overlay`` is
    nonzero, the source security identity is taken from ``tunnel_id``
    — the identity the sending node stamped into the tunnel key — not
    re-derived from the ipcache.  ``mark_identity`` is the proxy-mark
    analog (bpf_netdev.c:128-146 MARK_MAGIC_PROXY): flows re-entering
    the datapath from the L7 proxy carry the ORIGINAL source identity
    in the mark, so they are not re-classified (as WORLD or as the
    proxy host) on the way to the upstream; nonzero values win over
    the ipcache.  All three default to None."""

    endpoint: jnp.ndarray
    saddr: jnp.ndarray
    daddr: jnp.ndarray
    sport: jnp.ndarray
    dport: jnp.ndarray
    proto: jnp.ndarray
    direction: jnp.ndarray
    tcp_flags: jnp.ndarray
    length: jnp.ndarray
    is_fragment: jnp.ndarray
    from_overlay: jnp.ndarray = None
    tunnel_id: jnp.ndarray = None
    mark_identity: jnp.ndarray = None


class NATResult(NamedTuple):
    """Post-NAT forwarding result: forward packets carry the DNAT'd
    destination; reply packets carry the rev-NAT'd (VIP-restored)
    source.  ``tunnel_ep``/``tunnel_id`` are the encap decision
    (encap.h encap_and_redirect): nonzero tunnel_ep means the packet
    leaves encapsulated to that node IP with the source security
    identity in the tunnel key.  All [B] int32."""

    daddr: jnp.ndarray
    dport: jnp.ndarray
    saddr: jnp.ndarray
    sport: jnp.ndarray
    rev_nat: jnp.ndarray
    tunnel_ep: jnp.ndarray
    tunnel_id: jnp.ndarray


def lb_rev_nat_arrays(lb_tables, saddr, sport, rev_nat_idx):
    """Clamp-safe reverse NAT (see lb.lb_rev_nat)."""
    has = rev_nat_idx > 0
    n = lb_tables.rev_vip.shape[0]
    idx = jnp.clip(jnp.where(has, rev_nat_idx, 0), 0, n - 1)
    return (jnp.where(has, lb_tables.rev_vip[idx], saddr),
            jnp.where(has, lb_tables.rev_port[idx], sport))


class FullTables(NamedTuple):
    """All device state for the full step.  The tunnel LPM (tun_*) is
    the device twin of the reference's cilium_tunnel_map (pkg/maps/
    tunnel): pod-CIDR -> tunnel endpoint node IP.  ``ep_identity`` [E]
    is each local endpoint slot's own security identity — the SECLABEL
    the per-endpoint program compiles in (bpf_lxc.c) — stamped into the
    tunnel key on encap.  All optional: None disables the overlay
    stage."""

    datapath: DatapathTables          # policy + ipcache LPM
    lb: LBTables                      # service tables
    pf_masks: jnp.ndarray             # prefilter deny LPM
    pf_key_a: jnp.ndarray
    pf_key_b: jnp.ndarray
    pf_value: jnp.ndarray
    pf_plens: jnp.ndarray
    tun_masks: jnp.ndarray = None     # tunnel map LPM (encap.h)
    tun_key_a: jnp.ndarray = None
    tun_key_b: jnp.ndarray = None
    tun_value: jnp.ndarray = None
    tun_plens: jnp.ndarray = None
    ep_identity: jnp.ndarray = None   # [E] local slot -> own identity
    # On-device L7 fast-verdict tables (l7/fast.L7FastPrograms): the
    # per-slot program classification emitted by the policy compiler
    # plus the fused class-compressed k-stride DFA walked inline by
    # the fast-verdict stage.  All None = fast verdicts disabled (the
    # compiled program is byte-identical to the pre-fast step).
    l7_prog: jnp.ndarray = None       # [E, S] slot -> program id (-1)
    l7_flat: jnp.ndarray = None       # [S * c1**k] stride table
    l7_map: jnp.ndarray = None        # [258] byte+2 -> class
    l7_accept: jnp.ndarray = None     # [S] 0/1 per-state accept
    l7_starts: jnp.ndarray = None     # [R] per-regex start state
    l7_pmask: jnp.ndarray = None      # [P, R] program -> regex rows
    # Inline threat-scoring model (threat/model.ThreatModel.tables()):
    # the quantized Q8.8 scorer weights + the policy-controlled
    # threshold/mode config vector, packed as their own "threat-model"
    # dispatch group.  All None = threat scoring disabled (compiled
    # program byte-identical to the pre-threat step).
    tm_w1: jnp.ndarray = None         # [F, H] int32 layer-1 weights
    tm_b1: jnp.ndarray = None         # [H] int32 layer-1 bias
    tm_w2: jnp.ndarray = None         # [H] int32 layer-2 weights
    tm_b2: jnp.ndarray = None         # [1] int32 layer-2 bias
    tm_cfg: jnp.ndarray = None        # [8] int32 thresholds/mode/gen


def _flow_identities(ep_identity, endpoint, peer_identity, direction):
    """(src, dst) security identities for the flow key: the endpoint's
    own identity (SECLABEL) on its side of the flow, the resolved peer
    identity on the other — egress flows read ep->peer, ingress flows
    peer->ep (hubble/aggregation flow key convention)."""
    if ep_identity is not None:
        n_ep = ep_identity.shape[0]
        own = ep_identity[jnp.clip(endpoint, 0, n_ep - 1)]
    else:
        own = jnp.zeros_like(peer_identity)
    egress = direction == 1
    src = jnp.where(egress, own, peer_identity)
    dst = jnp.where(egress, peer_identity, own)
    return src, dst


# field order of the serving path's packed [10, B] batch matrix
# (datapath/serving.py staging buffers; full_datapath_step_packed
# unpacks in this exact order inside the fused program)
PACKED_FIELDS = ("endpoint", "saddr", "daddr", "sport", "dport",
                 "proto", "direction", "tcp_flags", "length",
                 "is_fragment")
PACKED_INDEX = {f: i for i, f in enumerate(PACKED_FIELDS)}


def host_fail_static_step(soa, n: int, *, established, identity_of,
                          policy_verdict):
    """Host-serveable fail-static twin of ``full_datapath_step``'s
    verdict precedence — what the dataplane supervisor
    (datapath/supervisor.py) answers with while the device lane is
    degraded, mirroring the reference's fail-static property
    (daemon/state.go: the kernel keeps forwarding on last-known-good
    state while the agent is down).

    Precedence mirrors step 7 of the compiled program: an established
    flow follows its CT entry (its recorded proxy port; 0 == allow),
    everything else takes the (degraded-mode) policy verdict for a new
    flow.  The LB/prefilter/overlay stages are deliberately NOT served
    degraded — fail-static answers policy, not NAT (documented
    limitation; the reference's agent-down window likewise freezes LB
    backend churn).

    ``soa`` is the PacketRing SoA dict of [>=n] int32 arrays
    (PACKED_FIELDS keys).  Callbacks:

    - ``established(saddr_u32, daddr_u32, sport, dport, proto,
      direction) -> Optional[int]``: the flow's recorded proxy port
      when its CT entry (forward or reply tuple) is live, else None;
    - ``identity_of(addr_u32) -> int``: host-ipcache identity of the
      peer address (WORLD when unknown);
    - ``policy_verdict(endpoint_slot, identity, dport, proto,
      direction) -> int``: the new-flow decision (the compiler oracle,
      a blanket deny, or a blanket allow — the configured degraded
      policy).

    Returns (verdict [n], identity [n]) int32 arrays.
    """
    verdicts = np.empty(n, np.int32)
    idents = np.empty(n, np.int32)
    ep = soa["endpoint"]
    sa = np.ascontiguousarray(soa["saddr"][:n]).view(np.uint32)
    da = np.ascontiguousarray(soa["daddr"][:n]).view(np.uint32)
    sp, dp = soa["sport"], soa["dport"]
    pr, di = soa["proto"], soa["direction"]
    for j in range(n):
        direction = int(di[j])
        # peer identity: src on ingress, dst on egress (bpf_lxc.c:205)
        peer = int(sa[j]) if direction == 0 else int(da[j])
        ident = int(identity_of(peer))
        idents[j] = ident
        ct = established(int(sa[j]), int(da[j]), int(sp[j]),
                         int(dp[j]), int(pr[j]), direction)
        if ct is not None:
            verdicts[j] = ct  # the flow keeps its verdict (0 = allow)
            continue
        verdicts[j] = int(policy_verdict(int(ep[j]), ident,
                                         int(dp[j]), int(pr[j]),
                                         direction))
    return verdicts, idents


def full_datapath_step_packed(tables: FullTables, ct,
                              counters: Counters, packed, now,
                              flows=None, payload=None, threat=None,
                              analytics=None, **statics):
    """full_datapath_step over ONE [10, B] int32 field matrix.

    The latency-tier fix for small-batch dispatch overhead: ten
    per-field host->device transfers (each paying a full dispatch,
    ~80 us apiece on the CPU backend — batch-size independent)
    collapse into a single H2D of the packed matrix; the per-field
    unpack is row slicing INSIDE the jitted program, which XLA fuses
    away.  Field order is PACKED_FIELDS.  ``payload`` is the optional
    [B, W] L7 payload lane (its own buffer beside the field matrix —
    present only when the fast-verdict stage is compiled in, so the
    no-L7 program keeps its exact argument list)."""
    pkt = FullPacketBatch(**{f: packed[i]
                             for i, f in enumerate(PACKED_FIELDS)})
    return full_datapath_step(tables, ct, counters, pkt, now,
                              flows, payload, threat, analytics,
                              **statics)


def _l7_fast_stage(tables, payload, pol_verdict, pol_slot, *,
                   k: int, c1: int):
    """The on-device L7 fast-verdict stage (l7/fast.py tables): where
    the policy verdict is a redirect whose matched slot carries a
    first-bytes-decidable program AND the payload window is present
    and untruncated, walk the fused class-compressed k-stride DFA and
    decide allow/deny inline — the flow never reaches the proxy.
    Everything else keeps the redirect verdict (fail-to-redirect,
    never fail-open).

    Returns (verdict', fast_allow [B], fast_deny [B])."""
    from ..ops.dfa_engine import _packed_walk
    from .verdict import VERDICT_DROP_L7
    prog_flat = tables.l7_prog.reshape(-1)
    slot = jnp.clip(pol_slot, 0, prog_flat.shape[0] - 1)
    prog = jnp.where(pol_slot >= 0, prog_flat[slot], jnp.int32(-1))
    eligible = (pol_verdict > 0) & (prog >= 0)
    # decidability: an absent (all -1) payload or a window-truncation
    # poison row (-2, the encode_strings overlong contract) cannot be
    # judged from first bytes — those flows redirect to the proxy
    has_payload = payload[:, 0] >= 0
    truncated = jnp.any(payload == jnp.int32(-2), axis=1)
    b, w = payload.shape
    # class map + stride pack + ceil(W/k) dependent gathers: the
    # ops/dfa_engine stride strategy fused into this program (negative
    # bytes map to the identity class, which composes as the identity
    # function — pads freeze states exactly like the standalone engine)
    cls = tables.l7_map[payload + jnp.int32(2)]
    pad = (-w) % k
    if pad:
        cls = jnp.concatenate(
            [cls, jnp.full((b, pad), c1 - 1, jnp.int32)], axis=1)
    grp = cls.reshape(b, -1, k)
    idx = grp[:, :, 0]
    for j in range(1, k):
        idx = idx * jnp.int32(c1) + grp[:, :, j]
    n_regex = tables.l7_starts.shape[0]
    states = jnp.broadcast_to(tables.l7_starts[None, :],
                              (b, n_regex)).astype(jnp.int32)
    final = _packed_walk(c1 ** k, tables.l7_flat, states, idx)
    hit = tables.l7_accept[final] != 0              # [B, R]
    n_prog = tables.l7_pmask.shape[0]
    own = tables.l7_pmask[jnp.clip(prog, 0, n_prog - 1)]
    l7_allow = jnp.any(hit & (own != 0), axis=1)
    fast = eligible & has_payload & ~truncated
    fast_allow = fast & l7_allow
    fast_deny = fast & ~l7_allow
    verdict = jnp.where(
        fast_allow, jnp.int32(0),
        jnp.where(fast_deny, jnp.int32(VERDICT_DROP_L7), pol_verdict))
    return verdict, fast_allow, fast_deny


def full_datapath_step(tables: FullTables, ct, counters: Counters,
                       pkt: FullPacketBatch, now: jnp.ndarray,
                       flows=None, payload=None, threat=None,
                       analytics=None, *,
                       policy_probe: int, lpm_probe: int, pf_probe: int,
                       lb_probe: int, ct_slots: int, ct_probe: int,
                       tun_probe: int = 0, flow_slots: int = 0,
                       flow_probe: int = 0,
                       flow_claim_budget: int = 1024,
                       with_provenance: int = 0,
                       with_l7_fast: int = 0, l7_k: int = 1,
                       l7_c1: int = 2, with_threat: int = 0,
                       threat_window_s: int = 8,
                       threat_stripe: int = 4,
                       with_analytics: int = 0,
                       analytics_depth: int = 2,
                       analytics_lanes: int = 4,
                       analytics_stripe: int = 16):
    """The batched equivalent of the reference's per-packet egress path
    (bpf_lxc.c:432 handle_ipv4_from_lxc): XDP prefilter drop, service
    DNAT (lb4_local), conntrack lookup, ipcache identity resolve, policy
    verdict for CT_NEW flows, CT entry creation gated on the verdict —
    plus the overlay plane: ingress packets flagged from_overlay take
    their source identity from the tunnel key (bpf_overlay.c:151), and
    allowed egress packets whose destination hits the tunnel map are
    marked for encap with the endpoint's identity in the tunnel key
    (encap.h encap_and_redirect, TRACE_TO_OVERLAY).

    Returns (verdict [B], event [B], identity [B], ct', counters').
    Verdict: -N drop code / 0 allow / >0 proxy port.

    ``with_provenance`` (static) appends two [B] int32 outputs: the
    matched policymap entry's flat slot (-1 = no entry decided) and
    the decision-tier code (events.TIER_*).  0 keeps the compiled
    program identical to the pre-provenance step.

    ``with_l7_fast`` (static) fuses the on-device L7 fast-verdict
    stage: redirect verdicts whose matched slot names a first-bytes-
    decidable program (tables.l7_*) are decided inline from the
    [B, W] ``payload`` lane — allow (0) or DROP_POLICY_L7 — and fall
    back to redirect-to-proxy for truncated/absent payloads.  0 keeps
    the compiled program byte-identical to the pre-fast step (the
    payload arg is never passed then).

    ``with_threat`` (static) fuses the inline threat-scoring stage
    (threat/stage.py): every packet gets an anomaly score from the
    flow-table probe + the claim-window aggregates in ``threat`` (the
    shard-local ThreatState buffer, returned updated) + its own tuple
    features; in enforce mode the score maps through the
    policy-controlled thresholds (tables.tm_cfg) to drop
    (VERDICT_DROP_THREAT), redirect-to-proxy, or token-bucket
    rate-limit, and NEVER overrides an existing drop.  Appends
    (threat', threat_out [B]) outputs.  0 keeps the compiled program
    byte-identical to the pre-threat step.

    ``with_analytics`` (static) fuses the device-resident traffic-
    analytics stage (analytics/stage.py): the batch's FINAL verdicts
    fold into ``analytics`` (the shard-local AnalyticsState buffer) —
    count-min heavy-hitter sketches, candidate key tables, and
    distinct-flow cardinality registers — and the updated state is
    appended as one extra output.  0 keeps the compiled program
    byte-identical to the pre-analytics step (the analytics arg is
    never passed then).
    """
    from .conntrack import CT_NEW, CTBatch, ct_step
    from .events import (DROP_FRAG_NOSUPPORT, DROP_POLICY, DROP_POLICY_L7,
                         DROP_PREFILTER, DROP_THREAT, TRACE_TO_LXC,
                         TRACE_TO_PROXY)
    from .lb import lb_step
    from .verdict import (VERDICT_ALLOW, VERDICT_DROP, VERDICT_DROP_FRAG,
                          VERDICT_DROP_L7, VERDICT_DROP_THREAT)

    # 1. Prefilter (bpf_xdp.c:158 check_filters).
    with jax.named_scope("prefilter"):
        if tables.pf_key_a.shape[0] > 0:
            pf_hit, _ = lpm_lookup(tables.pf_masks, tables.pf_key_a,
                                   tables.pf_key_b, tables.pf_value,
                                   tables.pf_plens, pkt.saddr, pf_probe)
        else:
            pf_hit = jnp.zeros(pkt.saddr.shape[0], bool)

    # 2. Service LB DNAT (lb.h lb4_local).
    with jax.named_scope("lb"):
        daddr, dport, rev_nat, is_svc = lb_step(
            tables.lb, pkt.daddr, pkt.dport, pkt.proto, pkt.saddr, pkt.sport,
            max_probe=lb_probe)

    # 3. Conntrack on the DNAT'd tuple (bpf_lxc.c:501 ct_lookup4) — the
    # create decision comes after the policy verdict.
    with jax.named_scope("ct"):
        ctb = CTBatch(saddr=pkt.saddr, daddr=daddr, sport=pkt.sport,
                      dport=dport, proto=pkt.proto, direction=pkt.direction,
                      tcp_flags=pkt.tcp_flags,
                      related=jnp.zeros_like(pkt.proto))

    # 4. ipcache: remote identity from the *peer* address (src on
    # ingress, dst on egress — bpf_lxc.c:205/eps.h lookup).
    with jax.named_scope("ipcache"):
        peer = jnp.where(pkt.direction == 0, pkt.saddr, daddr)
        found, ident = lpm_lookup(tables.datapath.lpm_masks,
                                  tables.datapath.lpm_key_a,
                                  tables.datapath.lpm_key_b,
                                  tables.datapath.lpm_value,
                                  tables.datapath.lpm_plens, peer, lpm_probe)
        identity = jnp.where(found, ident, jnp.int32(WORLD_IDENTITY))
        # Overlay decap: the sending node stamped the source identity into
        # the tunnel key; it wins over the local ipcache view
        # (bpf_overlay.c:151 key.tunnel_id -> ipv4_local_delivery secctx).
        if pkt.from_overlay is not None:
            decap = (pkt.from_overlay != 0) & (pkt.direction == 0)
            identity = jnp.where(decap, pkt.tunnel_id, identity)
        # Proxy re-entry: the mark carries the original source identity of
        # a proxied flow (bpf_netdev.c:128-146) — without it the upstream
        # leg would classify as the proxy host / WORLD.
        if pkt.mark_identity is not None:
            identity = jnp.where(pkt.mark_identity > 0,
                                 pkt.mark_identity, identity)

    # 5. Policy verdict (bpf/lib/policy.h __policy_can_access).
    with jax.named_scope("policy"):
        vb = PacketBatch(endpoint=pkt.endpoint, identity=identity,
                         dport=dport, proto=pkt.proto,
                         direction=pkt.direction, length=pkt.length,
                         is_fragment=pkt.is_fragment)
        if with_provenance or with_l7_fast:
            # the fast-verdict stage needs the matched slot even when
            # provenance outputs are off (the unused tier is dead code XLA
            # eliminates; the lookups are shared either way)
            pol_verdict, counters, pol_slot, pol_tier = verdict_step(
                tables.datapath.key_id, tables.datapath.key_meta,
                tables.datapath.value, counters, vb, policy_probe,
                with_provenance=True)
        else:
            pol_verdict, counters = verdict_step(
                tables.datapath.key_id, tables.datapath.key_meta,
                tables.datapath.value, counters, vb, policy_probe)

    # 5.5 On-device L7 fast verdict: decide first-bytes-decidable
    # redirects inline from the payload lane — a fast-allowed flow
    # creates its CT entry with proxy port 0 (the whole connection
    # bypasses the proxy), a fast-denied flow creates nothing.
    with jax.named_scope("l7"):
        if with_l7_fast:
            pol_verdict, l7_fast_allow, l7_fast_deny = _l7_fast_stage(
                tables, payload, pol_verdict, pol_slot, k=l7_k, c1=l7_c1)

    # 6. CT step. Creation is gated on the policy allowing the flow
    # (bpf_lxc.c:545 ct_create4 after policy_can_egress); prefilter-
    # dropped packets may neither create nor touch live entries; new
    # entries record the flow's rev-NAT index and proxy port so the
    # whole connection keeps its NAT and L7 redirect.
    with jax.named_scope("ct"):
        create_ok = (pol_verdict >= 0) & ~pf_hit
        proxy_in = jnp.maximum(pol_verdict, 0)
        ct_verdict, ct_rev_nat, ct_proxy, ct = ct_step(
            ct, ctb, now, create_ok, update_mask=~pf_hit,
            rev_nat_in=rev_nat, proxy_port_in=proxy_in,
            slots=ct_slots, max_probe=ct_probe)

    # 7. Final verdict: prefilter drop beats everything; established
    # flows follow their CT entry (including its recorded proxy port);
    # CT_NEW flows take the policy verdict.
    with jax.named_scope("verdict"):
        established = ct_verdict != CT_NEW
        verdict = jnp.where(
            pf_hit, jnp.int32(VERDICT_DROP),
            jnp.where(established, ct_proxy, pol_verdict))

    # 7.5 Inline threat scoring (threat/stage.py): per-packet anomaly
    # score from the flow-table probe + window aggregates + tuple
    # features; enforce-mode arms override allow/redirect verdicts
    # BEFORE the event/overlay stages so a threat-dropped packet never
    # encaps and a threat-redirect routes to the proxy like any other.
    with jax.named_scope("threat"):
        if with_threat:
            from ..threat.stage import threat_stage
            t_src, t_dst = _flow_identities(tables.ep_identity,
                                            pkt.endpoint, identity,
                                            pkt.direction)
            verdict, threat, threat_out, thr_drop, thr_redir, rl_drop = \
                threat_stage(
                    tables, threat, flows, verdict,
                    identity=identity, dport=dport, proto=pkt.proto,
                    tcp_flags=pkt.tcp_flags, length=pkt.length,
                    is_fragment=pkt.is_fragment, established=established,
                    saddr_w=pkt.saddr, daddr_w=daddr, sport=pkt.sport,
                    flow_src=t_src, flow_dst=t_dst, now=now,
                    window_s=threat_window_s, flow_slots=flow_slots,
                    flow_probe=flow_probe, stripe=threat_stripe)

    # 8. Reply-path reverse NAT (lb.h lb4_rev_nat): restore VIP/port on
    # packets of flows whose CT entry carries a rev-NAT index.
    with jax.named_scope("revnat"):
        from .conntrack import CT_REPLY, CT_RELATED
        is_reply = (ct_verdict == CT_REPLY) | (ct_verdict == CT_RELATED)
        rn = jnp.where(is_reply, ct_rev_nat, jnp.int32(0))
        nat_saddr, nat_sport = lb_rev_nat_arrays(tables.lb, pkt.saddr,
                                                 pkt.sport, rn)

    with jax.named_scope("verdict"):
        event = jnp.where(
            pf_hit, jnp.int32(DROP_PREFILTER),
            jnp.where(verdict == VERDICT_DROP_FRAG,
                      jnp.int32(DROP_FRAG_NOSUPPORT),
                      jnp.where(verdict < 0, jnp.int32(DROP_POLICY),
                                jnp.where(verdict > 0,
                                          jnp.int32(TRACE_TO_PROXY),
                                          jnp.int32(TRACE_TO_LXC)))))
        if with_l7_fast:
            # VERDICT_DROP_L7 is produced only by the fast stage, so the
            # final verdict identifies inline L7 denials exactly
            event = jnp.where(verdict == jnp.int32(VERDICT_DROP_L7),
                              jnp.int32(DROP_POLICY_L7), event)
        if with_threat:
            # VERDICT_DROP_THREAT likewise names the threat stage exactly
            event = jnp.where(verdict == jnp.int32(VERDICT_DROP_THREAT),
                              jnp.int32(DROP_THREAT), event)

    # 8.5 Fused traffic analytics (analytics/stage.py): fold the
    # batch's FINAL verdicts into the device-resident heavy-hitter
    # sketches / candidate key tables / cardinality registers — one
    # scatter-add per sketch plus one combined max-scatter.  Runs
    # post-threat so the drops metric attributes every drop arm.
    with jax.named_scope("analytics"):
        if with_analytics:
            from ..analytics.stage import analytics_stage
            analytics = analytics_stage(
                analytics, identity=identity, dport=dport, proto=pkt.proto,
                sport=pkt.sport, length=pkt.length, verdict=verdict,
                saddr_key=pkt.saddr, daddr_key=daddr, now=now,
                depth=analytics_depth, lanes=analytics_lanes,
                stripe=analytics_stripe)

    # 9. Overlay encap (encap.h encap_and_redirect): allowed egress
    # packets whose (DNAT'd) destination falls in a peer node's pod
    # CIDR leave encapsulated to that node's tunnel endpoint, carrying
    # the sending endpoint's own identity (SECLABEL) in the tunnel key.
    # Proxy-redirected packets go to the proxy first, not the overlay.
    with jax.named_scope("encap"):
        zero = jnp.zeros_like(verdict)
        if tun_probe > 0 and tables.tun_key_a is not None:
            from .events import TRACE_TO_OVERLAY
            t_hit, t_ep = lpm_lookup(tables.tun_masks, tables.tun_key_a,
                                     tables.tun_key_b, tables.tun_value,
                                     tables.tun_plens, daddr, tun_probe)
            encap = t_hit & (pkt.direction == 1) & (verdict == 0) & ~pf_hit
            if tables.ep_identity is not None:
                n_ep = tables.ep_identity.shape[0]
                src_sec = tables.ep_identity[
                    jnp.clip(pkt.endpoint, 0, n_ep - 1)]
            else:
                src_sec = zero
            tun_ep_out = jnp.where(encap, t_ep, zero)
            tun_id_out = jnp.where(encap, src_sec, zero)
            event = jnp.where(encap, jnp.int32(TRACE_TO_OVERLAY), event)
        else:
            tun_ep_out = zero
            tun_id_out = zero

    nat = NATResult(daddr=daddr, dport=dport, saddr=nat_saddr,
                    sport=nat_sport, rev_nat=ct_rev_nat,
                    tunnel_ep=tun_ep_out, tunnel_id=tun_id_out)
    out = (verdict, event, identity, nat, ct, counters)
    if flows is not None and flow_slots > 0:
        # 10. Hubble on-device flow aggregation: the same compiled
        # program that produced the verdict reduces per-flow state —
        # packet/byte counters + last-seen keyed by (src identity,
        # dst identity, DNAT'd dport, proto, event) — so host-side
        # observability reads compact aggregates, not packets.
        with jax.named_scope("flows"):
            from ..hubble.aggregation import flow_update_step
            src_id, dst_id = _flow_identities(tables.ep_identity,
                                              pkt.endpoint, identity,
                                              pkt.direction)
            flows = flow_update_step(
                flows, src_id, dst_id, dport, pkt.proto, event,
                pkt.length, now, slots=flow_slots, max_probe=flow_probe,
                claim_budget=flow_claim_budget)
        out = out + (flows,)
    if with_threat:
        # 10.5 Threat outputs: the updated shard-local state buffer
        # and the per-packet score|band|fired lane (engine keeps the
        # last batch's lane for the observability consumers)
        out = out + (threat, threat_out)
    if with_analytics:
        # 10.7 Analytics output: the updated shard-local buffer (the
        # host never reads per-batch lanes — decode.py queries the
        # quiesced epoch of this state directly)
        out = out + (analytics,)
    if with_provenance:
        # 11. Provenance finalization: mirror the final-verdict
        # precedence (step 7) — prefilter beats everything, CT
        # fast-path hits next, then the policy tiers.  Slots stay -1
        # wherever no compiled policymap entry decided.
        from .events import TIER_CT_ESTABLISHED, TIER_PREFILTER
        if with_l7_fast:
            # the fast stage decided where it fired (and nothing above
            # it did): report the fast tier, keeping the matched
            # redirect entry as the attributed slot
            from .events import TIER_L7_FAST_ALLOW, TIER_L7_FAST_DENY
            pol_tier = jnp.where(
                l7_fast_allow, jnp.int32(TIER_L7_FAST_ALLOW),
                jnp.where(l7_fast_deny, jnp.int32(TIER_L7_FAST_DENY),
                          pol_tier))
        tier = jnp.where(
            pf_hit, jnp.int32(TIER_PREFILTER),
            jnp.where(established, jnp.int32(TIER_CT_ESTABLISHED),
                      pol_tier))
        slot = jnp.where(pf_hit | established, jnp.int32(-1), pol_slot)
        if with_threat:
            # the threat stage decided last: where it overrode the
            # verdict, it owns the tier (the slot keeps the matched
            # policy attribution — the rule that ALLOWED the traffic
            # the scorer then refused)
            from .events import (TIER_THREAT_DROP,
                                 TIER_THREAT_RATELIMIT,
                                 TIER_THREAT_REDIRECT)
            tier = jnp.where(
                rl_drop, jnp.int32(TIER_THREAT_RATELIMIT),
                jnp.where(thr_drop, jnp.int32(TIER_THREAT_DROP),
                          jnp.where(thr_redir,
                                    jnp.int32(TIER_THREAT_REDIRECT),
                                    tier)))
        out = out + (slot, tier)
    return out


# ---------------------------------------------------------------------------
# IPv6 path (bpf_lxc.c:114 ipv6_l3_from_lxc, :745 ipv6_policy)
# ---------------------------------------------------------------------------
#
# Addresses are [B, 4] int32 word arrays (big-endian u32 words).  The
# policy verdict tables are family-agnostic (identity x port x proto),
# so the v6 path shares them — only the address-keyed stages differ:
# prefilter and ipcache run the 4-word LPM (full 128-bit compare).
#
# Conntrack: the reference keeps a separate ct6 map with full 128-bit
# tuple keys.  Here the v6 CT is a SEPARATE CT table whose two address
# words hold 32-bit mixes of the 128-bit addresses (fold6 below) — a
# deliberate TPU trade: the CT hot loop stays the same 4-word-key
# scatter/gather kernel for both families instead of doubling gather
# volume.  Two distinct v6 flows alias only if both address folds AND
# the exact port pair AND proto/direction all collide (~2^-64 per flow
# pair); the effect of an alias is one shared CT entry (stale
# timeout/flag sharing), the same class of benign interference as the
# reference's documented CT races — not a policy bypass, because policy
# runs on the ipcache identity, which uses full 128-bit compares.

IPPROTO_ICMPV6 = 58
ICMP6_NS = 135            # neighbour solicitation
ICMP6_NA = 136            # neighbour advertisement
ICMP6_ECHO_REQUEST = 128


class FullPacketBatch6(NamedTuple):
    """v6 wire metadata; addresses [B, 4], everything else [B] int32.

    ``icmp_type`` carries the ICMPv6 type for proto-58 rows (0
    elsewhere); ``nd_target`` the ND target address of NS packets
    ([B, 4], zeros elsewhere) — bpf/lib/icmp6.h reads both from the
    wire at ICMP6_TYPE_OFFSET / ICMP6_ND_TARGET_OFFSET."""

    endpoint: jnp.ndarray
    saddr: jnp.ndarray       # [B, 4]
    daddr: jnp.ndarray       # [B, 4]
    sport: jnp.ndarray
    dport: jnp.ndarray
    proto: jnp.ndarray
    direction: jnp.ndarray
    tcp_flags: jnp.ndarray
    length: jnp.ndarray
    is_fragment: jnp.ndarray
    from_overlay: jnp.ndarray = None
    tunnel_id: jnp.ndarray = None
    mark_identity: jnp.ndarray = None
    icmp_type: jnp.ndarray = None
    nd_target: jnp.ndarray = None


class LPM6Tables(NamedTuple):
    masks: jnp.ndarray   # [P, 4]
    k0: jnp.ndarray      # [P, S]
    k1: jnp.ndarray
    k2: jnp.ndarray
    k3: jnp.ndarray
    kb: jnp.ndarray
    value: jnp.ndarray
    plens: jnp.ndarray   # [P]


class NAT6Result(NamedTuple):
    """v6 forwarding result: DNAT'd destination (forward) and
    rev-NAT'd VIP-restored source (reply).  Addresses [B, 4]."""

    daddr: jnp.ndarray
    dport: jnp.ndarray
    saddr: jnp.ndarray
    sport: jnp.ndarray
    rev_nat: jnp.ndarray


class FullTables6(NamedTuple):
    key_id: jnp.ndarray      # shared policy tables [E, S]
    key_meta: jnp.ndarray
    value: jnp.ndarray
    ipcache6: LPM6Tables
    pf6: LPM6Tables
    lb6: object = None       # LB6Tables (None = no v6 services)
    # the node's router IP words [4] (icmp6.h BPF_V6(router, ROUTER_IP))
    # — the address whose NS/echo the datapath answers itself; None
    # disables the ICMPv6 responder stage
    router_ip6: jnp.ndarray = None
    # [E] local slot -> own security identity (shared with the v4
    # tables; the flow-aggregation stage keys on it)
    ep_identity: jnp.ndarray = None
    # L7 fast-verdict tables (shared with the v4 family — the policy
    # tensors and therefore the per-slot classification are family-
    # agnostic); all None = fast verdicts disabled
    l7_prog: jnp.ndarray = None
    l7_flat: jnp.ndarray = None
    l7_map: jnp.ndarray = None
    l7_accept: jnp.ndarray = None
    l7_starts: jnp.ndarray = None
    l7_pmask: jnp.ndarray = None
    # Inline threat-scoring model (shared with the v4 family — flow
    # keys and features are identity-based, family-agnostic); all
    # None = threat scoring disabled
    tm_w1: jnp.ndarray = None
    tm_b1: jnp.ndarray = None
    tm_w2: jnp.ndarray = None
    tm_b2: jnp.ndarray = None
    tm_cfg: jnp.ndarray = None


def lpm6_tables(c) -> LPM6Tables:
    """CompiledLPM6 -> device tables."""
    return LPM6Tables(masks=jnp.asarray(c.masks), k0=jnp.asarray(c.k0),
                      k1=jnp.asarray(c.k1), k2=jnp.asarray(c.k2),
                      k3=jnp.asarray(c.k3), kb=jnp.asarray(c.kb),
                      value=jnp.asarray(c.value),
                      plens=jnp.asarray(c.prefix_lens))


def fold6(words: jnp.ndarray) -> jnp.ndarray:
    """[B, 4] -> [B] 32-bit mix (CT key fold; see module comment)."""
    from ..ops.hashtab_ops import hash_mix_jnp
    return hash_mix_jnp(hash_mix_jnp(words[:, 0], words[:, 1]),
                        hash_mix_jnp(words[:, 2], words[:, 3]))


def full_datapath_step6(tables: FullTables6, ct, counters: Counters,
                        pkt: FullPacketBatch6, now: jnp.ndarray,
                        flows=None, payload=None, threat=None,
                        analytics=None, *,
                        policy_probe: int, lpm6_probe: int,
                        pf6_probe: int, ct_slots: int, ct_probe: int,
                        lb6_probe: int = 0, flow_slots: int = 0,
                        flow_probe: int = 0,
                        flow_claim_budget: int = 1024,
                        with_provenance: int = 0,
                        with_l7_fast: int = 0, l7_k: int = 1,
                        l7_c1: int = 2, with_threat: int = 0,
                        threat_window_s: int = 8,
                        threat_stripe: int = 4,
                        with_analytics: int = 0,
                        analytics_depth: int = 2,
                        analytics_lanes: int = 4,
                        analytics_stripe: int = 16):
    """The v6 twin of full_datapath_step (bpf_lxc.c:745 ipv6_policy):
    prefilter drop, service DNAT (lb6_local), conntrack, ipcache
    identity, policy verdict for CT_NEW flows, CT create gated on the
    verdict, reply-path reverse NAT (lb6_rev_nat).  ``with_l7_fast``
    fuses the same on-device L7 fast-verdict stage as the v4 family
    (the policy tensors and per-slot classification are shared).

    Returns (verdict [B], event [B], identity [B], nat6, ct',
    counters').
    """
    from ..ops.lpm_ops import lpm6_lookup
    from .conntrack import CT_NEW, CTBatch, ct_step
    from .events import (DROP_FRAG_NOSUPPORT, DROP_POLICY, DROP_POLICY_L7,
                         DROP_PREFILTER, DROP_THREAT,
                         DROP_UNKNOWN_TARGET, ICMP6_ECHO_REPLY,
                         ICMP6_NS_REPLY, TRACE_TO_LXC, TRACE_TO_PROXY)
    from .lb import lb6_rev_nat, lb6_step
    from .verdict import (VERDICT_DROP, VERDICT_DROP_FRAG,
                          VERDICT_DROP_L7, VERDICT_DROP_THREAT,
                          verdict_step)

    b = pkt.sport.shape[0]

    # 1. Prefilter (bpf_xdp.c check_v6 analog).
    with jax.named_scope("prefilter"):
        if tables.pf6.kb.shape[0] > 0:
            pf_hit, _ = lpm6_lookup(tables.pf6.masks, tables.pf6.k0,
                                    tables.pf6.k1, tables.pf6.k2,
                                    tables.pf6.k3, tables.pf6.kb,
                                    tables.pf6.value, tables.pf6.plens,
                                    pkt.saddr, pf6_probe)
        else:
            pf_hit = jnp.zeros(b, bool)

    # 1.5 ICMPv6/NDP responder (bpf/lib/icmp6.h icmp6_handle, called
    # before LB/CT/policy on the from-container path bpf_lxc.c:403-408):
    # an NS whose ND target is the router answers with an NA
    # (send_icmp6_ndisc_adv terminal action); an NS for anything else
    # drops (ACTION_UNKNOWN_ICMP6_NS); an echo request addressed to
    # the router answers with an echo reply.  Every other ICMPv6 type
    # (NA, RS/RA, errors, echo to peers) flows on through CT + policy
    # like the reference's fall-through `return 0`.
    with jax.named_scope("lb"):
        is_icmp6 = pkt.proto == IPPROTO_ICMPV6
        if tables.router_ip6 is not None and pkt.icmp_type is not None:
            icmp_type = pkt.icmp_type
            is_ns = is_icmp6 & (icmp_type == ICMP6_NS)
            nd_target = pkt.nd_target if pkt.nd_target is not None \
                else jnp.zeros_like(pkt.saddr)
            target_is_router = jnp.all(
                nd_target == tables.router_ip6[None, :], axis=1)
            ns_answer = is_ns & target_is_router
            ns_unknown = is_ns & ~target_is_router
            echo_answer = is_icmp6 & (icmp_type == ICMP6_ECHO_REQUEST) & \
                jnp.all(pkt.daddr == tables.router_ip6[None, :], axis=1)
            icmp6_handled = ns_answer | ns_unknown | echo_answer
        else:
            icmp_type = jnp.zeros(b, jnp.int32)
            ns_answer = ns_unknown = echo_answer = jnp.zeros(b, bool)
            icmp6_handled = jnp.zeros(b, bool)

        # 2. Service LB DNAT (lb.h lb6_local).
        if lb6_probe > 0 and tables.lb6 is not None:
            daddr, dport, rev_nat, _is_svc = lb6_step(
                tables.lb6, pkt.daddr, pkt.dport, pkt.proto, pkt.saddr,
                pkt.sport, max_probe=lb6_probe)
        else:
            daddr, dport = pkt.daddr, pkt.dport
            rev_nat = jnp.zeros(b, jnp.int32)

    # 3. Conntrack on the DNAT'd folded tuple (separate v6 table).
    with jax.named_scope("ct"):
        ctb = CTBatch(saddr=fold6(pkt.saddr), daddr=fold6(daddr),
                      sport=pkt.sport, dport=dport, proto=pkt.proto,
                      direction=pkt.direction, tcp_flags=pkt.tcp_flags,
                      related=jnp.zeros_like(pkt.proto))

    # 4. ipcache6: identity of the peer (src on ingress, dst on egress).
    with jax.named_scope("ipcache"):
        peer = jnp.where((pkt.direction == 0)[:, None], pkt.saddr, daddr)
        if tables.ipcache6.kb.shape[0] > 0:
            found, ident = lpm6_lookup(
                tables.ipcache6.masks, tables.ipcache6.k0,
                tables.ipcache6.k1, tables.ipcache6.k2, tables.ipcache6.k3,
                tables.ipcache6.kb, tables.ipcache6.value,
                tables.ipcache6.plens, peer, lpm6_probe)
        else:
            found = jnp.zeros(b, bool)
            ident = jnp.zeros(b, jnp.int32)
        identity = jnp.where(found, ident, jnp.int32(WORLD_IDENTITY))
        if pkt.from_overlay is not None:
            decap = (pkt.from_overlay != 0) & (pkt.direction == 0)
            identity = jnp.where(decap, pkt.tunnel_id, identity)
        if pkt.mark_identity is not None:
            # proxy-mark re-entry (bpf_netdev.c:128-146), same as v4
            identity = jnp.where(pkt.mark_identity > 0,
                                 pkt.mark_identity, identity)

    # 5. Policy verdict on the shared (family-agnostic) tables —
    # against the DNAT'd port, like the v4 path.
    with jax.named_scope("policy"):
        vb = PacketBatch(endpoint=pkt.endpoint, identity=identity,
                         dport=dport, proto=pkt.proto,
                         direction=pkt.direction, length=pkt.length,
                         is_fragment=pkt.is_fragment)
        if with_provenance or with_l7_fast:
            pol_verdict, counters, pol_slot, pol_tier = verdict_step(
                tables.key_id, tables.key_meta, tables.value, counters,
                vb, policy_probe, count_mask=~icmp6_handled,
                with_provenance=True)
        else:
            pol_verdict, counters = verdict_step(
                tables.key_id, tables.key_meta, tables.value, counters, vb,
                policy_probe, count_mask=~icmp6_handled)

    # 5.5 On-device L7 fast verdict (same stage as the v4 family).
    with jax.named_scope("l7"):
        if with_l7_fast:
            pol_verdict, l7_fast_allow, l7_fast_deny = _l7_fast_stage(
                tables, payload, pol_verdict, pol_slot, k=l7_k, c1=l7_c1)

    # 6. CT step, creation gated on the verdict; new entries record the
    # flow's rev-NAT index so replies can restore the VIP.  Locally
    # answered ICMPv6 never creates CT state (the reply is synthesized,
    # not forwarded).
    with jax.named_scope("ct"):
        create_ok = (pol_verdict >= 0) & ~pf_hit & ~icmp6_handled
        proxy_in = jnp.maximum(pol_verdict, 0)
        ct_verdict, ct_rev_nat, ct_proxy, ct = ct_step(
            ct, ctb, now, create_ok, update_mask=~pf_hit & ~icmp6_handled,
            rev_nat_in=rev_nat, proxy_port_in=proxy_in,
            slots=ct_slots, max_probe=ct_probe)

    with jax.named_scope("verdict"):
        established = ct_verdict != CT_NEW
        verdict = jnp.where(
            pf_hit, jnp.int32(VERDICT_DROP),
            jnp.where(ns_unknown, jnp.int32(VERDICT_DROP),
                      jnp.where(ns_answer | echo_answer, jnp.int32(0),
                                jnp.where(established, ct_proxy,
                                          pol_verdict))))

    # 6.5 Inline threat scoring (same fused stage as the v4 family;
    # addresses enter the tuple hash as their CT folds).  Locally
    # answered ICMPv6 rows are scored but exempt from overrides — the
    # responder's reply is synthesized, not forwarded.
    with jax.named_scope("threat"):
        if with_threat:
            from ..threat.stage import threat_stage
            t_src, t_dst = _flow_identities(tables.ep_identity,
                                            pkt.endpoint, identity,
                                            pkt.direction)
            verdict, threat, threat_out, thr_drop, thr_redir, rl_drop = \
                threat_stage(
                    tables, threat, flows, verdict,
                    identity=identity, dport=dport, proto=pkt.proto,
                    tcp_flags=pkt.tcp_flags, length=pkt.length,
                    is_fragment=pkt.is_fragment, established=established,
                    saddr_w=ctb.saddr, daddr_w=ctb.daddr, sport=pkt.sport,
                    flow_src=t_src, flow_dst=t_dst, now=now,
                    window_s=threat_window_s, flow_slots=flow_slots,
                    flow_probe=flow_probe, stripe=threat_stripe,
                    exempt=icmp6_handled)

    # 7. Reply-path reverse NAT (lb6_rev_nat).
    with jax.named_scope("revnat"):
        from .conntrack import CT_RELATED, CT_REPLY
        is_reply = (ct_verdict == CT_REPLY) | (ct_verdict == CT_RELATED)
        rn = jnp.where(is_reply, ct_rev_nat, jnp.int32(0))
        if tables.lb6 is not None:
            nat_saddr, nat_sport = lb6_rev_nat(tables.lb6, pkt.saddr,
                                               pkt.sport, rn)
        else:
            nat_saddr, nat_sport = pkt.saddr, pkt.sport

    with jax.named_scope("verdict"):
        event = jnp.where(
            pf_hit, jnp.int32(DROP_PREFILTER),
            jnp.where(ns_answer, jnp.int32(ICMP6_NS_REPLY),
            jnp.where(echo_answer, jnp.int32(ICMP6_ECHO_REPLY),
            jnp.where(ns_unknown, jnp.int32(DROP_UNKNOWN_TARGET),
            jnp.where(verdict == VERDICT_DROP_FRAG,
                      jnp.int32(DROP_FRAG_NOSUPPORT),
                      jnp.where(verdict < 0, jnp.int32(DROP_POLICY),
                                jnp.where(verdict > 0,
                                          jnp.int32(TRACE_TO_PROXY),
                                          jnp.int32(TRACE_TO_LXC))))))))
        if with_l7_fast:
            event = jnp.where(verdict == jnp.int32(VERDICT_DROP_L7),
                              jnp.int32(DROP_POLICY_L7), event)
        if with_threat:
            event = jnp.where(verdict == jnp.int32(VERDICT_DROP_THREAT),
                              jnp.int32(DROP_THREAT), event)

    # 7.5 Fused traffic analytics (same stage as the v4 family; the
    # address words enter the flow hash and dst-prefix key as their CT
    # folds — deterministic, shared with the oracle).
    with jax.named_scope("analytics"):
        if with_analytics:
            from ..analytics.stage import analytics_stage
            analytics = analytics_stage(
                analytics, identity=identity, dport=dport, proto=pkt.proto,
                sport=pkt.sport, length=pkt.length, verdict=verdict,
                saddr_key=ctb.saddr, daddr_key=ctb.daddr, now=now,
                depth=analytics_depth, lanes=analytics_lanes,
                stripe=analytics_stripe)
    nat = NAT6Result(daddr=daddr, dport=dport, saddr=nat_saddr,
                     sport=nat_sport, rev_nat=ct_rev_nat)
    out = (verdict, event, identity, nat, ct, counters)
    if flows is not None and flow_slots > 0:
        # Hubble flow aggregation, v6 twin (flow keys are identity-
        # based, so the table is family-agnostic like the policy
        # tables; locally answered ICMPv6 still aggregates, under its
        # reply event code).
        with jax.named_scope("flows"):
            from ..hubble.aggregation import flow_update_step
            src_id, dst_id = _flow_identities(tables.ep_identity,
                                              pkt.endpoint, identity,
                                              pkt.direction)
            flows = flow_update_step(
                flows, src_id, dst_id, dport, pkt.proto, event,
                pkt.length, now, slots=flow_slots, max_probe=flow_probe,
                claim_budget=flow_claim_budget)
        out = out + (flows,)
    if with_threat:
        out = out + (threat, threat_out)
    if with_analytics:
        out = out + (analytics,)
    if with_provenance:
        # Provenance finalization, mirroring the v6 verdict
        # precedence: prefilter, then the local ICMPv6 responder
        # (answered OR unknown-target dropped — either way the local
        # service tier decided, not policy), then CT, then policy.
        from .events import (TIER_CT_ESTABLISHED, TIER_LB,
                             TIER_PREFILTER)
        if with_l7_fast:
            from .events import TIER_L7_FAST_ALLOW, TIER_L7_FAST_DENY
            pol_tier = jnp.where(
                l7_fast_allow, jnp.int32(TIER_L7_FAST_ALLOW),
                jnp.where(l7_fast_deny, jnp.int32(TIER_L7_FAST_DENY),
                          pol_tier))
        tier = jnp.where(
            pf_hit, jnp.int32(TIER_PREFILTER),
            jnp.where(icmp6_handled, jnp.int32(TIER_LB),
                      jnp.where(established,
                                jnp.int32(TIER_CT_ESTABLISHED),
                                pol_tier)))
        slot = jnp.where(pf_hit | icmp6_handled | established,
                         jnp.int32(-1), pol_slot)
        if with_threat:
            from .events import (TIER_THREAT_DROP,
                                 TIER_THREAT_RATELIMIT,
                                 TIER_THREAT_REDIRECT)
            tier = jnp.where(
                rl_drop, jnp.int32(TIER_THREAT_RATELIMIT),
                jnp.where(thr_drop, jnp.int32(TIER_THREAT_DROP),
                          jnp.where(thr_redir,
                                    jnp.int32(TIER_THREAT_REDIRECT),
                                    tier)))
        out = out + (slot, tier)
    return out
