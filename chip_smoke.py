#!/usr/bin/env python
"""Chip smoke: the served verdict path on one TPU chip, end to end.

    python chip_smoke.py [--seed N]          # one chip: agent + node-share
    python chip_smoke.py --chips 4 [--seed N]  # the sharded dataplane only

One process holds the chip for its whole life.  Phases:

- **agent**: ``Daemon()`` with the default ``DaemonConfig`` (supervision,
  Hubble flows and IPv4+IPv6 on) behind ``APIServer``; endpoints over
  REST, a JSON rule set with an L7 HTTP rule over the CLI, records
  through the daemon's serving lane, every verdict checked against the
  realized-state oracle and ``policy_trace_replay``, and ``/healthz``
  read for the device it reports.
- **node-share**: one node's share of a 5,000-node / 150,000-pod
  Kubernetes cluster (SIG-scalability thresholds, 110 pods per node) at
  the reference's map capacities (BASELINE.md): 110 endpoints x 16,384
  policymap entries, 150,000 pod /32s + 2,048 CIDRs in the ipcache,
  65,536 CT slots, flow aggregation on.  Seeded SYN first packets
  (half installed identity/port pairs, half misses) at buckets 1, 256,
  4,096 and 32,768 through ``VerdictDispatcher``, then the same
  5-tuples again (the CT-established path), and one IPv6 batch through
  ``process6``; first packets are checked against
  ``host_fail_static_step`` / ``oracle_verdict``, repeats against the
  first verdicts.
- **sharded** (``--chips 4`` only): ``ShardedDatapath`` with 4 shards of
  the node-share size, the ipcache replicated, each shard's buffers on
  its own chip.

Every phase also reads the supervisors: a fail-static batch or a breaker
that is not closed means the host oracle answered instead of the chip,
and fails the run.  Data comes from ``--seed``.  The last stdout line is
the result; it is printed only when every phase passed on a TPU.
"""

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

import numpy as np

# one node's share of the cluster (module docstring) and a CPU-sized cut
# of the same shapes for the rehearsal tests
NODE_SHARE = {"endpoints": 110, "entries": 16_384, "pods": 150_000,
              "cidrs": 2_048, "ct_slots": 1 << 16,
              "buckets": (1, 256, 4096, 32768), "v6_batch": 4096}
TINY = {"endpoints": 6, "entries": 96, "pods": 600, "cidrs": 24,
        "ct_slots": 1 << 12, "buckets": (1, 64, 256), "v6_batch": 64}

# requests served per bucket size (each one a ticket of that many
# records); about 4,000 requests in all at the node-share size
REQUESTS = {1: 64, 256: 12, 4096: 2, 32768: 1}

_WORLD = 2


class SmokeError(RuntimeError):
    """A check failed: the run prints no result."""


def _check(cond, msg):
    if not cond:
        raise SmokeError(msg)


class JaxCounters:
    """Compile wall seconds (the union of JAX's trace, lowering and
    backend-compile spans, so shard lanes compiling at once count
    once) and persistent-cache hits/misses, from JAX's own monitoring
    events, for the span of a ``with`` block."""

    _COMPILE = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax.monitoring as mon
        self._spans = []
        self.hits = 0
        self.misses = 0

        def on_span(event, start, end, **_kw):
            if event in self._COMPILE:
                self._spans.append((start, end))

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        self._span, self._ev = on_span, on_event
        mon.register_event_time_span_listener(on_span)
        mon.register_event_listener(on_event)
        return self

    @property
    def compile_s(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_time_span_listener(self._span)
        mon.unregister_event_listener(self._ev)
        return False


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_supervision(status) -> list:
    """Every serving lane ran, on the device: no fail-static batch, no
    fail-closed batch, every breaker closed.  ``status`` is
    ``supervision_status()`` of a Datapath or a ShardedDatapath."""
    lanes = list(status["shards"].values()) if "shards" in status \
        else [status]
    out = []
    for st in lanes:
        serving = st.get("serving")
        _check(serving is not None, "a serving lane never ran")
        sup = serving.get("supervisor")
        _check(sup is not None, "a serving lane runs unsupervised")
        fail_static = sup["fail-static"]["batches"]
        _check(fail_static == 0 and sup["breaker"] == "closed",
               f"lane {serving['lane']}: fail-static batches "
               f"{fail_static}, breaker {sup['breaker']}, last fault "
               f"{sup['last-fault']}")
        _check(serving["static-batches"] == 0 and serving["errors"] == 0,
               f"lane {serving['lane']}: {serving['errors']} failed "
               f"batches")
        out.append({"lane": serving["lane"], "batches": serving["batches"],
                    "breaker": sup["breaker"],
                    "fail_static_batches": fail_static})
    _check(status["mode"] == "ok", f"dataplane mode {status['mode']}")
    return out


def _serve(lane, soa, n):
    ticket = lane.submit_records({k: v.copy() for k, v in soa.items()}, n)
    verdict, identity = ticket.result(timeout=600)
    _check(ticket.error is None, f"serving lane error: {ticket.error!r}")
    return np.asarray(verdict), np.asarray(identity)


def _phase_line(name, t0, build_s, jc, **extra):
    total = time.perf_counter() - t0
    return {"phase": name, "seconds": round(total, 3),
            "build_s": round(build_s, 3),
            "compile_s": round(jc.compile_s, 3),
            "run_s": round(total - build_s - jc.compile_s, 3),
            "cache_hits": jc.hits, "cache_misses": jc.misses,
            "peak_bytes_in_use": _peak_bytes(), **extra}


# --------------------------------------------------------------- agent

AGENT_RULES = [
    {"endpointSelector": {"matchLabels": {"app": "web"}},
     "ingress": [
         {"fromEndpoints": [{"matchLabels": {"app": "client"}}]},
         {"fromEndpoints": [{"matchLabels": {"app": "api"}}],
          "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}],
                       "rules": {"http": [{"method": "GET",
                                           "path": "/public/.*"}]}}]},
         {"toPorts": [{"ports": [{"port": "53", "protocol": "UDP"}]}]}],
     "labels": ["k8s:policy=smoke-web"]},
    {"endpointSelector": {"matchLabels": {"app": "client"}},
     "egress": [{"toEndpoints": [{"matchLabels": {"app": "web"}}],
                 "toPorts": [{"ports": [{"port": "80",
                                         "protocol": "TCP"}]}]}],
     "labels": ["k8s:policy=smoke-client"]},
]

AGENT_ENDPOINTS = ((101, "10.200.1.1", "web"), (102, "10.200.1.2", "client"),
                   (103, "10.200.1.3", "client"), (104, "10.200.1.4", "api"),
                   (105, "10.200.1.5", "batch"))


def _u32(ip: str) -> int:
    a, b, c, d = (int(x) for x in ip.split("."))
    return (a << 24) | (b << 16) | (c << 8) | d


def _ip(u: int) -> str:
    return f"{u >> 24}.{(u >> 16) & 255}.{(u >> 8) & 255}.{u & 255}"


def agent_phase(seed: int, requests: int = 6, per_request: int = 48):
    """Drive the agent through its normal entry points; see the module
    docstring.  Returns the phase's result line."""
    from cilium_tpu.cli import Client, main as cli_main
    from cilium_tpu.compiler.policy_tables import oracle_provenance
    from cilium_tpu.daemon import Daemon
    from cilium_tpu.daemon.rest import APIServer
    from cilium_tpu.policy.mapstate import PolicyMapState
    from cilium_tpu.utils.option import DaemonConfig
    from cilium_tpu.utils.platform import require_device

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    with JaxCounters() as jc, tempfile.TemporaryDirectory() as tmp:
        # the default config; only the state directory (a deployment
        # path) moves into a scratch dir
        d = Daemon(config=DaemonConfig(state_dir=os.path.join(tmp, "s")))
        srv = APIServer(d).start()
        try:
            c = Client(srv.base_url)
            for eid, ip, app in AGENT_ENDPOINTS:
                c.put(f"/endpoint/{eid}", {"ipv4": ip,
                                           "labels": [f"k8s:app={app}"]})
            rules = os.path.join(tmp, "rules.json")
            with open(rules, "w") as f:
                json.dump(AGENT_RULES, f)
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                rc = cli_main(["--api", srv.base_url, "policy", "import",
                               rules])
            _check(rc == 0, f"policy import exited {rc}")
            rev = int(out.getvalue().split("Revision:")[1].split()[0])
            _check(d.wait_for_policy_revision(rev, timeout=120),
                   f"policy revision {rev} not realized")
            build_s = time.perf_counter() - t0 - jc.compile_s

            eps = {eid: d.endpoints.lookup(eid)
                   for eid, _ip_, _app in AGENT_ENDPOINTS}
            web, clients = eps[101], (eps[102], eps[103])
            peers = [ip for _e, ip, _a in AGENT_ENDPOINTS] + \
                ["192.0.2.7", "198.51.100.9"]
            lane = d.datapath.serving()
            checked = 0
            replays = {}
            sport = 20000
            for _ in range(requests):
                n = per_request
                ingress = rng.random(n) < 0.7
                local = np.where(ingress, 0, rng.integers(0, 2, n))
                slot = np.array([web.table_slot if ing else
                                 clients[k].table_slot
                                 for ing, k in zip(ingress, local)],
                                np.int32)
                local_ip = np.array([_u32(web.ipv4) if ing else
                                     _u32(clients[k].ipv4)
                                     for ing, k in zip(ingress, local)],
                                    np.uint32)
                peer = np.array([_u32(peers[i]) for i in
                                 rng.integers(0, len(peers), n)],
                                np.uint32)
                dport = rng.choice([80, 53, 22, 443, 8080], n)
                proto = np.where(dport == 53, 17, 6)
                soa = {
                    "endpoint": slot,
                    "saddr": np.where(ingress, peer, local_ip)
                    .view(np.int32),
                    "daddr": np.where(ingress, local_ip, peer)
                    .view(np.int32),
                    "sport": (sport + np.arange(n)).astype(np.int32),
                    "dport": dport.astype(np.int32),
                    "proto": proto.astype(np.int32),
                    "direction": np.where(ingress, 0, 1).astype(np.int32),
                    "tcp_flags": np.where(proto == 6, 0x02, 0)
                    .astype(np.int32),
                    "length": np.full(n, 256, np.int32),
                    "is_fragment": np.zeros(n, np.int32)}
                sport += n
                verdict, identity = _serve(lane, soa, n)
                for j in range(n):
                    ep = web if ingress[j] else clients[local[j]]
                    want_id = d.ipcache.lookup_longest_prefix(
                        _ip(int(peer[j]))) or _WORLD
                    _check(identity[j] == want_id,
                           f"agent: identity {identity[j]} != {want_id}")
                    dirn = 0 if ingress[j] else 1
                    want, _tier, _key = oracle_provenance(
                        PolicyMapState(ep.realized), int(want_id),
                        int(dport[j]), int(proto[j]), dirn)
                    _check(verdict[j] == want,
                           f"agent: verdict {verdict[j]} != oracle {want} "
                           f"(ep {ep.id}, id {want_id}, dport {dport[j]})")
                    replays[(ep.id, int(want_id), int(dport[j]),
                             int(proto[j]), dirn)] = int(verdict[j])
                    checked += 1
            for (eid, ident, dp_, pr, dirn), served in replays.items():
                r = d.policy_trace_replay(
                    eid, identity=ident, dport=dp_, proto=pr,
                    direction="ingress" if dirn == 0 else "egress")
                _check(not r["drift"] and
                       r["device"]["verdict"] == served,
                       f"agent: replay of {(eid, ident, dp_, pr, dirn)} "
                       f"drift={r['drift']} device="
                       f"{r['device']['verdict']} served={served}")
            verdicts = {"allow": 0, "redirect": 0, "drop": 0}
            for v in replays.values():
                verdicts["drop" if v < 0 else
                         "redirect" if v > 0 else "allow"] += 1
            _check(min(verdicts.values()) > 0,
                   f"agent: traffic missed a verdict kind {verdicts}")
            lanes = check_supervision(d.datapath.supervision_status())
            feats = c.get("/healthz")["features"]
            platform, kind, _count = require_device()
            _check(feats.get("on_accelerator") == (platform != "cpu")
                   and feats.get("device_kind") == kind,
                   f"agent: /healthz features {feats}")
        finally:
            srv.shutdown()
            d.shutdown()
    return _phase_line("agent", t0, build_s, jc, records=checked,
                       replayed=len(replays), verdict_kinds=verdicts,
                       lanes=lanes,
                       healthz={"on_accelerator": feats["on_accelerator"],
                                "device_kind": feats["device_kind"]})


# ---------------------------------------------------------- node share

class NodeShare:
    """Seeded policy, ipcache and traffic of one node's share."""

    def __init__(self, rng, endpoints, entries, pods, cidrs):
        from cilium_tpu.policy.mapstate import (PolicyKey, PolicyMapState,
                                                PolicyMapStateEntry)
        self.rng = rng
        pod_addr = (np.uint32(0x0A000000) + rng.choice(
            np.uint32(1 << 24), pods, replace=False)).astype(np.uint32)
        pod_id = rng.integers(256, 65536, pods)
        self.prefixes = {f"{_ip(int(a))}/32": int(i)
                         for a, i in zip(pod_addr, pod_id)}
        cidr = {}
        while len(cidr) < cidrs:
            plen = int(rng.choice([16, 20, 24, 28]))
            a = int(rng.integers(11, 224)) << 24 | \
                int(rng.integers(0, 1 << 24))
            a &= (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF
            cidr[f"{_ip(a)}/{plen}"] = int(rng.integers(256, 65536))
        self.prefixes.update(cidr)
        # one address per pod identity: installed keys become traffic
        self.addr_of = np.zeros(65536, np.uint32)
        self.addr_of[pod_id] = pod_addr
        self.pod_addr = pod_addr
        # per endpoint: exact (identity, port, proto, dir) keys, ~5%
        # L3-only, ~1% L4-wildcard, ~5% redirecting to a proxy port
        self.states, self.keys = [], []
        for _ in range(endpoints):
            st = PolicyMapState()
            ident = np.where(rng.random(entries) < 0.9,
                             rng.choice(pod_id, entries),
                             rng.integers(256, 65536, entries))
            kind = rng.random(entries)
            ident = np.where(kind < 0.01, 0, ident)
            port = np.where((kind >= 0.01) & (kind < 0.06), 0,
                            rng.integers(1, 65536, entries))
            proto = np.where(port == 0, 0,
                             rng.choice([6, 17], entries, p=[0.8, 0.2]))
            dirn = rng.integers(0, 2, entries)
            proxy = np.where(rng.random(entries) < 0.05,
                             rng.integers(10000, 20000, entries), 0)
            proxy = np.where(port == 0, 0, proxy)
            rows = []
            for i, p, pr, d_, px in zip(ident.tolist(), port.tolist(),
                                        proto.tolist(), dirn.tolist(),
                                        proxy.tolist()):
                key = PolicyKey(identity=i, dest_port=p, nexthdr=pr,
                                direction=d_)
                if key not in st:
                    st[key] = PolicyMapStateEntry(proxy_port=px)
                    rows.append((i, p, pr, d_))
            while len(st) < entries:  # top up past duplicate draws
                i = int(rng.choice(pod_id))
                p = int(rng.integers(1, 65536))
                key = PolicyKey(identity=i, dest_port=p, nexthdr=6,
                                direction=int(rng.integers(0, 2)))
                if key not in st:
                    st[key] = PolicyMapStateEntry()
                    rows.append((i, p, 6, key.direction))
            self.states.append(st)
            self.keys.append(np.array(rows, np.int64))
        self._flow = 0

    def traffic(self, n, copies=1):
        """``n`` SYN first packets, unique 5-tuples: half from installed
        keys (peer address carries the key's identity), half misses.
        With ``copies`` > 1 the table holds each state that many times
        in a row (slot ``g`` serves state ``g // copies``)."""
        rng = self.rng
        ep = rng.integers(0, len(self.states) * copies, n)
        hit = rng.random(n) < 0.5
        ident = np.zeros(n, np.int64)
        dport = rng.integers(1, 65536, n)
        proto = rng.choice([6, 17], n, p=[0.8, 0.2])
        dirn = rng.integers(0, 2, n)
        peer = np.where(rng.random(n) < 0.5,
                        rng.choice(self.pod_addr, n),
                        rng.integers(0, 1 << 32, n, dtype=np.uint32))
        for j in np.flatnonzero(hit):
            keys = self.keys[ep[j] // copies]
            i, p, pr, d_ = keys[rng.integers(0, len(keys))]
            if i and self.addr_of[i]:
                peer[j] = self.addr_of[i]
            if p:
                dport[j], proto[j] = p, pr
            dirn[j] = d_
        # unique (local address, sport) per flow => unique 5-tuples
        flow = self._flow + np.arange(n)
        self._flow += n
        local = (np.uint32(0x0AFF0000) + (flow // 64000)).astype(np.uint32)
        sport = 1024 + flow % 64000
        ingress = dirn == 0
        return {
            "endpoint": ep.astype(np.int32),
            "saddr": np.where(ingress, peer, local).astype(np.uint32)
            .view(np.int32),
            "daddr": np.where(ingress, local, peer).astype(np.uint32)
            .view(np.int32),
            "sport": sport.astype(np.int32),
            "dport": dport.astype(np.int32),
            "proto": proto.astype(np.int32),
            "direction": dirn.astype(np.int32),
            "tcp_flags": np.where(proto == 6, 0x02, 0).astype(np.int32),
            "length": rng.choice([64, 256, 1500], n).astype(np.int32),
            "is_fragment": np.zeros(n, np.int32)}


def host_lpm(prefixes):
    """Plain host LPM over the ipcache prefixes: addr -> identity."""
    by_len = {}
    for cidr, ident in prefixes.items():
        addr, plen = cidr.split("/")
        plen = int(plen)
        mask = (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF
        by_len.setdefault(plen, (mask, {}))[1][_u32(addr) & mask] = ident
    order = [by_len[p] for p in sorted(by_len, reverse=True)]

    def identity_of(addr):
        for mask, table in order:
            ident = table.get(addr & mask)
            if ident is not None:
                return ident
        return _WORLD
    return identity_of


def reference_first(soa, n, states, identity_of):
    """First-packet reference: the fail-static precedence with no CT
    entry, the scalar policy oracle over the same map states."""
    from cilium_tpu.compiler.policy_tables import oracle_verdict
    from cilium_tpu.datapath.pipeline import host_fail_static_step
    return host_fail_static_step(
        soa, n, established=lambda *a: None, identity_of=identity_of,
        policy_verdict=lambda slot, ident, dport, proto, dirn:
        oracle_verdict(states[slot], ident, dport, proto, dirn))


def serve_and_check(lane, share, identity_of, states, buckets,
                    copies=1):
    """Per bucket: SYN first packets checked against the reference,
    then the same 5-tuples again checked against the first verdicts.
    Returns ({bucket: records}, {verdict kind: first packets})."""
    served = {}
    kinds = {"allow": 0, "redirect": 0, "drop": 0}
    for bucket in buckets:
        records = 0
        for _ in range(REQUESTS.get(bucket, 2)):
            soa = share.traffic(bucket, copies)
            want_v, want_i = reference_first(soa, bucket, states,
                                             identity_of)
            v1, i1 = _serve(lane, soa, bucket)
            bad = np.flatnonzero((v1 != want_v) | (i1 != want_i))
            _check(bad.size == 0,
                   f"bucket {bucket}: {bad.size} first-packet verdicts "
                   f"differ from the oracle, e.g. row {bad[:1]}: device "
                   f"{v1[bad[:1]]}/{i1[bad[:1]]} oracle "
                   f"{want_v[bad[:1]]}/{want_i[bad[:1]]}")
            kinds["allow"] += int((v1 == 0).sum())
            kinds["redirect"] += int((v1 > 0).sum())
            kinds["drop"] += int((v1 < 0).sum())
            soa["tcp_flags"] = np.where(soa["proto"] == 6, 0x10, 0) \
                .astype(np.int32)
            v2, i2 = _serve(lane, soa, bucket)
            bad = np.flatnonzero((v2 != v1) | (i2 != i1))
            _check(bad.size == 0,
                   f"bucket {bucket}: {bad.size} repeat verdicts differ "
                   f"from the first")
            records += 2 * bucket
        served[bucket] = records
    _check(min(kinds.values()) > 0,
           f"traffic missed a verdict kind: {kinds}")
    return served, kinds


def _v6_words(i):
    """f00d::<hi>:<lo> for pod index i, as [4] int32 words."""
    return [0xF00D0000 - (1 << 32), 0, 0, int(i)]


def node_share_phase(seed: int, size=NODE_SHARE):
    """One node's share at the reference capacities; see the module
    docstring.  Returns the phase's result line."""
    from cilium_tpu.compiler.policy_tables import oracle_verdict
    from cilium_tpu.datapath.engine import Datapath, make_full_batch6

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    with JaxCounters() as jc:
        share = NodeShare(rng, size["endpoints"], size["entries"],
                          size["pods"], size["cidrs"])
        dp = Datapath(ct_slots=size["ct_slots"])
        dp.enable_flow_aggregation()  # as the daemon's default config
        # v6 pods: f00d::<i>/128 -> the identity of v4 pod i (loaded
        # before the policy so the step compiles once)
        n6 = min(2048, size["pods"])
        ids6 = np.array([share.prefixes[f"{_ip(int(a))}/32"]
                         for a in share.pod_addr[:n6]])
        prefixes6 = {f"f00d::{i >> 16:x}:{i & 0xFFFF:x}/128": int(ids6[i])
                     for i in range(n6)}
        dp.load_ipcache6(prefixes6)
        dp.load_policy(share.states, revision=1,
                       ipcache_prefixes=share.prefixes)
        build_s = time.perf_counter() - t0 - jc.compile_s
        identity_of = host_lpm(share.prefixes)
        served, kinds = serve_and_check(dp.serving(), share, identity_of,
                                        share.states, size["buckets"])

        # one IPv6 batch through process6: first packets from v6 pods
        # (and unknown v6 peers -> world), checked like v4
        n = size["v6_batch"]
        pod = rng.integers(0, 2 * n6, n)  # half beyond the v6 pods
        soa = share.traffic(n)
        peer = np.array([_v6_words(i) for i in pod], np.int64) \
            .astype(np.int32)
        local = np.tile(np.array(_v6_words(0xFFFFFFF), np.int64)
                        .astype(np.int32), (n, 1))
        ingress = (soa["direction"] == 0)[:, None]
        verdict, _event, identity, _nat = dp.process6(make_full_batch6(
            endpoint=soa["endpoint"], saddr=np.where(ingress, peer, local),
            daddr=np.where(ingress, local, peer), sport=soa["sport"],
            dport=soa["dport"], proto=soa["proto"],
            direction=soa["direction"], tcp_flags=soa["tcp_flags"],
            length=soa["length"]))
        verdict, identity = np.asarray(verdict), np.asarray(identity)
        for j in range(n):
            want_id = int(ids6[pod[j]]) if pod[j] < n6 else _WORLD
            want = oracle_verdict(share.states[soa["endpoint"][j]],
                                  want_id, int(soa["dport"][j]),
                                  int(soa["proto"][j]),
                                  int(soa["direction"][j]))
            _check(identity[j] == want_id and verdict[j] == want,
                   f"v6 row {j}: device {verdict[j]}/{identity[j]} "
                   f"oracle {want}/{want_id}")
        lanes = check_supervision(dp.supervision_status())
        sizes = {"endpoints": len(share.states),
                 "entries_per_endpoint": min(len(s) for s in share.states),
                 "policy_entries": sum(len(s) for s in share.states),
                 "ipcache_prefixes": len(share.prefixes),
                 "ipcache6_prefixes": len(prefixes6),
                 "ct_slots": dp.ct.slots}
    return _phase_line("node-share", t0, build_s, jc,
                       records=sum(served.values()) + n,
                       records_by_bucket=served, first_verdicts=kinds,
                       v6_records=n,
                       sizes=sizes, lanes=lanes)


# -------------------------------------------------------------- sharded

def sharded_phase(seed: int, size=NODE_SHARE, n_shards: int = 4):
    """``ShardedDatapath`` over ``n_shards`` chips, each shard holding
    the node-share size, the ipcache replicated; verdicts checked as in
    the node-share phase and every shard's buffers on its own chip."""
    import jax
    from cilium_tpu.parallel.sharded import ShardedDatapath

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    with JaxCounters() as jc:
        share = NodeShare(rng, size["endpoints"], size["entries"],
                          size["pods"], size["cidrs"])
        # global slot g -> shard g % n, local slot g // n: slot g holds
        # state g // n, so every shard serves the whole state list
        states = [st for st in share.states for _ in range(n_shards)]
        sd = ShardedDatapath(n_shards=n_shards, n_devices=n_shards,
                             ct_slots=size["ct_slots"])
        sd.enable_flow_aggregation()
        sd.load_policy(states, revision=1,
                       ipcache_prefixes=share.prefixes)
        build_s = time.perf_counter() - t0 - jc.compile_s
        served, kinds = serve_and_check(sd.serving(), share,
                                        host_lpm(share.prefixes), states,
                                        size["buckets"], copies=n_shards)
        lanes = check_supervision(sd.supervision_status())
        placement = []
        for k, sh in enumerate(sd.shards):
            devs = set()
            for buf in (*sh._tbufs4, *jax.tree_util.tree_leaves(
                    sh.ct.state)):
                devs |= buf.devices()
            _check(len(devs) == 1,
                   f"shard {k} spans devices {sorted(devs, key=str)}")
            placement.append(next(iter(devs)))
        _check(len(set(placement)) == n_shards,
               f"shards share devices: {placement}")
        in_use = []
        for dev in placement:
            stats = dev.memory_stats()
            if stats is not None:  # the CPU reports none
                _check(stats["bytes_in_use"] > 0, f"{dev} holds nothing")
                in_use.append(stats["bytes_in_use"])
    return _phase_line("sharded", t0, build_s, jc,
                       records=sum(served.values()),
                       records_by_bucket=served, first_verdicts=kinds,
                       shard_devices=[str(d) for d in placement],
                       bytes_in_use=in_use,
                       sizes={"shards": n_shards,
                              "endpoints_per_shard": size["endpoints"],
                              "entries_per_endpoint": size["entries"],
                              "ipcache_prefixes": len(share.prefixes)},
                       lanes=lanes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from cilium_tpu.utils.platform import (enable_compile_cache,
                                           require_device)
    platform, kind, count = require_device()
    # a CPU rehearsal runs the phase functions (tests/); the script's
    # own result only ever comes from a TPU
    _check(platform == "tpu", f"no TPU: JAX serves {platform}")
    cache_dir = enable_compile_cache()
    print(json.dumps({"device": {"platform": platform, "kind": kind,
                                 "count": count},
                      "compile_cache": cache_dir}), flush=True)
    if args.chips == 4:
        _check(count >= 4, f"--chips 4 needs 4 devices, found {count}")
        phases = [lambda: sharded_phase(args.seed)]
    else:
        phases = [lambda: agent_phase(args.seed),
                  lambda: node_share_phase(args.seed + 1)]
    for phase in phases:
        print(json.dumps(phase()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception as e:  # noqa: BLE001 — any failure: no result line
        import traceback
        traceback.print_exc()
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon and dispatcher threads must not hold the exit
    os._exit(rc)
