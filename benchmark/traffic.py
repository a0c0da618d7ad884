"""The one traffic generator: flows, frames and schedules from a mix
file (``traffic/<mix>.json``) and ``--seed``.

A *flow* is one connection between a client and a server; either side
is a local endpoint or a remote peer.  Packet ``k`` of a flow goes
client -> server (forward) or back (reply); each local side sees it as
one record: the sender's egress, the receiver's ingress.  A flow lives
``L`` packets: a SYN first; a TCP flow closes FIN, FIN back, last ACK
(one FIN last where it is shorter than five packets; a one-packet flow
is a bare SYN); it is then replaced by a new flow.  Tuples never repeat
within a run, so the conntrack entries of two flows never meet.

A *pool* holds the active flows in slots.  One *round* emits the next
packet of every flow in the pool.  Closed loop: each submitter owns a share of the pool, and each
of its frames is one round of that share.  Open loop: one pool, its
rounds in a fixed slot order cut into frames of drawn sizes, each frame
due at a drawn time.

Everything is drawn from the seed; the same seed gives the same flows,
frames and due times.  Mix keys are documented in ``PERF.md`` §4.

A frame is cut from one round, so it never holds two packets of one
flow: where two records of one flow meet in one launch, the step's
order among them is its own, and the reference could no longer name
one answer (``PERF.md`` §7).
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

FIELDS = ("endpoint", "saddr", "daddr", "sport", "dport", "proto",
          "direction", "tcp_flags", "length", "is_fragment")
SYN, ACK, FIN = 0x02, 0x10, 0x01
EPHEMERAL_LO, EPHEMERAL_SPAN = 1024, 63488   # probes use 64512-65511
# addresses the generator never gives a peer: warm-up records use them
RESERVED_LO, RESERVED_HI = 0xC6120000, 0xC6140000   # 198.18.0.0/15


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _mix64(x):
    """splitmix64 finaliser on uint64 arrays."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def hash3(seed: int, a, b):
    """A uint64 hash of (seed, a, b), elementwise."""
    with np.errstate(over="ignore"):
        s = _mix64(np.uint64(seed & 0xFFFFFFFFFFFFFFFF))
        x = _mix64(s ^ np.asarray(a, np.uint64) *
                   np.uint64(0x9E3779B97F4A7C15))
        return _mix64(x ^ np.asarray(b, np.uint64) *
                      np.uint64(0xC2B2AE3D27D4EB4F))


class FlowSource:
    """An endless, seeded stream of new flows for one submitter
    (``stream``).  Flow ``i`` of the stream is the same for a given
    (deployment, mix, seed, stream), however it is consumed.

    The default flows of a kind (``byname.py``).  A kind's
    ``flows/<kind>.py`` provides a ``FlowSource`` with what ``run.py``,
    ``Pool`` and ``packets`` use: ``FlowSource(dep, mix, seed, stream,
    streams)``; the attributes ``mix``, ``seed`` and ``stream``; and
    ``flows(start, n)``, a dict of ``n`` rows of ``c_ep`` and ``s_ep``
    (the client's and the server's local endpoint, -1 for a remote
    peer), ``caddr``, ``saddr``, ``cport``, ``sport``, ``proto`` and
    ``len`` (packets in the flow, at least 1).  Tuples must never
    repeat within a run (module docstring)."""

    CHUNK = 1 << 14

    def __init__(self, dep, mix, seed: int, stream: int,
                 streams: int = 1):
        self.dep, self.mix, self.seed, self.stream = dep, mix, seed, stream
        self._chunks = []
        e = dep.endpoints
        self._port_base = np.zeros(e, np.int64)
        self._port_span = EPHEMERAL_SPAN // max(1, streams)
        self._port_lo = EPHEMERAL_LO + stream * self._port_span
        self._local_slot = {int(a): j for j, a in
                            enumerate(dep.local_addr.tolist())}
        z = mix.get("zipf", 0.0)
        self._w = (np.arange(len(dep.pod_addr), dtype=np.float64) + 1) ** -z
        self._pod_cdf = np.cumsum(self._w) / self._w.sum()
        # hot_shift {"every_flows", "by"}: every that many flows of the
        # stream, the Zipf ranks move by ``by`` pods, so the hot set moves
        hs = mix.get("hot_shift")
        if hs and hs["every_flows"] % self.CHUNK:
            raise ValueError(f"hot_shift.every_flows: a multiple of "
                             f"{self.CHUNK}")
        self._hot = hs
        self._key_cdf = {}

    def _shift(self, chunk: int) -> int:
        if not self._hot:
            return 0
        epoch = chunk * self.CHUNK // self._hot["every_flows"]
        return epoch * self._hot["by"] % len(self.dep.pod_addr)

    def _key_cdfs(self, chunk: int):
        """Per endpoint: cumulative key weights, each key weighted by the
        Zipf weight of the most popular pod carrying its identity."""
        shift = self._shift(chunk)
        if shift not in self._key_cdf:
            dep, w = self.dep, self._w
            rank = np.full(1 << 16, -1, np.int64)
            ident = np.roll(dep.pod_ident, -shift)
            ranks = np.arange(len(ident))
            rank[ident[::-1]] = ranks[::-1]   # first (best) rank wins
            wk = np.where(rank >= 0, w[np.maximum(rank, 0)], w[len(w) // 2])
            cdfs = []
            for p in dep.policy:
                c = np.cumsum(wk[p["ident"]])
                cdfs.append(c / c[-1])
            self._key_cdf[shift] = cdfs
        return self._key_cdf[shift]

    def _zipf_pods(self, rng, n, chunk: int):
        r = np.searchsorted(self._pod_cdf, rng.random(n), side="right")
        r = np.minimum(r, len(self._pod_cdf) - 1)
        r = (r + self._shift(chunk)) % len(self._pod_cdf)
        return self.dep.pod_addr[r]

    def _draw(self, chunk: int) -> dict:
        dep, mix = self.dep, self.mix
        rng = np.random.default_rng([self.seed, 7, self.stream, chunk])
        n = self.CHUNK
        e = rng.integers(0, dep.endpoints, n)
        hit = rng.random(n) < mix.get("hit_share", 0.5)
        udp = mix.get("udp_share", 0.2)
        port = rng.integers(1, 65536, n)
        proto = np.where(rng.random(n) < udp, 17, 6)
        dirn = rng.integers(0, 2, n)
        peer = np.where(rng.random(n) < mix.get("pod_peer_share", 0.5),
                        self._zipf_pods(rng, n, chunk),
                        rng.integers(0x0B000000, 0xE0000000, n)
                        .astype(np.uint32)).astype(np.int64)
        peer = np.where((peer >= RESERVED_LO) & (peer < RESERVED_HI),
                        peer + (RESERVED_HI - RESERVED_LO), peer)
        zipf_peer = self._zipf_pods(rng, n, chunk).astype(np.int64)
        u = rng.random(n)
        for ep in np.unique(e[hit]):
            rows = np.flatnonzero(hit & (e == ep))
            pol = dep.policy[ep]
            kidx = np.searchsorted(self._key_cdfs(chunk)[ep], u[rows],
                                   side="right")
            kidx = np.minimum(kidx, len(pol["ident"]) - 1)
            ident = pol["ident"][kidx]
            kport = pol["port"][kidx]
            dirn[rows] = pol["dir"][kidx]
            port[rows] = np.where(kport > 0, kport, port[rows])
            proto[rows] = np.where(kport > 0, pol["proto"][kidx],
                                   proto[rows])
            addr = dep.addr_arr[ident]
            peer[rows] = np.where((ident > 0) & (addr >= 0), addr,
                                  zipf_peer[rows])
        local = dep.local_addr[e].astype(np.int64)
        ingress = dirn == 0
        # per-endpoint ephemeral ports, unique within the stream
        order = np.argsort(e, kind="stable")
        counts = np.bincount(e, minlength=dep.endpoints)
        starts = np.cumsum(counts) - counts
        rank_in = np.empty(n, np.int64)
        rank_in[order] = np.arange(n) - np.repeat(starts, counts)
        eph_n = self._port_base[e] + rank_in
        self._port_base += counts
        if eph_n.max(initial=0) >= self._port_span:
            raise RuntimeError("ephemeral ports exhausted: a tuple would "
                               "repeat; lengthen the flows or shorten "
                               "the run")
        eph = self._port_lo + eph_n
        fl = mix["flow_len"]
        if fl["kind"] == "fixed":
            length = np.full(n, int(fl["value"]), np.int64)
        else:  # lomax: heavy tail, mean fl["mean"], at least 2 packets
            a = float(fl["alpha"])
            xm = (float(fl["mean"]) - 2) * (a - 1)
            length = 2 + np.floor(
                xm * (rng.random(n) ** (-1 / a) - 1)).astype(np.int64)
            length = np.minimum(length, int(fl["max"]))
        caddr = np.where(ingress, peer, local)
        saddr = np.where(ingress, local, peer)
        c_ep = np.where(ingress, -1, e)
        s_ep = np.where(ingress, e, -1)
        # a peer that is itself a local endpoint is a second local side
        for addr, slot in self._local_slot.items():
            c_ep = np.where(caddr == addr, slot, c_ep)
            s_ep = np.where(saddr == addr, slot, s_ep)
        return {"c_ep": c_ep, "s_ep": s_ep, "caddr": caddr,
                "saddr": saddr, "cport": eph, "sport": port,
                "proto": proto, "len": length}

    def flows(self, start: int, n: int) -> dict:
        """Attributes of flows ``start .. start+n-1`` of the stream."""
        last = start + max(n, 1) - 1
        while len(self._chunks) <= last // self.CHUNK:
            self._chunks.append(self._draw(len(self._chunks)))
        c0 = start // self.CHUNK
        pick = np.arange(start, start + n) - c0 * self.CHUNK
        return {k: np.concatenate([self._chunks[c][k] for c in
                                   range(c0, last // self.CHUNK + 1)])[pick]
                for k in self._chunks[0]}


def packets(mix, seed: int, stream: int, fl: dict, idx, k):
    """Records of packet ``k`` of flows ``fl`` (attribute arrays, one row
    per packet, flow index ``idx`` in the stream).

    Returns (records dict of int32 arrays, meta dict: ``row`` = position
    in the input, ``side`` = 0 sender egress / 1 receiver ingress)."""
    idx = np.asarray(idx, np.int64)
    k = np.asarray(k, np.int64)
    last = fl["len"] - 1
    h = hash3(seed ^ (stream << 40), idx, k)
    if mix.get("alternate"):
        fwd = (k % 2) == 0
    else:
        fwd = (k == 0) | (k == last) | ((h & np.uint64(1)) == 1)
    tcp = fl["proto"] == 6
    flags = np.where(k == last, FIN | ACK, ACK)
    # a TCP flow of five packets or more closes FIN from the closer, FIN
    # back, the closer's last ACK: its last two packets meet an entry
    # that is closing
    hs = tcp & (fl["len"] >= 5) & (k >= last - 2)
    closer = (hash3(seed ^ (stream << 40) ^ 0xC105E, idx, 0) &
              np.uint64(1)) == 1
    fwd = np.where(hs, closer ^ (k == last - 1), fwd)
    flags = np.where(hs, np.where(k == last, ACK, FIN | ACK), flags)
    flags = np.where(k == 0, SYN,
                     np.where((k == 1) & ~fwd & (flags == ACK), SYN | ACK,
                              flags))
    flags = np.where(tcp, flags, 0)
    if "imix" in mix:
        sizes = np.array([s for s, _w in mix["imix"]], np.int64)
        cw = np.cumsum([w for _s, w in mix["imix"]])
        pick = (h >> np.uint64(8)) % np.uint64(cw[-1])
        length = sizes[np.searchsorted(cw, pick.astype(np.int64),
                                       side="right")]
    else:
        length = np.full(len(k), int(mix["length"]), np.int64)
    snd_addr = np.where(fwd, fl["caddr"], fl["saddr"])
    rcv_addr = np.where(fwd, fl["saddr"], fl["caddr"])
    snd_port = np.where(fwd, fl["cport"], fl["sport"])
    rcv_port = np.where(fwd, fl["sport"], fl["cport"])
    snd_ep = np.where(fwd, fl["c_ep"], fl["s_ep"])
    rcv_ep = np.where(fwd, fl["s_ep"], fl["c_ep"])
    rows_e = np.flatnonzero(snd_ep >= 0)
    rows_i = np.flatnonzero(rcv_ep >= 0)
    rows = np.concatenate([rows_e, rows_i])
    side = np.concatenate([np.zeros(len(rows_e), np.int64),
                           np.ones(len(rows_i), np.int64)])
    ep = np.concatenate([snd_ep[rows_e], rcv_ep[rows_i]])
    u32 = lambda a: a.astype(np.uint32).view(np.int32)
    rec = {"endpoint": ep.astype(np.int32),
           "saddr": u32(snd_addr[rows]), "daddr": u32(rcv_addr[rows]),
           "sport": snd_port[rows].astype(np.int32),
           "dport": rcv_port[rows].astype(np.int32),
           "proto": fl["proto"][rows].astype(np.int32),
           "direction": (1 - side).astype(np.int32),
           "tcp_flags": flags[rows].astype(np.int32),
           "length": length[rows].astype(np.int32),
           "is_fragment": np.zeros(len(rows), np.int32)}
    return rec, {"row": rows, "side": side}


class Pool:
    """``size`` flow slots fed by one FlowSource; ``round()`` emits the
    next packet of every active flow, in slot order ``perm``."""

    def __init__(self, source: FlowSource, size: int, perm=None,
                 sample_mod: int = 0):
        self.src = source
        self.size = size
        self.perm = np.arange(size) if perm is None else perm
        self.idx = np.arange(size, dtype=np.int64)
        self.next_flow = size
        self.k = np.zeros(size, np.int64)
        self.fl = source.flows(0, size)
        self.sample_mod = sample_mod
        self.sampled = self._sampled(self.idx)
        self.rounds = 0

    def _sampled(self, idx):
        if not self.sample_mod:
            return np.zeros(len(idx), bool)
        h = hash3(self.src.seed ^ 0x5A17, self.src.stream, idx)
        return (h % np.uint64(self.sample_mod)) == 0

    def round(self):
        """(records, meta): one packet of every flow in ``perm`` order;
        meta has ``idx``, ``k``, ``side`` and ``sampled`` per record."""
        p = self.perm
        fl = {key: v[p] for key, v in self.fl.items()}
        rec, m = packets(self.src.mix, self.src.seed, self.src.stream,
                         fl, self.idx[p], self.k[p])
        # both sides of one packet sit next to each other
        order = np.argsort(m["row"], kind="stable")
        rec = {f: a[order] for f, a in rec.items()}
        rows = m["row"][order]
        meta = {"idx": self.idx[p][rows], "k": self.k[p][rows],
                "side": m["side"][order],
                "sampled": self.sampled[p][rows]}
        self.k += 1
        done = np.flatnonzero(self.k >= self.fl["len"])
        if len(done):
            new = self.src.flows(self.next_flow, len(done))
            for key in self.fl:
                self.fl[key][done] = new[key]
            self.idx[done] = self.next_flow + np.arange(len(done))
            self.sampled[done] = self._sampled(self.idx[done])
            self.next_flow += len(done)
            self.k[done] = 0
        self.rounds += 1
        return rec, meta


def open_schedule(mix, seed: int, seconds: float, records_done: int = 0):
    """Frame sizes and due times (seconds from the window's start) of an
    open-loop mix over ``seconds``: Poisson frame arrivals whose rate is
    ``burst.factor`` times the mean for ``burst.on_s`` of every
    ``burst.period_s``, and lower in between, so the mean stays
    ``rate`` records per second."""
    rng = np.random.default_rng([seed, 11])
    mean_len = float(mix["frame_len"]["mean"])
    cap = int(mix["frame_len"]["max"])
    lam = float(mix["rate"]) / mean_len          # frames per second
    b = mix.get("burst")
    # thinning: candidate arrivals at the peak rate
    peak = lam * (b["factor"] if b else 1.0)
    gaps = rng.exponential(1.0 / peak, int(peak * seconds * 1.2) + 64)
    t = np.cumsum(gaps)
    t = t[t < seconds]
    if b:
        on = (t % b["period_s"]) < b["on_s"]
        off_rate = lam * (b["period_s"] - b["factor"] * b["on_s"]) / \
            (b["period_s"] - b["on_s"])
        keep = np.where(on, 1.0, off_rate / peak)
        t = t[rng.random(len(t)) < keep]
    # frame sizes: geometric with the given mean, cut to [1, cap]
    p = 1.0 / mean_len
    sizes = np.minimum(rng.geometric(p, len(t)), cap)
    return t, sizes.astype(np.int64)
