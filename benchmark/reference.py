"""The plain reference: what each served record's verdict and identity
must be, from the deployment's own data.  It imports nothing of the
program and takes nothing it made.

Semantics (Cilium's bpf_lxc path, as the configuration states them):

- identity: longest-prefix match of the peer address (the source on
  ingress, the destination on egress) over the ipcache prefixes;
  ``WORLD`` when none matches;
- conntrack first: a record whose reverse tuple has an entry is a
  reply; one whose own tuple has an entry is established.  Either
  follows the entry: its recorded proxy port (0 = allow), or allow for
  a reply of a flow with no forward entry;
- otherwise the policymap, in three tiers: the exact key (identity,
  port, protocol, direction) gives its proxy port; else the L3-only key
  (identity, 0, 0, direction) allows; else the L4-wildcard key (0, port,
  protocol, direction) gives its proxy port; else drop (-1).  A new
  record that policy does not drop creates its entry, with the proxy
  port it was given;
- lifetimes and closing (``bpf/lib/conntrack.h``): an entry lives
  60 s from a bare SYN, 21,600 s from other TCP, 60 s from other
  protocols; a hit on a live entry (one side at most closing) renews
  it so.  A TCP FIN or RST marks its direction's side closing; once
  both sides are closing the entry lives ``CLOSE_S`` more and is no
  longer renewed, and until then it still answers as established or
  reply.  A SYN on a closing entry reopens it.  An entry past its
  lifetime may still answer until garbage collection takes it, so
  there either answer is correct.

Records of one flow are checked in packet order.  Where the served
answer could have seen an earlier record's effect or not (the two were
in flight together; only open-loop traffic can do this), every answer
one of those orders gives is correct, and the check goes on from the
answer served.  The program's conntrack clock is whole seconds of the
host's wall clock, read once per launch; ``clock_offset`` maps the
benchmark's ``perf_counter`` times onto it.
"""

from __future__ import annotations

import numpy as np

WORLD = 2
DROP = -1
TCP, FIN, SYN, RST, ACK = 6, 0x01, 0x02, 0x04, 0x10
LIFE_TCP_S, LIFE_OTHER_S, LIFE_SYN_S, CLOSE_S = 21600, 60, 60, 10
RX_CLOSING, TX_CLOSING = 1, 2      # ingress side, egress side


def _u32(ip: str) -> int:
    a, b, c, d = (int(x) for x in ip.split("."))
    return (a << 24) | (b << 16) | (c << 8) | d


def host_lpm(prefixes):
    """addr (uint32) -> identity, by longest prefix (from
    ``chip_smoke.host_lpm``)."""
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
        return WORLD
    return identity_of


class Policy:
    """One endpoint's policymap: (identity, port, proto, dir) ->
    proxy port."""

    def __init__(self, row):
        self.d = {(i, p, pr, d): x for i, p, pr, d, x in zip(
            row["ident"].tolist(), row["port"].tolist(),
            row["proto"].tolist(), row["dir"].tolist(),
            row["proxy"].tolist())}

    def verdict(self, ident, dport, proto, dirn) -> int:
        d = self.d
        v = d.get((ident, dport, proto, dirn))
        if v is not None:
            return v
        if (ident, 0, 0, dirn) in d:
            return 0
        v = d.get((0, dport, proto, dirn))
        if v is not None:
            return v
        return DROP


def lifetime(proto: int, flags: int) -> int:
    if proto != TCP:
        return LIFE_OTHER_S
    return LIFE_SYN_S if flags & (SYN | ACK) == SYN else LIFE_TCP_S


class Entry:
    """One conntrack entry: its proxy port, when the record that made
    it was answered, its closing sides, and its expiry as a range of
    the program's clock (the launch read the clock somewhere between
    the record's submit and its answer)."""

    __slots__ = ("proxy", "made", "closing", "exp_lo", "exp_hi")

    def __init__(self, proxy, made):
        self.proxy, self.made, self.closing = proxy, made, 0
        self.exp_lo = self.exp_hi = None

    def expire_in(self, life, now_lo, now_hi):
        self.exp_lo, self.exp_hi = now_lo + life, now_hi + life

    def hit(self, proto, flags, dirn, now_lo, now_hi):
        both = RX_CLOSING | TX_CLOSING
        if proto == TCP and flags & SYN and self.closing:
            self.closing = 0                       # reopened
        if self.closing != both:
            self.expire_in(lifetime(proto, flags), now_lo, now_hi)
        if proto == TCP and flags & (FIN | RST):
            self.closing |= RX_CLOSING if dirn == 0 else TX_CLOSING
            if self.closing == both:
                self.expire_in(CLOSE_S, now_lo, now_hi)


class Reference:
    """The default reference of a kind (``byname.py``).  A kind's
    ``references/<kind>.py`` provides a ``Reference`` with what
    ``run.py``, ``control.py`` and the tests call:

    - ``Reference(dep, clock_offset)``: ``dep`` the deployment,
      ``clock_offset`` the program's clock minus ``perf_counter``;
    - ``check(rec, verdict, identity[, events])``, returning (verdict
      mismatches, identity mismatches, records checked, ambiguous
      records, examples).  ``events`` is passed only in a cell that
      plays events: one stamp per event played, ``(event, due, start,
      done, error)`` on the host clock;
    - ``closing_checked``: records that met a closing entry;
    - ``policy_only(rec)``: the control's (verdict, identity) arrays.

    A subclass may widen ``policy_verdicts``, what policy may answer a
    record that met no conntrack entry (a policy that changes inside
    the window: either side of the change while it is in flight).
    """

    def __init__(self, dep, clock_offset: float = 0.0):
        self.identity_of = host_lpm(dep.prefixes)
        self.policy = [Policy(row) for row in dep.policy]
        self.offset = clock_offset
        self.closing_checked = 0   # records that met a closing entry

    def _clock(self, t) -> int:
        return int(np.floor(t + self.offset))

    def policy_verdicts(self, ep, ident, dport, proto, dirn, submit,
                        resolve):
        """The verdicts policy may give a record of endpoint ``ep`` that
        met no conntrack entry, submitted and answered at ``submit`` and
        ``resolve`` (host clock): here the endpoint's one policymap's."""
        return (self.policy[ep].verdict(ident, dport, proto, dirn),)

    def policy_only(self, rec):
        """The control's answers: the identity and the policy verdict of
        each record of ``rec`` with no conntrack at all (replies and
        established flows lose what their entry gave them)."""
        n = len(rec["endpoint"])
        verdict = np.empty(n, np.int64)
        ident = np.empty(n, np.int64)
        cols = [np.asarray(rec[f]).astype(np.int64).tolist() for f in (
            "endpoint", "saddr", "daddr", "dport", "proto", "direction")]
        for j in range(n):
            ep, s, d, dp, pr, dirn = (c[j] for c in cols)
            peer = (s if dirn == 0 else d) & 0xFFFFFFFF
            ident[j] = self.identity_of(peer)
            pol = self.policy[ep] if 0 <= ep < len(self.policy) else None
            verdict[j] = DROP if pol is None else \
                pol.verdict(int(ident[j]), dp, pr, dirn)
        return verdict, ident

    def check(self, rec, verdict, identity, limit_examples=5):
        """Compare answers with the reference.  ``rec``: dict of arrays
        (the record fields as uint32/int, plus ``flow`` (one int per
        flow), ``k``, ``side``, ``submit``, ``resolve``).  Returns
        (verdict mismatches, identity mismatches, records checked,
        ambiguous records, examples)."""
        n = len(verdict)
        order = np.lexsort((rec["side"], rec["k"], rec["flow"]))
        sa = rec["saddr"].astype(np.int64) & 0xFFFFFFFF
        da = rec["daddr"].astype(np.int64) & 0xFFFFFFFF
        cols = [a.tolist() for a in (
            rec["flow"], rec["endpoint"], sa, da, rec["sport"],
            rec["dport"], rec["proto"], rec["direction"], rec["tcp_flags"],
            rec["submit"], rec["resolve"], verdict, identity)]
        bad_v = bad_i = ambiguous = 0
        examples = []
        entries = {}
        flow_prev = None
        for j in order.tolist():
            (flow, ep, s, d, sp, dp, pr, dirn, flags, sub, res, got_v,
             got_i) = (c[j] for c in cols)
            if flow != flow_prev:
                entries = {}      # tuples never repeat across flows
                flow_prev = flow
            now_lo, now_hi = self._clock(sub), self._clock(res)
            peer = s if dirn == 0 else d
            want_i = self.identity_of(peer)
            if got_i != want_i:
                bad_i += 1
                if len(examples) < limit_examples:
                    examples.append(("identity", flow, j, got_i, want_i))
            fwd_key = (s, d, sp, dp, pr, dirn)
            rev_key = (d, s, dp, sp, pr, 1 - dirn)
            fwd, rev = entries.get(fwd_key), entries.get(rev_key)

            def may(e):
                """(may be there, may be absent) for entry ``e``: absent
                where it was made by a record in flight with this one,
                or where its lifetime may have run out."""
                if e is None:
                    return (False,)
                sure = e.made < sub and now_hi < e.exp_lo
                return (True,) if sure else (True, False)

            want, via_entry = set(), set()
            for f in may(fwd):
                for r in may(rev):
                    if f or r:
                        v = fwd.proxy if f else 0
                        want.add(v)
                        via_entry.add(v)
                    else:
                        want.update(self.policy_verdicts(
                            ep, want_i, dp, pr, dirn, sub, res))
            if len(want) > 1:
                ambiguous += 1
            if got_v not in want:
                bad_v += 1
                if len(examples) < limit_examples:
                    examples.append(("verdict", flow, j, got_v,
                                     sorted(want)))
                continue
            hit = rev if rev is not None else fwd
            if hit is not None and got_v in via_entry:
                self.closing_checked += bool(hit.closing)
                hit.hit(pr, flags, dirn, now_lo, now_hi)
            elif got_v >= 0 and fwd is None and rev is None:
                e = entries[fwd_key] = Entry(got_v, res)
                e.expire_in(lifetime(pr, flags), now_lo, now_hi)
            elif got_v >= 0 and fwd is not None:
                # answered as new beside the record that made the entry,
                # and before it: the entry dates from the earlier answer
                fwd.made = min(fwd.made, res)
        return bad_v, bad_i, n, ambiguous, examples
