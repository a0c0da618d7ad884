"""The fail-static oracle's periodic refresh (datapath/supervisor.py
``HostStaticOracle.refresh``) rebuilds only what changed.

- The host LPM is read out of the engine's compiled LPM and answers
  ``_identity_of`` exactly as the CIDR-string parse it replaced did (and
  as ``compiler/lpm.oracle_lpm``); it is reused while the compiled LPM
  is the same object and rebuilt after an ipcache load.
- Policy states are held by reference: the table manager replaces a
  state on sync and never mutates one, so the oracle keeps answering
  from the states as of its refresh until the next one.
- The CT decode equals the per-slot decode it replaced.
"""

import ipaddress

import numpy as np
import pytest

from cilium_tpu.compiler.lpm import LPM_MISS, compile_lpm, oracle_lpm
from cilium_tpu.datapath.engine import Datapath
from cilium_tpu.datapath.pipeline import WORLD_IDENTITY
from cilium_tpu.datapath.supervisor import HostStaticOracle
from cilium_tpu.endpoint.tables import DeviceTableManager
from cilium_tpu.policy.mapstate import (INGRESS, PolicyKey, PolicyMapState,
                                        PolicyMapStateEntry)
from cilium_tpu.utils.metrics import DATAPLANE_ORACLE_LPM_BUILDS


def _string_lpm(prefixes):
    """The CIDR-string parse the refresh used to run: the reference the
    compiled-LPM read-out must equal."""
    by_plen = {}
    for cidr, ident in prefixes.items():
        addr, _, plen_s = cidr.partition("/")
        plen = int(plen_s) if plen_s else 32
        a, b, c, d = (int(x) for x in addr.split("."))
        val = (a << 24) | (b << 16) | (c << 8) | d
        mask = 0 if plen == 0 else (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF
        by_plen.setdefault(plen, {})[val & mask] = int(ident)
    return [(plen, 0 if plen == 0 else
             (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF, table)
            for plen, table in sorted(by_plen.items(), reverse=True)]


def _random_prefixes(rng, n=150):
    """/8–/32 at random, nested and overlapping ones, a non-canonical
    host-bits-set CIDR and its canonical twin (the later one wins in
    both builds)."""
    out = {}
    ident = 1000
    for _ in range(n):
        plen = int(rng.integers(8, 33))
        addr = int(rng.integers(0, 1 << 32)) & \
            ((0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF)
        out[f"{ipaddress.IPv4Address(addr)}/{plen}"] = ident
        ident += 1
        if rng.random() < 0.3:       # a longer prefix inside this one
            sub = min(32, plen + int(rng.integers(1, 9)))
            inner = addr | (int(rng.integers(0, 1 << 32)) &
                            ~((0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF)
                            & 0xFFFFFFFF)
            inner &= (0xFFFFFFFF << (32 - sub)) & 0xFFFFFFFF
            out[f"{ipaddress.IPv4Address(inner)}/{sub}"] = ident
            ident += 1
    out["10.0.0.0/8"] = 7
    out["10.1.2.3/8"] = 8            # non-canonical: same /8, wins
    out["10.1.0.0/16"] = 9
    out["10.1.2.3/32"] = 10
    return out


def _oracle_with(lpm):
    o = HostStaticOracle(None)
    o._lpm = lpm
    return o


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("with_default", [False, True])
def test_host_lpm_from_compiled_matches_string_parse(seed, with_default):
    rng = np.random.default_rng(seed)
    prefixes = _random_prefixes(rng)
    if with_default:
        prefixes["0.0.0.0/0"] = 2
    got = HostStaticOracle._host_lpm(compile_lpm(prefixes))
    assert got == _string_lpm(prefixes)
    oracle = _oracle_with(got)
    # addresses inside installed prefixes, the hand-placed overlaps,
    # and uniform ones (most miss: world, or the /0 when installed)
    keys = [int(ipaddress.IPv4Network(c, strict=False).network_address)
            for c in prefixes]
    probes = [k | int(rng.integers(0, 256)) for k in keys[:100]]
    probes += [int(rng.integers(0, 1 << 32)) for _ in range(100)]
    probes += [int(ipaddress.IPv4Address(a)) for a in
               ("10.1.2.3", "10.1.2.4", "10.2.0.1", "11.0.0.1")]
    # a CIDR with host bits set lands on its canonical one, the later
    # entry winning, in the device's tables as in both host builds;
    # oracle_lpm would keep the first, so it is given the canonical map
    canonical = {str(ipaddress.IPv4Network(c, strict=False)): v
                 for c, v in prefixes.items()}
    for addr in probes:
        want = oracle_lpm(canonical, str(ipaddress.IPv4Address(addr)))
        want = WORLD_IDENTITY if want == LPM_MISS else want
        assert oracle._identity_of(addr) == want, \
            ipaddress.IPv4Address(addr)
    assert oracle._identity_of(int(ipaddress.IPv4Address("10.1.2.3"))) == 10
    assert oracle._identity_of(int(ipaddress.IPv4Address("10.1.9.9"))) == 9
    assert oracle._identity_of(int(ipaddress.IPv4Address("10.9.9.9"))) == 8


def test_host_lpm_of_nothing_is_empty():
    assert HostStaticOracle._host_lpm(None) == []
    assert HostStaticOracle._host_lpm(compile_lpm({})) == []


# ------------------------------------------------------ engine-driven

def _state(ident, dport, proxy=0):
    st = PolicyMapState()
    st[PolicyKey(identity=ident, dest_port=dport, nexthdr=6,
                 direction=INGRESS)] = PolicyMapStateEntry(proxy_port=proxy)
    return st


def _engine(prefixes):
    mgr = DeviceTableManager(initial_endpoints=4)
    for ep in (1, 2):
        mgr.attach(ep)
        mgr.sync_endpoint(ep, _state(300, 80), revision=1)
    dp = Datapath(ct_slots=1 << 8)
    dp.telemetry_enabled = False
    dp.use_table_manager(mgr, ipcache_prefixes=prefixes)
    return dp, mgr


def test_refresh_reuses_the_lpm_until_the_ipcache_is_loaded_again():
    dp, _mgr = _engine({"10.0.0.0/8": 300, "10.1.0.0/16": 301})
    oracle = HostStaticOracle(dp)
    ten_one = int(ipaddress.IPv4Address("10.1.2.3"))
    builds = DATAPLANE_ORACLE_LPM_BUILDS.total()

    assert oracle.refresh()
    first = oracle._lpm
    assert oracle._identity_of(ten_one) == 301
    assert oracle.refresh()
    assert oracle._lpm is first        # the same list: nothing rebuilt
    st = oracle.stats()
    assert (st["lpm-builds"], st["lpm-reuses"]) == (1, 1)
    assert DATAPLANE_ORACLE_LPM_BUILDS.total() == builds + 1

    dp.load_ipcache({"10.0.0.0/8": 300, "10.1.2.0/24": 302})
    assert oracle._identity_of(ten_one) == 301   # until the next refresh
    assert oracle.refresh()
    assert oracle._lpm is not first
    assert oracle._identity_of(ten_one) == 302
    assert oracle._identity_of(int(ipaddress.IPv4Address("10.1.9.9"))) \
        == 300
    assert oracle._identity_of(int(ipaddress.IPv4Address("11.0.0.1"))) \
        == WORLD_IDENTITY
    st = oracle.stats()
    assert (st["lpm-builds"], st["lpm-reuses"]) == (2, 1)
    assert st["ipcache-prefixes"] == 2
    assert DATAPLANE_ORACLE_LPM_BUILDS.total() == builds + 2


def test_states_held_by_reference_are_the_last_known_good():
    dp, mgr = _engine({"10.0.0.0/8": 300})
    oracle = HostStaticOracle(dp)
    assert oracle.refresh()
    slot = mgr.slot_of(2)
    held = oracle._states[slot]
    assert held is mgr.states_by_slot()[slot]     # not copied
    before = dict(held)
    assert oracle._policy_verdict(slot, 300, 80, 6, INGRESS) == 0
    assert oracle._policy_verdict(slot, 300, 443, 6, INGRESS) < 0

    # the sync replaces the stored state; the held one is untouched
    mgr.sync_endpoint(2, _state(300, 443, proxy=15001), revision=2)
    assert dict(held) == before
    assert mgr.states_by_slot()[slot] is not held
    assert oracle._policy_verdict(slot, 300, 80, 6, INGRESS) == 0
    assert oracle._policy_verdict(slot, 300, 443, 6, INGRESS) < 0

    assert oracle.refresh()
    assert oracle._states[slot] is mgr.states_by_slot()[slot]
    assert oracle._policy_verdict(slot, 300, 80, 6, INGRESS) < 0
    assert oracle._policy_verdict(slot, 300, 443, 6, INGRESS) == 15001
    # the endpoint not synced keeps its (same) state object
    other = mgr.slot_of(1)
    assert oracle._policy_verdict(other, 300, 80, 6, INGRESS) == 0


# ---------------------------------------------------------- CT decode

def _decode_per_slot(snap):
    """The per-index decode the refresh used to run."""
    k0 = np.ascontiguousarray(snap["k0"]).view(np.uint32)
    k1 = np.ascontiguousarray(snap["k1"]).view(np.uint32)
    k2 = np.ascontiguousarray(snap["k2"]).view(np.uint32)
    k3 = np.ascontiguousarray(snap["k3"]).view(np.uint32)
    exp = snap["expires"]
    pp = snap["proxy_port"]
    live = np.flatnonzero(k3[:-1])
    return {(int(k0[i]), int(k1[i]), int(k2[i]), int(k3[i])):
            (int(exp[i]), int(pp[i])) for i in live.tolist()}


def test_ct_decode_equals_the_per_slot_decode():
    rng = np.random.default_rng(24)
    n = (1 << 19) + 1                   # 524,288 slots and the sentinel
    full = lambda: rng.integers(-(1 << 31), 1 << 31, n,  # noqa: E731
                                dtype=np.int64).astype(np.int32)
    snap = {k: full() for k in ("k0", "k1", "k2", "k3")}
    empty = rng.random(n) < 0.75
    snap["k3"][empty] = 0               # free slots
    snap["k3"][-1] = -5                 # the sentinel row is set
    snap["k3"][:4] = [-1, 1, 0, -(1 << 31)]
    snap["expires"] = rng.integers(0, 1 << 31, n,
                                   dtype=np.int64).astype(np.int32)
    snap["proxy_port"] = rng.integers(0, 65536, n).astype(np.int32)
    got = HostStaticOracle._decode_ct(snap)
    want = _decode_per_slot(snap)
    assert got == want
    assert len(got) == int((snap["k3"][:-1] != 0).sum())
    assert max(k[3] for k in got) == 0xFFFFFFFF   # the uint32 view
    assert all(type(x) is int for k, v in list(got.items())[:8]
               for x in k + v)
