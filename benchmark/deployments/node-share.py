"""``node-share``: one node's share of a large Kubernetes cluster.  Pods
across the cluster carry identities; each local endpoint holds a
policymap of exact, L3-only, L4-wildcard and proxy-redirect keys."""

import numpy as np

from deploy import LOCAL_BASE, Deployment, ip_str, pack_keys


def _policy_row(rng, n, pod_ident, cfg):
    """``n`` unique keys: the mix of ``chip_smoke.NodeShare`` drawn
    vectorised.  Returns a dict of arrays."""
    known, l3, l4w = cfg["known_peer_share"], cfg["l3_only_share"], \
        cfg["l4_wildcard_share"]
    proxy_share, udp = cfg["proxy_share"], cfg["udp_share"]
    keys = np.zeros(0, np.int64)
    cols = {k: np.zeros(0, np.int64)
            for k in ("ident", "port", "proto", "dir", "proxy")}
    while len(keys) < n:
        m = 2 * (n - len(keys)) + 64
        ident = np.where(rng.random(m) < known, rng.choice(pod_ident, m),
                         rng.integers(256, 65536, m))
        kind = rng.random(m)
        ident = np.where(kind < l4w, 0, ident)
        port = np.where((kind >= l4w) & (kind < l4w + l3), 0,
                        rng.integers(1, 65536, m))
        proto = np.where(port == 0, 0,
                         np.where(rng.random(m) < udp, 17, 6))
        dirn = rng.integers(0, 2, m)
        proxy = np.where((rng.random(m) < proxy_share) & (port != 0),
                         rng.integers(10000, 20000, m), 0)
        new = pack_keys(ident, port, proto, dirn)
        allk = np.concatenate([keys, new])
        _, first = np.unique(allk, return_index=True)
        first.sort()
        first = first[:n]
        merged = {"ident": ident, "port": port, "proto": proto,
                  "dir": dirn, "proxy": proxy}
        cols = {k: np.concatenate([cols[k], merged[k]])[first]
                for k in cols}
        keys = allk[first]
    return cols


def build(cfg, seed: int) -> Deployment:
    rng = np.random.default_rng([seed, 1])
    pods = cfg["pods"]
    # pod addresses in 10.0.0.0 - 10.254.255.255 (10.255/16 is local)
    pod_addr = (np.uint32(0x0A000000) + rng.choice(
        np.uint32(0xFF0000), pods, replace=False)).astype(np.uint32)
    pod_ident = rng.integers(256, 65536, pods)
    prefixes = {f"{ip_str(a)}/32": i
                for a, i in zip(pod_addr.tolist(), pod_ident.tolist())}
    cidr = {}
    while len(cidr) < cfg["cidrs"]:
        plen = int(rng.choice([16, 20, 24, 28]))
        a = int(rng.integers(11, 224)) << 24 | int(rng.integers(0, 1 << 24))
        a &= (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF
        cidr[f"{ip_str(a)}/{plen}"] = int(rng.integers(256, 65536))
    prefixes.update(cidr)
    policy = [_policy_row(rng, cfg["entries_per_endpoint"], pod_ident,
                          cfg) for _ in range(cfg["endpoints"])]
    # Zipf rank order over pods: a random permutation
    order = rng.permutation(pods)
    local = LOCAL_BASE + np.arange(cfg["endpoints"], dtype=np.uint32)
    return Deployment(policy, prefixes, local, pod_addr[order],
                      pod_ident[order])
