"""``pair``: two local endpoints, a client and a server, and one L3/L4
rule between them (the reference's netperf harness)."""

import numpy as np

from deploy import HOST, LOCAL_BASE, WORLD, Deployment, ip_str


def build(cfg, seed: int) -> Deployment:
    """Client (endpoint 0) and server (endpoint 1) on one node, one
    L3/L4 rule allowing client -> server TCP on ``ports``: the server's
    ingress keys and the client's egress keys."""
    client_id, server_id = cfg["client_identity"], cfg["server_identity"]
    local = np.array([LOCAL_BASE + 1, LOCAL_BASE + 2], np.uint32)
    ports = np.asarray(cfg["ports"], np.int64)
    n = len(ports)

    def row(ident, dirn):
        return {"ident": np.full(n, ident, np.int64), "port": ports,
                "proto": np.full(n, 6, np.int64),
                "dir": np.full(n, dirn, np.int64),
                "proxy": np.zeros(n, np.int64)}

    policy = [row(server_id, 1), row(client_id, 0)]
    prefixes = {f"{ip_str(int(local[0]))}/32": client_id,
                f"{ip_str(int(local[1]))}/32": server_id,
                f"{ip_str(cfg['host_addr'])}/32": HOST,
                "0.0.0.0/0": WORLD}
    return Deployment(policy, prefixes, local, local,
                      np.array([client_id, server_id]))
