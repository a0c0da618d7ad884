"""Deployments, made from a configuration file and its
``deployment_seed``.

A deployment is plain data: per-endpoint policy keys, the ipcache
prefixes and the addresses traffic may use.  Nothing here imports the
program; ``sut.py`` loads a deployment into it and ``reference.py``
answers from the same data.

Each configuration names a ``kind``; its builder is
``deployments/<kind>.py``, found by that name, whose ``build(cfg,
seed)`` returns a ``Deployment``.  A new kind is a new file.

The deployment is one of a kind's roles (``byname.kind_parts``, which
``run.py`` resolves once per run).  The others default to the shipped
classes and may each come as a file of the kind's own:
``systems/<kind>.py`` (``sut.System``), ``references/<kind>.py``
(``reference.Reference``), ``flows/<kind>.py`` (``traffic.FlowSource``)
and ``events/<kind>.py`` (``schedule(cfg, mix, dep, seed, seconds)``
returning ``[(at_s, event), ...]``, played inside the window by
``run.py``'s ``bench-events`` thread through ``system.apply(event)``;
read only for a mix with an ``"events"`` key, and required there).  A
kind's file may subclass the default or load another kind's file by
name: a kind that reuses node-share's deployment has
``deployments/<kind>.py`` return
``byname.module("deployments", "node-share").build(cfg, seed)``.
Like this module, the reference's side (``reference.py``,
``traffic.py``, ``references/``, ``flows/``, ``events/``,
``deployments/``) imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

import byname

WORLD = 2
HOST = 1
# local endpoints live in 10.255.0.0/16, outside every ipcache prefix
LOCAL_BASE = 0x0AFF0000


def ip_str(u: int) -> str:
    return f"{u >> 24}.{(u >> 16) & 255}.{(u >> 8) & 255}.{u & 255}"


def pack_keys(ident, port, proto, dirn):
    """One int64 per policy key (identity, port, proto, direction)."""
    return (np.asarray(ident, np.int64) << 25) | \
        (np.asarray(port, np.int64) << 9) | \
        (np.asarray(proto, np.int64) << 1) | np.asarray(dirn, np.int64)


class Deployment:
    """Plain deployment data.

    - ``policy[e]``: dict of int arrays ``ident, port, proto, dir,
      proxy`` for local endpoint ``e`` (unique keys);
    - ``prefixes``: {"a.b.c.d/len": identity} for the ipcache;
    - ``local_addr[e]``: the endpoint's own address;
    - ``pod_addr``, ``pod_ident``: cluster pods that traffic may use
      as peers, with their identities, in Zipf rank order (most
      popular first).
    """

    def __init__(self, policy, prefixes, local_addr, pod_addr,
                 pod_ident):
        self.policy = policy
        self.prefixes = prefixes
        self.local_addr = np.asarray(local_addr, np.uint32)
        self.pod_addr = np.asarray(pod_addr, np.uint32)
        self.pod_ident = np.asarray(pod_ident, np.int64)
        # one address per identity a pod carries: installed keys name
        # identities, and traffic needs an address that resolves to one
        self.addr_arr = np.full(1 << 16, -1, np.int64)
        # the first pod of an identity (the most popular) wins
        self.addr_arr[self.pod_ident[::-1]] = \
            self.pod_addr[::-1].astype(np.int64)

    @property
    def endpoints(self) -> int:
        return len(self.policy)

    def entries(self) -> int:
        return sum(len(p["ident"]) for p in self.policy)


def build(cfg, seed: int) -> Deployment:
    return byname.module("deployments", cfg["kind"]).build(cfg, seed)
