"""The least bytes one served record needs, whatever the
implementation: the fused step's roofline (``PERF.md`` §3).

A record is read in, its verdict and identity go out, its conntrack
entry is read and written back, and one ipcache entry and one policymap
entry are read.  Map entries count at the sizes of the reference's BPF
maps (Cilium 1.2, ``bpf/lib/common.h``): an implementation cannot keep
less per entry and give the same answers.
"""

RECORD_IN = 10 * 4          # the served record: 10 int32 fields
ANSWER_OUT = 2 * 4          # verdict + identity, int32 each
CT_KEY = 14                 # struct ipv4_ct_tuple (packed)
CT_VALUE = 48               # struct ct_entry: 4 u64 counters, lifetime,
#                             flags, rev_nat_index, proxy_port, pad
IPCACHE_KEY = 24            # struct ipcache_key (LPM trie key, v4/v6)
IPCACHE_VALUE = 12          # struct remote_endpoint_info
POLICY_KEY = 8              # struct policy_key
POLICY_VALUE = 24           # struct policy_entry: proxy port, pad,
#                             packets, bytes


def bytes_per_record() -> int:
    ct = CT_KEY + CT_VALUE
    return (RECORD_IN + ANSWER_OUT + 2 * ct + IPCACHE_KEY + IPCACHE_VALUE
            + POLICY_KEY + POLICY_VALUE)
