"""The default system under test, configured as the daemon configures
it.

A kind builds its system here unless it brings ``systems/<kind>.py``
(``byname.py``); this module and those files are the only ones of the
benchmark that import the program.  It loads a deployment
(``deploy.py``) through the daemon's own table path and returns the
engine and its serving lane; what it reads back is the lane's verdicts,
the dispatcher's counters, the stage spans and the supervisor's status.
"""

from __future__ import annotations

# DaemonConfig defaults (utils/option.py) that shape the served path
SUPERVISION = {"watchdog_s": 10.0, "failure_threshold": 3,
               "reset_s": 1.0, "new_flow_policy": "oracle",
               "max_pending": 1 << 17, "default_deadline": None}
FLOW_SLOTS, FLOW_PROBE = 1 << 12, 8


def to_state(row):
    """A deployment policy row as the program's PolicyMapState."""
    from cilium_tpu.policy.mapstate import (PolicyKey, PolicyMapState,
                                            PolicyMapStateEntry)
    st = PolicyMapState()
    for i, p, pr, d, x in zip(row["ident"].tolist(), row["port"].tolist(),
                              row["proto"].tolist(), row["dir"].tolist(),
                              row["proxy"].tolist()):
        st[PolicyKey(identity=i, dest_port=p, nexthdr=pr,
                     direction=d)] = PolicyMapStateEntry(proxy_port=x)
    return st


class System:
    """The engine with its tables loaded and its serving lane.

    A kind's ``systems/<kind>.py`` provides a ``System(cfg, dep)`` with
    what ``run.py``, ``control.py`` and the tests use: ``lane`` (the
    serving lane: ``submit_records(soa, n)`` returning a ticket),
    ``stats()``, ``stages()``, ``supervision()``, ``ct_entries()``,
    ``geometry()`` (with ``ct_slots``), ``wrap_step(wrap)`` and
    ``close()``; and, for a kind whose mixes play events,
    ``apply(event)``, called inside the window by the events thread."""

    def __init__(self, cfg, dep):
        from cilium_tpu.datapath.engine import Datapath
        from cilium_tpu.endpoint.tables import DeviceTableManager
        dp = Datapath(ct_slots=cfg["ct_slots"])
        dp.telemetry_enabled = True         # DaemonConfig.enable_tracing
        dp.configure_supervision(enabled=True, **SUPERVISION)
        if cfg.get("flow_aggregation", True):
            dp.enable_flow_aggregation(slots=FLOW_SLOTS,
                                       max_probe=FLOW_PROBE)
        rows, slots = cfg.get("policy_rows"), cfg.get("policy_slots")
        mgr = DeviceTableManager(initial_endpoints=rows, initial_slots=slots) \
            if rows else DeviceTableManager()
        for e, row in enumerate(dep.policy):
            slot = mgr.attach(e + 1)
            if slot != e:
                raise RuntimeError(f"endpoint {e} got table row {slot}")
            mgr.sync_endpoint(e + 1, to_state(row), revision=1)
        dp.use_table_manager(mgr, ipcache_prefixes=dep.prefixes)
        self.dp, self.mgr = dp, mgr
        self.lane = dp.serving()

    def geometry(self):
        st = self.mgr.stats()
        return {"rows": st["capacity"], "slots": st["slots"],
                "max_probe": st["max_probe"], "ct_slots": self.dp.ct.slots}

    def wrap_step(self, wrap):
        """Put ``wrap(step)`` in the place of the engine's packed step,
        the call the serving lane makes once per launch (the tests'
        faults and the control).  Returns the packed matrix's field
        order."""
        from cilium_tpu.datapath.pipeline import PACKED_FIELDS
        self.dp.process_packed = wrap(self.dp.process_packed)
        return PACKED_FIELDS

    def stats(self):
        return self.lane.stats()

    def stages(self):
        from cilium_tpu.observability.stages import pipeline_report
        return pipeline_report()

    def supervision(self):
        return self.dp.supervision_status()

    def ct_entries(self) -> int:
        return self.dp.ct.entry_count()

    def close(self):
        self.lane.close(timeout=30)

