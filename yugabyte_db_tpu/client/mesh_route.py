"""By-leader routing of multi-tablet aggregates: one rule, both frontends.

A table's tablets are grouped by the tserver that leads them; a group of
two or more on a node with more than one accelerator chip is sent as ONE
``ts.multi_agg_scan`` (the tserver runs it as one device program over
its chips, tserver/mesh_scan.py), everything else as one ``ts.scan`` a
tablet. The route is chosen from what the node reports of itself
(``TabletLocation.replica_chips``, from the tserver's heartbeat), not
from a flag: a one-chip node keeps the per-tablet programs. A leader
between two lease renewals is asked again; any other reply that is not
``ok`` demotes the group to the per-tablet path, which follows leader
hints and replicas as it always has.

``YBSession._scan_aggregate`` (client API, tools) and the PG frontend's
aggregate path (``RemoteTabletGroup``, yql/cql/client_cluster.py) both
route through here.

Reference analog: the client-side fan-out the reference does a tablet at
a time (src/yb/client/batcher.h:80) with partials merged above the scan
(src/yb/docdb/pgsql_operation.cc:473).
"""

from __future__ import annotations

import time

from yugabyte_db_tpu.storage import wire
from yugabyte_db_tpu.storage.scan_spec import ScanSpec
from yugabyte_db_tpu.utils import trace
from yugabyte_db_tpu.utils.metrics import count_swallowed


def leader_groups(tablets: list, engine: str):
    """``(groups, rest)``: ``groups`` is ``[(leader uuid, [location,
    ...])]`` for every leader whose node has more than one chip and
    which leads two or more of ``tablets``; ``rest`` the other tablets,
    in their order."""
    by_leader: dict[str, list] = {}
    if engine == "tpu":
        for loc in tablets:
            if loc.leader and loc.replica_chips.get(loc.leader, 1) > 1:
                by_leader.setdefault(loc.leader, []).append(loc)
    groups = [(leader, g) for leader, g in by_leader.items() if len(g) >= 2]
    grouped = {id(loc) for _l, g in groups for loc in g}
    return groups, [loc for loc in tablets if id(loc) not in grouped]


def multi_agg_scan(client, leader: str, group: list, spec: ScanSpec,
                   timeout_s: float) -> dict | None:
    """One ``ts.multi_agg_scan`` for ``group`` at ``leader``: the ``ok``
    reply (its ``read_ht`` is the one read point the tserver pinned
    across the group's tablets), or None where the group has to be
    served a tablet at a time. ``not_leader`` from a server that still
    names itself (or nobody) is a leader between two lease renewals, a
    stalled heartbeat round: the request is sent again under the
    client's retry policy, as ``tablet_rpc`` sends a ``ts.scan`` again,
    and not demoted (the per-tablet path would wait for the same lease,
    and its programs may never have run on this node). A hint that names
    another server means the grouping is stale: demote. A request that
    used up the caller's whole budget raises what the transport raised:
    nothing is left to serve it another way."""
    payload = {"tablet_ids": [g.tablet_id for g in group],
               "spec": wire.encode_spec(spec),
               "propagated_ht": client.last_observed_ht}
    trace.inject(payload)  # the request's id, for the server's Trace
    deadline = time.monotonic() + timeout_s
    for attempt in client.retry_policy.attempts(timeout_s=timeout_s):
        # The caller's budget, as a per-tablet ts.scan gets it: the
        # first request for a new run set stacks, uploads and compiles,
        # which takes as long as the tablets are big. It rides
        # server-side below the transport timeout so a slow pin returns
        # a clean timed_out.
        budget = timeout_s if attempt.number == 1 else attempt.timeout(None)
        payload["timeout"] = max(0.05, round(budget * 0.8, 3))
        try:
            resp = client.transport.send(leader, "ts.multi_agg_scan",
                                         payload, timeout=budget)
        except Exception as e:  # noqa: BLE001 — per-tablet fallback
            if time.monotonic() >= deadline:
                raise
            count_swallowed("mesh_route.multi_agg_scan", e)
            return None
        code = resp.get("code")
        if code == "not_leader" and resp.get("leader_hint") in (None,
                                                                leader):
            continue
        if code != "ok":
            return None
        seen = resp.get("read_ht") or 0
        if seen > client.last_observed_ht:
            client.last_observed_ht = seen
        return resp
    return None
