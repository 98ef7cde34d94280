"""Mean per event of the control loop's own time: the tick less the
coordinator's dispatch (``PlanStats.last_dispatch_s``) and plan-table
rebuild (``PlanStats.table_rebuild_s`` delta) seconds."""


def read(run):
    evs = run.counters.get("events")
    if not evs:
        return None
    own = [e["tick_s"] - e["dispatch_s"] - e["rebuild_s"] for e in evs]
    return 1e3 * sum(own) / len(own)
