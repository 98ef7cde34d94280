"""Mean plan-table rebuild time in the window, from the coordinator's
``PlanStats`` (``table_rebuild_s`` over ``table_rebuilds``)."""


def read(run):
    evs = run.counters.get("events")
    if not evs:
        return None
    n = sum(e["rebuilds"] for e in evs)
    return 1e3 * sum(e["rebuild_s"] for e in evs) / n if n else None
