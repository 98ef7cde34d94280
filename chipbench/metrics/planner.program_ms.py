"""Device time of the planner's fused whole-table program per
execution, from the trace's ``XLA Modules`` line."""

MODULE = "jit__program"


def read(run):
    if run.summary is None:
        return None
    secs, n = run.summary.modules(lambda name: name == MODULE)
    return 1e3 * secs / n if n else None
