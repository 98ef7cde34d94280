"""Host time of the argmax tracebacks that turn plan-table values into
assignments (the program's ``plan.traceback`` spans), in the window per
plan-table rebuild.  Every span counts, wherever it runs: an eager table
traces back each scenario inside its rebuild, a lazy one at dispatch."""
from chipbench import program_spans


def read(run):
    s = program_spans.per_parent(run, "plan.rebuild", ["plan.traceback"],
                                 under=False)
    return None if s is None else 1e3 * s
