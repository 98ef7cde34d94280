"""Mean host time per plan-table rebuild of building the stacked reward
rows (the program's ``plan.rows`` spans under each ``plan.rebuild``)."""
from chipbench import program_spans


def read(run):
    s = program_spans.per_parent(run, "plan.rebuild", ["plan.rows"])
    return None if s is None else 1e3 * s
