"""Mean host time per plan-table rebuild of unpacking the fused program's
slot buffer into the table's stores (the program's ``plan.unpack`` spans
under each ``plan.rebuild``)."""
from chipbench import program_spans


def read(run):
    s = program_spans.per_parent(run, "plan.rebuild", ["plan.unpack"])
    return None if s is None else 1e3 * s
