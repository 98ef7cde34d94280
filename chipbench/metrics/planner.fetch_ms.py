"""Mean host time per plan-table rebuild of copying the fused program's
outputs to the host (the program's ``plan.fetch`` spans under each
``plan.rebuild``)."""
from chipbench import program_spans


def read(run):
    s = program_spans.per_parent(run, "plan.rebuild", ["plan.fetch"])
    return None if s is None else 1e3 * s
