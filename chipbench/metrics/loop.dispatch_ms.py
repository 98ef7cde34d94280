"""Mean host time per fused step of building its batch and enqueueing
the compiled step (the program's ``loop.batch`` and ``loop.dispatch``
spans under each ``loop.step`` of kind ``fused``)."""
from chipbench import program_spans


def read(run):
    s = program_spans.per_parent(run, "loop.step",
                                 ["loop.batch", "loop.dispatch"],
                                 kind="fused")
    return None if s is None else 1e3 * s
