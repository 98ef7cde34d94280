"""Mean seconds per in-memory snapshot of its device-to-host transfers
(the program's ``ckpt.d2h`` spans, one a leaf, under each ``ckpt.save``)."""
from chipbench import program_spans


def read(run):
    return program_spans.per_parent(run, "ckpt.save", ["ckpt.d2h"])
