"""Mean seconds per in-memory snapshot of its copies from the runtime's
host buffers into arrays the snapshot owns (the program's
``ckpt.host_copy`` spans, one a leaf, under each ``ckpt.save``)."""
from chipbench import program_spans


def read(run):
    return program_spans.per_parent(run, "ckpt.save", ["ckpt.host_copy"])
