"""Mean seconds of the benchmark's ``ckpt.snapshot`` spans in the window."""


def read(run):
    return run.mean_span("ckpt.snapshot")
