"""Mean seconds of the benchmark's ``ckpt.restore`` spans in the window."""


def read(run):
    return run.mean_span("ckpt.restore")
