"""Device time of the fused train-step program per execution, from the
trace's ``XLA Modules`` line."""

MODULE = "jit_train_step"


def read(run):
    if run.summary is None:
        return None
    secs, n = run.summary.modules(lambda name: name == MODULE)
    return 1e3 * secs / n if n else None
