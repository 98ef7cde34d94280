"""Mean seconds of the benchmark's ``sev2.iteration`` spans in the window."""


def read(run):
    return run.mean_span("sev2.iteration")
