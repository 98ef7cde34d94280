"""Share of the traced window in which no operation ran on the device, in
a cell with failures (it moves that cell's goodput)."""


def read(run):
    return run.idle_share()
