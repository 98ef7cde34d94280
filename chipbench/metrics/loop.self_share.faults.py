"""``loop.self_share`` in a cell with failures (it moves that cell's
goodput): the window outside the benchmark's spans around the step, the
SEV2 iteration, the snapshot and the restore."""
from chipbench.harness import load_reader

read = load_reader("loop.self_share")
