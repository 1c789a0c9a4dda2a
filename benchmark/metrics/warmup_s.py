"""The benchmark's span around solver construction and the fenced warm-up
run of the cell's shapes (compile, or load from the compile cache: the
record's ``cache`` says which)."""

NAME = "warmup_s"
UNIT = "s"
SOURCE = "host_clock"
LAYER = "set-up"
MOVES = "setup_s"


def read(run, trace):
    return run["spans"]["warmup_s"]
