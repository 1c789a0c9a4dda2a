"""The benchmark's span around the program's ``generate_on_device`` and
``block_until_ready`` on every shard."""

NAME = "data_gen_s"
UNIT = "s"
SOURCE = "host_clock"
LAYER = "set-up"
MOVES = "setup_s"


def read(run, trace):
    return run["spans"]["data_gen_s"]
