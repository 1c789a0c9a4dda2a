"""The share of a dense shard's 128-row lane tiles a worker step fetches
(100 times ``dense_tiles_read_share`` of ``TrainResult.extras``): what the
tile-list kernel (``pallas_kernels.dense_onepass_tiles``) reads of the
shard, expected over the draw from its rate, ``1 - (1 - b)^128``; host
arithmetic where the programs are built, no device read.  72.4 at ASAGA's
``b`` 0.01; 100 where the step reads the whole shard (the whole-shard
kernel, the two XLA products).  None where the program does not say
(before ISSUE 49)."""

NAME = "dense_tiles_read"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "steps"
MOVES = "updates_per_s"


def read(run, trace):
    share = run["result"]["extras"].get("dense_tiles_read_share")
    return None if share is None else 100.0 * share
