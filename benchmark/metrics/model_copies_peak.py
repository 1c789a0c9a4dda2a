"""The most model-sized device buffers the engine held at once
(``TrainResult.extras["model_copies_peak"]``): the live model, the model
versions pinned by tasks that are out, the results computed and not yet
applied, the trajectory's snapshots and, at the run's end, one
evaluation call's stack of them.  Free at 3 kB a copy; at 219 MB each it is
what fills the chip beside the shards (``peak_hbm_gb``).  None where the
program does not count them."""

NAME = "model_copies_peak"
UNIT = "copies"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    return run["result"]["extras"].get("model_copies_peak")
