"""Seconds the program spent evaluating its trajectory after the run's
clock had stopped (``trajectory_eval_s`` of ``TrainResult.extras``: the
stage ``trajectory.eval``, from the stack of the snapshots to the read-back
of the last shard's sums).  Every ``run()`` pays one, the warm-up's
included, so it is part of ``setup_s`` and of every run's wall clock; for
padded ELL it is one gather pass over the whole dataset for every eight
snapshots.  The harness's own ``spans.trajectory_eval_s`` (``run()``'s
return less ``elapsed_s``, host clock) stands beside it on every run's
``info`` line: that one also holds the teardown.  None where the program
does not count it."""

NAME = "trajectory_eval_s"
UNIT = "s"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "setup_s"


def read(run, trace):
    return run["result"]["extras"].get("trajectory_eval_s")
