"""Seconds a ``run()`` spends after its fence beside evaluating its
trajectory: the final checkpoint (``checkpoint_s`` of ``TrainResult.extras``)
and the close of the run's instruments (``close_s``: the bus drained and
joined, the UI and the event log closed).  With ``trajectory_eval_s`` it
accounts for the harness's ``spans.trajectory_eval_s`` (``run()``'s return
less ``elapsed_s``).  Every ``run()`` pays it, the warm-up's included, so it
is part of ``setup_s``.  None where the program does not count it."""

NAME = "teardown_s"
UNIT = "s"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "setup_s"


def read(run, trace):
    extras = run["result"]["extras"]
    checkpoint_s, close_s = extras.get("checkpoint_s"), extras.get("close_s")
    if checkpoint_s is None or close_s is None:
        return None
    return checkpoint_s + close_s
