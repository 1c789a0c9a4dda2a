"""The scale of the injected delays: the mean submit-to-finish time of a
worker task over the calibration (the first ``100 x num_workers`` accepted
updates), which the straggler model multiplies by ``U(1.5, 2.5)`` or
``U(2.5, 10)`` for every sleep it injects (``avg_delay_ms`` of
``TrainResult.extras``, from ``engine/straggler.py: DelayModel.account``).
The delays are multiples of the run's OWN task time, so a change that
makes a task shorter makes every sleep shorter with it: read the other
delay metrics beside this one.  None where the program keeps no such
account (before ISSUE 51) or injected nothing (``coeff`` 0, or a run that
ended inside its calibration)."""

NAME = "delay_avg_ms"
UNIT = "ms"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    return run["result"]["extras"].get("avg_delay_ms") or None
