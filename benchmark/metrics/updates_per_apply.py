"""Accepted updates to a device dispatch of ASGD's updater (``accepted``
over ``apply_dispatches`` of ``TrainResult.extras``): the updater folds
whatever is queued when it wakes into one dispatch, split only where a
snapshot is due, so this reads 1.0 while results come one at a time and
rises with the backlog the updater finds.  None where the program does
not count its apply dispatches."""

NAME = "updates_per_apply"
UNIT = "updates"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    result = run["result"]
    dispatches = result["extras"].get("apply_dispatches")
    if not dispatches:
        return None
    return result["accepted"] / dispatches
