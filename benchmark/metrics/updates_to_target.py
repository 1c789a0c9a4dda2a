"""Accepted updates until the objective first falls to the target
(``target.py``), from this run's trajectory: the statistical half of
``time_to_target_s``."""

NAME = "updates_to_target"
UNIT = "updates"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"


def read(run, trace):
    return run["target"]["updates_to_target"]
