"""Share of the run the one updater thread spent dispatching the history
path (``updater_history_s`` of ``TrainResult.extras`` over ``elapsed_s``):
the table delta and the commit of every accepted update, a part of
``updater_apply_s``.  None where the program does not count it."""

from benchmark.metrics.updater_busy import busy_share

NAME = "updater_history"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "updater_history_s"


def read(run, trace):
    return busy_share(run, COUNTER)
