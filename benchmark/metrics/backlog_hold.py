"""Share of the run the submitter slept because the updater was a whole
fleet of results behind (``ctx.size() >= num_workers``, the backlog bound
of PR 26): ``submit_hold_backlog_s`` of ``TrainResult.extras`` over
``elapsed_s``, counted in every run.  None where the program does not
count it."""

from benchmark.metrics.updater_busy import busy_share

NAME = "backlog_hold"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "submit_hold_backlog_s"


def read(run, trace):
    return busy_share(run, COUNTER)
