"""Share of the run the one submitter thread spent outside its 1 ms sleep
and outside a blocking first job (``submitter_busy_s`` of
``TrainResult.extras`` over ``elapsed_s``): the partial barrier's polls,
building a cohort's tasks, ``run_job``.  None where the program does not
count it (the synchronous drivers have no submitter thread)."""

from benchmark.metrics.updater_busy import busy_share

NAME = "submitter_busy"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "submitter_busy_s"


def read(run, trace):
    return busy_share(run, COUNTER)
