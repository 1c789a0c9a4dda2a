"""How old a HEALTHY worker's slice of ASAGA's history table is when one of
its results is accepted and replaces it, on the mean: the accepted updates
since that worker's previous commit (``history_age_healthy_sum`` over
``history_age_healthy_n`` of ``TrainResult.extras``; see
``late_history_age``, which it is read beside).  Under the tail the healthy
workers come round oftener than the worker count: the late ones give a
smaller share of the updates than they hold of the data.  None where the
program keeps no such count (before ISSUE 58), where nobody is late and
where the run ended inside its calibration."""

from benchmark.metrics.late_history_age import mean_age

NAME = "healthy_history_age"
UNIT = "updates"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"


def read(run, trace):
    return mean_age(run, "healthy")
