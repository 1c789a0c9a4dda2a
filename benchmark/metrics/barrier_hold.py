"""Share of the run the submitter slept while workers WERE available and
the recipe's bucket held them back (fewer than ``floor(num_workers x
bucket_ratio)``): ``submit_hold_barrier_s`` of ``TrainResult.extras`` over
``elapsed_s``, counted in every run.  Large wherever the device sets the
pace, and harmless there: it is read together with ``chip_starved``, which
says whether a chip went without work meanwhile.  None where the program
does not count it."""

from benchmark.metrics.updater_busy import busy_share

NAME = "barrier_hold"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "submit_hold_barrier_s"


def read(run, trace):
    return busy_share(run, COUNTER)
