"""Share of the run in which the emptiest chip had been given NOTHING:
``chip_empty_max_s`` of ``TrainResult.extras`` over ``elapsed_s``.  A chip is
empty while none of its workers is between its submit and its result (the
program's occupancy account, kept for every update of a traced run), so no
step the engine had handed out could run there: the part of a chip's idle
time that is the submitter's decision (the recipe's barrier, the backlog
bound) and not a dispatch that came late.  Read it beside ``device_idle`` of
the same run and ``barrier_hold``.  None where the program keeps no such
account (an untraced run, a program before ISSUE 34)."""

from benchmark.metrics.updater_busy import busy_share

NAME = "chip_starved"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "chip_empty_max_s"


def read(run, trace):
    return busy_share(run, COUNTER)
