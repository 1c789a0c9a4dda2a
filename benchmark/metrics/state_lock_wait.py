"""Seconds ALL threads together stood at the engine's ``state`` lock, over
the run's fenced seconds, in percent (``lock_wait_state_s`` of
``TrainResult.extras`` over ``elapsed_s``).  It is taken by
the submitter at every poll and twice a cohort, the updater twice a
drain and once a dispatch, the main thread at the run's end.
Waits of several threads add, so it can pass 100.  ``extras`` has the
table by who waited behind whom (``lock_wait_state_<waiter>_behind_
<holder>_s``) and the count of waits (``lock_contended_state``).  0.0
where nothing waited; None where the program keeps no such clock (before
ISSUE 53)."""

from benchmark.metrics.updater_busy import busy_share

NAME = "state_lock_wait"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "lock_wait_state_s"


def read(run, trace):
    return busy_share(run, COUNTER)
