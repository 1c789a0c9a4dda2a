"""Seconds ALL threads together stood at the engine's ``context`` lock, over
the run's fenced seconds, in percent (``lock_wait_context_s`` of
``TrainResult.extras`` over ``elapsed_s``).  It is taken by
every handler's ``merge_result``, the submitter's ``partial_barrier``,
``available_workers`` and ``mark_busy``, the updater's queue reads.
Waits of several threads add, so it can pass 100.  ``extras`` has the
table by who waited behind whom (``lock_wait_context_<waiter>_behind_
<holder>_s``) and the count of waits (``lock_contended_context``).  0.0
where nothing waited; None where the program keeps no such clock (before
ISSUE 53)."""

from benchmark.metrics.updater_busy import busy_share

NAME = "context_lock_wait"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "lock_wait_context_s"


def read(run, trace):
    return busy_share(run, COUNTER)
