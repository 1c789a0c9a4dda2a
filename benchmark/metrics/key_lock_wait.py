"""Seconds ALL threads together stood at the engine's ``key`` lock, over
the run's fenced seconds, in percent (``lock_wait_key_s`` of
``TrainResult.extras`` over ``elapsed_s``).  It is taken by
every result handler on its executor's thread, the submitter where it
pins a cohort's model version and builds its tasks, the updater where it
counts the model copies.
Waits of several threads add, so it can pass 100.  ``extras`` has the
table by who waited behind whom (``lock_wait_key_<waiter>_behind_
<holder>_s``) and the count of waits (``lock_contended_key``).  0.0
where nothing waited; None where the program keeps no such clock (before
ISSUE 53)."""

from benchmark.metrics.updater_busy import busy_share

NAME = "key_lock_wait"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "lock_wait_key_s"


def read(run, trace):
    return busy_share(run, COUNTER)
