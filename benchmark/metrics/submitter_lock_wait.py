"""Share of the run the one submitter thread stood at the engine's locks
(``lock_wait_submitter_s`` of ``TrainResult.extras`` over ``elapsed_s``):
the contended waits at ``state_lock``, ``key_lock`` and the context's lock,
each clocked only where the non-blocking try failed
(``instrumentation.ClockedLock``; always on).  It is a PART of
``submitter_busy``, which counts a lock wait as busy: the two are read side
by side, and what is left of the busy share is the thread's own work, the
interpreter and calls that block.  ``extras`` also names the holder:
``lock_wait_<lock>_submitter_behind_<holder>_s``.  0.0 where nothing
waited; None where the program keeps no such clock (before ISSUE 53)."""

from benchmark.metrics.updater_busy import busy_share

NAME = "submitter_lock_wait"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "lock_wait_submitter_s"


def read(run, trace):
    return busy_share(run, COUNTER)
