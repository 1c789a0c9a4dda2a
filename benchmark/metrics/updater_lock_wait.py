"""Share of the run the one updater thread stood at the engine's locks
(``lock_wait_updater_s`` of ``TrainResult.extras`` over ``elapsed_s``):
the contended waits at ``state_lock`` (twice a drain, once a dispatch),
``key_lock`` (the account of model copies; under ASAGA the history
slices, booked as ``history``) and the context's lock.  A PART of
``updater_busy``, which counts a lock wait as busy: beside it, it says how
much of a 99% busy updater is standing.  0.0 where nothing waited; None
where the program keeps no such clock (before ISSUE 53)."""

from benchmark.metrics.updater_busy import busy_share

NAME = "updater_lock_wait"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "lock_wait_updater_s"


def read(run, trace):
    return busy_share(run, COUNTER)
