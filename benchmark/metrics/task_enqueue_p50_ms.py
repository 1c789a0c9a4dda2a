"""Median of the program's ``task.enqueue`` span: the jitted worker step's
call alone, in to returned, on the executor's thread (PJRT's own
``PjitFunction(step)`` event is the same interval in a device trace).  Host
work by kind; while the device's queue is full the call blocks and its
tail is a wait on the device.  It lies inside ``task.dispatch``
(``task_dispatch_p50_ms``), behind the turn's wait and the model's copy.
None where the program records no such stage (before ISSUE 41)."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_enqueue_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.enqueue"


def read(run, trace):
    return stage_p50(run, STAGE)
