"""Median of the program's ``task.turn`` span: a task's wait, on its
executor's thread and inside ``task.dispatch``, for the tasks built before
it to have enqueued their steps on the same chip
(``instrumentation.DispatchTurns``: shards of unequal width reach one
device queue in the cohort's order).  A chip without turns records the
stage empty.  None where the program records no such stage (before
ISSUE 41)."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_turn_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.turn"


def read(run, trace):
    return stage_p50(run, STAGE)
