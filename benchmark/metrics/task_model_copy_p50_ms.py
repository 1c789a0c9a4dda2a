"""Median of the program's ``task.model_copy`` span: ONE ``device_put``
that really copies what a step needs (the model handle, from the driver's
chip) to the worker's chip, inside ``task.dispatch``.  One span a copy, so
a task on the driver's chip has none and the median is a copy's, not a
task's.  Only where a process spreads its workers over several chips; None
where nothing was copied or the program records no such span (before
ISSUE 41 the stage was an annotation only)."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_model_copy_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.model_copy"


def read(run, trace):
    return stage_p50(run, STAGE)
