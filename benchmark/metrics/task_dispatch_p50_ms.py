"""Median of the program's ``task.dispatch`` span: on the executor's
thread, from the task closure's entry to the return of the worker step's
dispatch, the copy of the model handle to the worker's chip included (host
work; the device has only been handed the step).  None where the program
records no such stage."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_dispatch_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.dispatch"


def read(run, trace):
    return stage_p50(run, STAGE)
