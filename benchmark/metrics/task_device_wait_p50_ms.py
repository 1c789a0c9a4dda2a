"""Median of the program's ``task.device_wait`` span: the executor's thread
inside ``block_until_ready`` on the step's gradient, which is the device's
queue ahead of the step plus the step (a wait: the host does nothing).
None where the program records no such stage."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_device_wait_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.device_wait"


def read(run, trace):
    return stage_p50(run, STAGE)
