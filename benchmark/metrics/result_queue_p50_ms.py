"""Median of the program's ``result.queue`` span: a finished task's result
from the handler's put to the updater's drain (a wait: how far the one
updater thread runs behind the executors).  None where the program records
no such stage."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "result_queue_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "result.queue"


def read(run, trace):
    return stage_p50(run, STAGE)
