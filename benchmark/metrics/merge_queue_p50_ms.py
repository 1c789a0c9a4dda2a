"""Median of the program's ``merge.queue`` span: from a result's drain to
the start of its apply (the updater's lock and tau filter)."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "merge_queue_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "merge.queue"


def read(run, trace):
    return stage_p50(run, STAGE)
