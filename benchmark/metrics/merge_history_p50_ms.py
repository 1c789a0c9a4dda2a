"""Median of the program's ``merge.history`` span: inside ``merge.apply``,
on the updater's thread under the state lock, the dispatch of the table
delta and of the history commit (host work; with the device's queue full
the enqueue blocks inside it).  None where the program records no such
stage."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "merge_history_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "merge.history"


def read(run, trace):
    return stage_p50(run, STAGE)
