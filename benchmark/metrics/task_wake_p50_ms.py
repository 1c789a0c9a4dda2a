"""Median of the program's ``task.wake`` span: from this task's put into
its executor's inbox (the submitter's thread, inside ``run_job``) to the
task closure's entry on the executor's thread: the queue's hand-over, the
thread's wake-up and its wait for the interpreter.  It lies inside
``task.inbox`` (``task_inbox_p50_ms``).  None where the program records no
such stage (before ISSUE 41)."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_wake_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.wake"


def read(run, trace):
    return stage_p50(run, STAGE)
