"""Median of the program's ``task.inbox`` span: from ``compute``'s start
(the submitter, inside its ``submit``) to the task closure's entry on the
executor's thread.  It holds the rest of the submit in front of this task's
put into the inbox (``pin``, ``make_tasks``, ``run_job`` up to this task)
and then the executor's wake-up, which is ``task_wake_p50_ms``: the
difference of the two medians is the submitter's part.  Recorded since
PR 23; None where nothing was sampled."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_inbox_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.inbox"


def read(run, trace):
    return stage_p50(run, STAGE)
