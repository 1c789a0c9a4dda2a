"""Median of the program's ``task.delay`` span: an injected straggler's
sleep, on its executor's thread, from the task closure's entry to the
sleep's end (``instrumentation.worker_task``; a child of ``compute``,
recorded only for a sampled task that was given a delay and whose sleep
fired).  The median is a DELAYED task's, not a task's: at ``coeff`` -1 six
of the eight late workers draw ``U(1.5, 2.5)`` x the scale and come round
more often than the two of the long tail, so it sits near twice
``delay_avg_ms``.  None where the program records no such stage (before
ISSUE 51, and in every run that injects nothing)."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "task_delay_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.delay"


def read(run, trace):
    return stage_p50(run, STAGE)
