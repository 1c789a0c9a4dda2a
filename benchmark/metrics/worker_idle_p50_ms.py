"""Median of the program's ``worker.idle`` span: a worker from its last
result to the submit that takes it again (sampled updates; a worker's first
task has none).  The cycle of a worker is this plus ``task_p50_ms`` less
the result's wait for the updater.  The recipe's barrier makes it
two-humped (the workers whose result completes a bucket go out at once, the
others wait for them), so the median can sit far under the mean, which is
``waiting_time_ms`` over the worker's updates."""

from benchmark.metrics.task_p50_ms import stage_p50

NAME = "worker_idle_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "worker.idle"


def read(run, trace):
    return stage_p50(run, STAGE)
