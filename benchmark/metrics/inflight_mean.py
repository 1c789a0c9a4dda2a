"""Tasks in flight on average: ``inflight_task_s`` of ``TrainResult.extras``
(the integral over the run of the tasks between their submit and their
result, every update of a traced run) over ``elapsed_s``.  Its interval
ends at the result, ``task_p50_ms``'s at the updater's drain, so it reads a
little under the rate times ``task_p50_ms``; against the worker count it
says how much of the fleet the recipe's barrier lets work.  None where the
program keeps no occupancy account."""

NAME = "inflight_mean"
UNIT = "tasks"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    result = run["result"]
    task_s = result["extras"].get("inflight_task_s")
    if task_s is None or not result["elapsed_s"]:
        return None
    return task_s / result["elapsed_s"]
