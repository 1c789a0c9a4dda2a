"""The share of a run's tasks whose step took the model from a buffer
already on its shard's chip (``model_reads_local`` over ``model_reads_local
+ model_reads_copied`` of ``TrainResult.extras``; always on, counted where a
cohort's tasks are built).  The other tasks paid a ``device_put`` of the
model in front of their step: a call on the task's path and, where the
model is the output of an apply queued behind another chip's steps, a wait
between chips.  100% where every chip that holds a shard holds a replica
of the model; a quarter where four chips read one buffer on the driver's
chip.  None where the program counts neither (before ISSUE 47)."""

NAME = "model_read_local"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    extras = run["result"]["extras"]
    local = extras.get("model_reads_local")
    copied = extras.get("model_reads_copied")
    if local is None or copied is None or not local + copied:
        return None
    return 100.0 * local / (local + copied)
