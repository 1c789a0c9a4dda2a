"""Nanoseconds the trajectory evaluation took for each slot its gathers
picked (``trajectory_eval_s`` over ``eval_slots`` of ``TrainResult.extras``:
row blocks x rows a block x ELL width, over every shard and every call of
eight snapshots).  One gathered slot serves up to eight snapshots, so this
is the number to hold against the 7.3 ns a ``w[col]`` of ONE model costs on
the v5e (PERF.md section 6, PR 29).  None where the program counts no
slots: a dense cell, or a program without the blocked evaluation."""

NAME = "eval_slot_ns"
UNIT = "ns"
SOURCE = "program_counter"
LAYER = "steps"
MOVES = "setup_s"


def read(run, trace):
    extras = run["result"]["extras"]
    seconds, slots = extras.get("trajectory_eval_s"), extras.get("eval_slots")
    if seconds is None or not slots:
        return None
    return seconds / slots * 1e9
