"""Seconds between the submitter loop's exit (the deadline, in a cell) and
the end of the fence: ``run_tail_s`` of ``TrainResult.extras``.  All of it
lies inside ``elapsed_s`` while nothing is accepted in it: the updater
joined, the pool shut down, the final model read back behind the steps
that were in flight at the deadline and finish uncounted.  It shrinks with
the step, so a faster step raises ``updates_per_s`` by more than its own
factor (PERF.md section 6, PR 33).  None where the program does not count
it."""

NAME = "run_tail_s"
UNIT = "s"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    return run["result"]["extras"].get("run_tail_s")
