"""The longest the program's 0.25 s heartbeat ticker woke late in the run
(``host_stall_max_ms`` of ``TrainResult.extras``): how long the host held
that Python thread, and most likely every other, at its worst.  A run on
a quiet host reads a millisecond or two; PR 22 met holds of 1-3.5 s.  None
where the program does not count it."""

NAME = "host_stall_max_ms"
UNIT = "ms"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    return run["result"]["extras"].get(NAME)
