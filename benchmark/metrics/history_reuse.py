"""Share of ASAGA's accepted updates whose table delta was the worker
step's own ``g`` (``history_reused`` over ``history_reused +
history_recomputed`` of ``TrainResult.extras``): the step read the history
slice that still stood when its result was accepted, so the shard was not
read a second time.  The rest paid the exact delta (``history_device_ms``
is the device time of one of those).  None where the program does not
count it."""

NAME = "history_reuse"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    extras = run["result"]["extras"]
    reused = extras.get("history_reused")
    recomputed = extras.get("history_recomputed")
    if reused is None or recomputed is None or not reused + recomputed:
        return None
    return 100.0 * reused / (reused + recomputed)
