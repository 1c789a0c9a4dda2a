"""Share of the run the one updater thread spent outside its blocking
collect (``updater_busy_s`` of ``TrainResult.extras`` over ``elapsed_s``):
lock, tau filter, apply dispatch, snapshots -- and whatever blocked it
there: an apply dispatch waits while the device's queue is full
(``updater_apply_s`` of ``extras`` is that part).  So ``accepted /
updater_busy_s`` bounds the rate at which the updater alone would saturate
from below.  None where the program does not count it."""

NAME = "updater_busy"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "updater_busy_s"


def busy_share(run, counter):
    """A thread's busy seconds over the run's fenced seconds, in percent."""
    result = run["result"]
    busy = result["extras"].get(counter)
    if busy is None or not result["elapsed_s"]:
        return None
    return 100.0 * busy / result["elapsed_s"]


def read(run, trace):
    return busy_share(run, COUNTER)
