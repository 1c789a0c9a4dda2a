"""Share of its time a late worker is asleep: the sum of the injected
sleeps (``delay_sleep_s``, from their arguments) over ``straggler_workers``
x the seconds of the run that lay behind the calibration's end
(``elapsed_s - delay_calibrated_at_s``), all from ``TrainResult.extras``.
What is left of a late worker's time is its step, its way to the chip and
back and its wait for the barrier.  None where the program keeps no such
account (before ISSUE 51) or injected nothing."""

NAME = "delay_sleep_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"


def read(run, trace):
    result = run["result"]
    extras = result["extras"]
    late = extras.get("straggler_workers")
    if not late or not extras.get("delayed_tasks"):
        return None
    under = result["elapsed_s"] - extras["delay_calibrated_at_s"]
    if under <= 0:
        return None
    return 100.0 * extras["delay_sleep_s"] / (late * under)
