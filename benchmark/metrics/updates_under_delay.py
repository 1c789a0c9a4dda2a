"""Share of the accepted updates made behind the calibration's end, with
the stragglers late (``accepted_after_calibration`` over ``accepted``):
how much of the window the cell's end-to-end numbers read UNDER the tail.
The calibration is ``100 x num_workers`` accepted updates whatever the
rate, so a faster engine raises it.  None where the program keeps no such
account (before ISSUE 51) or injected nothing."""

NAME = "updates_under_delay"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"


def read(run, trace):
    result = run["result"]
    under = result["extras"].get("accepted_after_calibration")
    if not under or not result["accepted"]:
        return None
    return 100.0 * under / result["accepted"]
