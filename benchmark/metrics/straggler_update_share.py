"""Share of the accepted updates that came from the late workers
(``accepted_from_stragglers`` over ``accepted``): 25 where a quarter of the
workers is marked and nobody is late, and under the tail what a quarter of
the DATA still contributes to the descent.  None where the program keeps
no such account (before ISSUE 51) or no worker is marked late."""

NAME = "straggler_update_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"


def read(run, trace):
    result = run["result"]
    extras = result["extras"]
    if not extras.get("straggler_workers") or not result["accepted"]:
        return None
    if not extras.get("delay_calibrated_at_update"):
        return None  # the run ended inside its calibration
    return 100.0 * extras["accepted_from_stragglers"] / result["accepted"]
