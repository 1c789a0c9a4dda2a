"""How old a LATE worker's slice of ASAGA's history table is when one of its
results is accepted and replaces it, on the mean: the accepted updates since
that worker's previous commit (``history_age_late_sum`` over
``history_age_late_n`` of ``TrainResult.extras``; ``engine/straggler.py:
DelayModel.book_history_age``, booked by ASAGA's updater for every accept
behind the calibration's end of a worker that has committed before).  Until
then ``alpha_bar`` carries that slice's gradients: a quarter of the rows
under the cloud tail.  With nobody late it would read the worker count.
None where the program keeps no such count (before ISSUE 58: the parent's
record has neither key), where nobody is late (``coeff`` 0: zeros) and where
the run ended inside its calibration."""

NAME = "late_history_age"
UNIT = "updates"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"


def mean_age(run, who):
    """``history_age_<who>_sum`` over ``history_age_<who>_n`` of the run's
    ``extras``; None where the count is 0 or either key is absent."""
    extras = run["result"]["extras"]
    count = extras.get(f"history_age_{who}_n")
    total = extras.get(f"history_age_{who}_sum")
    if not count or total is None:
        return None
    return total / count


def read(run, trace):
    return mean_age(run, "late")
