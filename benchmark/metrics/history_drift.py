"""How far ASAGA's running mean history gradient is from the table it
summarises when the run ends: ``max |alpha_bar - sum_i alpha_i x_i / n|``
over ``max |sum_i y_i x_i / n|``, the mean gradient at ``w = 0``
(``history_drift`` of ``TrainResult.extras``, computed by the program on
the device after the final read-back).  The exact table delta keeps it at
f32 rounding; a drifting ``alpha_bar`` biases every later step.  None
where the program does not report it."""

NAME = "history_drift"
UNIT = "ratio"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"


def read(run, trace):
    return run["result"]["extras"].get(NAME)
