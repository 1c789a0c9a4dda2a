"""``history_drift`` in the delayed 32-worker ASAGA cell, by that metric's own
``read``: the program's own reading of ``alpha_bar`` against the mean of the
table the run left, over 32 slices of which eight were committed rarely.
A file of its own because ``history_drift`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.history_drift import read  # noqa: F401

NAME = "w32_history_drift"
UNIT = "ratio"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"
