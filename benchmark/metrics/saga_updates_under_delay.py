"""``updates_under_delay`` under ASAGA, by that metric's own ``read``: the share
of the accepted updates made behind the calibration's end.
A file of its own because ``updates_under_delay`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.updates_under_delay import read  # noqa: F401

NAME = "saga_updates_under_delay"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"
