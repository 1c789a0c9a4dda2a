"""``updater_history`` in the delayed 32-worker ASAGA cell, by that metric's
own ``read``: the share of the run the one updater thread spent dispatching
the table delta and the commit.  A file of its own because ``updater_history`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.updater_history import read  # noqa: F401

NAME = "w32_updater_history"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
