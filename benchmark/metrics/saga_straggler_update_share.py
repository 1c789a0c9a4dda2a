"""``straggler_update_share`` under ASAGA, by that metric's own ``read``: the
share of the accepted updates that came from the eight late workers, who
hold a quarter of the rows AND of the history table.
A file of its own because ``straggler_update_share`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.straggler_update_share import read  # noqa: F401

NAME = "saga_straggler_update_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"
