"""``delay_sleep_share`` under ASAGA, by that metric's own ``read``: the share
of its time a late worker is asleep once the tail is on.
A file of its own because ``delay_sleep_share`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.delay_sleep_share import read  # noqa: F401

NAME = "saga_delay_sleep_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
