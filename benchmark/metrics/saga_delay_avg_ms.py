"""``delay_avg_ms`` under ASAGA, by that metric's own ``read``: the scale every
injected sleep is a multiple of, the run's OWN mean task time over its
calibration (longer than ASGD's in the same layout: the unfolded updater).
A file of its own because ``delay_avg_ms`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.delay_avg_ms import read  # noqa: F401

NAME = "saga_delay_avg_ms"
UNIT = "ms"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
