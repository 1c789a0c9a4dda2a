"""``merge_history_p50_ms`` in the delayed 32-worker ASAGA cell, by that
metric's own ``read``: the median of the ``merge.history`` span (which here
also carries its worker's class and the age of the slice it replaces).
A file of its own because ``merge_history_p50_ms`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.merge_history_p50_ms import read  # noqa: F401

NAME = "w32_merge_history_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
