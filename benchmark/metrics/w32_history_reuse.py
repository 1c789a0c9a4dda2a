"""``history_reuse`` in the delayed 32-worker ASAGA cell, by that metric's own
``read``: the share of accepted updates whose table delta was the step's own
``g``.  With 32 slices in flight and a partial barrier of 22, does a worker
still find its slice as its step read it?  A file of its own because ``history_reuse`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.history_reuse import read  # noqa: F401

NAME = "w32_history_reuse"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
