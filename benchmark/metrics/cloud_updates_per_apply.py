"""``updates_per_apply`` in the straggler cell, by that metric's own
``read``: accepted updates to a device dispatch of ASGD's updater.  A file
of its own because ``updates_per_apply`` lists its cells, and a list is a
``benchmark`` PR's to extend (the doubling is PR 46's way, and goes where
that one goes: PERF.md section 7)."""

from benchmark.metrics.updates_per_apply import read  # noqa: F401

NAME = "cloud_updates_per_apply"
UNIT = "updates"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
