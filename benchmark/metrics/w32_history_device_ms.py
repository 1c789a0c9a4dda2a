"""``history_device_ms`` in the delayed 32-worker ASAGA cell, by that metric's
own ``read``: the history path's device time an update (every second of the
profiled window in ``jit_saga_table_delta`` plus every second in
``jit_saga_commit_history``, over the commits).  It rises with the share of
accepts that pay the second read of a 253,125-row shard.
A file of its own because ``history_device_ms`` lists its cells, and a list is
a ``benchmark`` PR's to extend (PR 46's and PR 51's way: PERF.md section 7)."""

from benchmark.metrics.history_device_ms import read  # noqa: F401

NAME = "w32_history_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"
