"""Device time of ASAGA's history path AN UPDATE, from the profiler
window: every second the window spent in the table delta (XLA module
``jit_saga_table_delta``, a second pass over the shard, which runs only on
the accepts whose slice moved while their step was in flight: none where
the window holds none) plus every second in the history commit
(``jit_saga_commit_history``, on every accept), over the number of commits.
Until PR 29 this name read the MEDIAN of one recomputed delta plus the
median commit (2.14 ms where this reads about 0.03): the metric was
re-pointed, the program did not get faster.  None where the trace holds no
commit: a cell that is not ASAGA, or a program whose commit has another
name."""

NAME = "history_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"
DELTA = "jit_saga_table_delta"
COMMIT = "jit_saga_commit_history"


def read(run, trace):
    modules = trace["modules"] if trace else {}
    if COMMIT not in modules:
        return None
    commit = modules[COMMIT]
    delta_s = modules[DELTA]["total_s"] if DELTA in modules else 0.0
    return (delta_s + commit["total_s"]) / commit["count"] * 1e3
