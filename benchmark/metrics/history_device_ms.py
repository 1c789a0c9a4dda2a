"""Median device time of ASAGA's history path on an accepted update: the
table delta (XLA module ``jit_saga_table_delta``: one pass over the shard)
plus the history commit (``jit_saga_commit_history``), from the profiler
window.  None where the trace holds no such pair: a cell that is not
ASAGA, or a program whose delta has another name."""

import re

NAME = "history_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"
DELTA = re.compile(r"^jit_saga_table_delta$")
COMMIT = re.compile(r"^jit_saga_commit_history$")


def module_seconds(trace, pattern):
    """Median device seconds of the most-run module that matches."""
    if not trace:
        return None
    hits = [m for name, m in trace["modules"].items() if pattern.match(name)]
    if not hits:
        return None
    return max(hits, key=lambda m: m["count"])["median_s"]


def read(run, trace):
    delta = module_seconds(trace, DELTA)
    commit = module_seconds(trace, COMMIT)
    if delta is None or commit is None:
        return None
    return (delta + commit) * 1e3
