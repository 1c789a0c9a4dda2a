"""Median device duration of the worker-step program in the profiler
window.  The step is the jitted function ``step`` of ``ops/steps.py``
today, so its XLA module is ``jit_step``; a refactor that renames it
brings a new metric file with its pattern."""

import re

NAME = "step_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"
MODULE = re.compile(r"^jit_step$")


def step_seconds(trace):
    if not trace:
        return None
    hits = [m for name, m in trace["modules"].items() if MODULE.match(name)]
    if not hits:
        return None
    return max(hits, key=lambda m: m["count"])["median_s"]


def read(run, trace):
    s = step_seconds(trace)
    return None if s is None else s * 1e3
