"""Device milliseconds of one apply dispatch of the updater: every second
the profiler window spent in the apply executables (the XLA modules whose
names start ``jit_apply``: ``jit_apply``, one result, and
``jit_apply_fold``, a drain of several, ``ops/steps.py``) over the number
of their dispatches in the window.  At a model of 219 MB an apply moves
0.66 GB where the dense cells' moves 9 kB.  None without a device trace,
or where the window holds no apply (a program that names it otherwise)."""

NAME = "apply_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"
PREFIX = "jit_apply"


def apply_seconds(trace):
    """Seconds of one apply dispatch in the window, or None."""
    if not trace:
        return None
    hits = [m for name, m in trace["modules"].items()
            if name.startswith(PREFIX)]
    count = sum(m["count"] for m in hits)
    if not count:
        return None
    return sum(m["total_s"] for m in hits) / count


def read(run, trace):
    s = apply_seconds(trace)
    return None if s is None else s * 1e3
