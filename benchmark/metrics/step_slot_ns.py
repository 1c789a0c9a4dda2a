"""Device nanoseconds of one sparse worker step for each slot it samples:
the step's median device time (``step_device_ms``'s reading of the
profiler window) over ``sampled_slots_per_step`` of ``TrainResult.extras``
(the compaction's static capacity x the ELL width: every one of them is
gathered from ``w`` and scatter-added into ``g``, filled or not).  The
number to hold against 7.3 ns a gathered and 6.7 ns a scatter-added entry
(PERF.md section 6, PR 29).  None without a device trace, or where the
program does not say how many slots its step samples (a dense cell)."""

from benchmark.metrics.step_device_ms import step_seconds

NAME = "step_slot_ns"
UNIT = "ns"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"


def read(run, trace):
    s = step_seconds(trace)
    slots = run["result"]["extras"].get("sampled_slots_per_step")
    if s is None or not slots:
        return None
    return s / slots * 1e9
