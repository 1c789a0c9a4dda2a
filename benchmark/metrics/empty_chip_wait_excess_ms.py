"""What a step costs on an EMPTY chip beyond its own device time: the
median of the program's ``task.device_wait.alone`` span (the wait of a
sampled task whose step was the only one out on its chip when its enqueue
returned: the runtime's launch, the step, and the completion's way back to
the host, with no sibling's step in front) less ``step_device_ms`` of the
same process's profiled run.  The device's side of "a chip was given its
step late".  The updater's applies on the driver's chip are not counted as
steps, so a task alone there may still wait behind one.  None without a
device trace, without the step's module in it, or where the program
records no such stage (before ISSUE 41) or no sampled task was alone."""

from benchmark.metrics.step_device_ms import step_seconds
from benchmark.metrics.task_p50_ms import stage_p50

NAME = "empty_chip_wait_excess_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.device_wait.alone"


def read(run, trace):
    alone_ms = stage_p50(run, STAGE)
    step_s = step_seconds(trace)
    if alone_ms is None or step_s is None:
        return None
    return alone_ms - step_s * 1e3
