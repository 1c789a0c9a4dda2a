"""Mean CPU time of the thread that called a sampled task's step, inside
the call (``cpu_ms`` on the program's ``task.enqueue`` span, from
``time.thread_time_ns`` in and out; ``program_trace["stages_cpu_ms"]``).
The MEAN, not the median ISSUE 53 asked for: the thread CPU clock of the
v5e's host ticks every 10 ms, so one call reads 0 or 10 ms, the median of
any cell is 0.0, and only the mean over a run's thousand sampled calls
converges on what a call costs (to about 0.05 ms).  To be read beside
``task_enqueue_p50_ms`` and the stage's mean, the same call on the wall
clock: wall less CPU is what the thread spent OFF the processor, blocked on
the interpreter lock, on a lock of PJRT's or on a full device queue (none
of the program's own locks lies inside the call).  CPU time flat in the
calls in progress while the wall time grows says the calls WAIT for each
other; CPU time that grows says they work against each other
(``program_trace["enqueue_cpu_ms_by_calls_in"]`` beside
``["enqueue_ms_by_calls_in"]``).  None where the program records no such
field (before ISSUE 53) or nothing was sampled."""

from benchmark.metrics.enqueue_calls_in_mean import stage_field

NAME = "task_enqueue_cpu_mean_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.enqueue"
TABLE = "stages_cpu_ms"


def read(run, trace):
    return stage_field(run, TABLE, STAGE, "mean")
