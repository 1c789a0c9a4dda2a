"""Mean, over the sampled tasks, of the engine's calls into PJRT that were
IN PROGRESS when the task's step was called (``calls_in`` on the program's
``task.enqueue`` span, folded as ``program_trace["stages_calls_in"]``): the
other tasks' step calls and copies and the updater's dispatches, on any
thread, to any chip (``instrumentation.CallsIn``, one count a run).  n
calls made at once each take n times as long (PERF.md section 5), so this
is how crowded a step's call is; ``program_trace["enqueue_ms_by_calls_in"]``
has ``task.enqueue``'s median by it.  None where the program records no
such field (before ISSUE 53) or nothing was sampled."""

NAME = "enqueue_calls_in_mean"
UNIT = "calls"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "task.enqueue"
TABLE = "stages_calls_in"


def stage_field(run, table, stage, field):
    """One figure of a span field's histogram, by stage; None where no
    span carried the field."""
    hist = (run["program_trace"] or {}).get(table, {}).get(stage)
    if not hist or not hist.get("count"):
        return None
    return hist[field]


def read(run, trace):
    return stage_field(run, TABLE, STAGE, "mean")
