"""Median of the program's ``compute`` span: a worker task from submit to
result on the host clock (``metrics.trace`` aggregator, sampled)."""

NAME = "task_p50_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "engine"
MOVES = "updates_per_s"
STAGE = "compute"


def stage_p50(run, stage):
    """Median of one of the program's span stages, None if none was sampled."""
    hist = (run["program_trace"] or {}).get("stages_ms", {}).get(stage)
    if not hist or not hist.get("count"):
        return None
    return hist["p50"]


def read(run, trace):
    return stage_p50(run, STAGE)
