"""Mean staleness, in model versions, of the gradients the server merged
(the program's ``staleness_versions`` histogram, sampled)."""

NAME = "staleness_mean"
UNIT = "updates"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "time_to_target_s"


def read(run, trace):
    hist = (run["program_trace"] or {}).get("staleness_versions") or {}
    if not hist.get("count"):
        return None
    return hist["mean"]
