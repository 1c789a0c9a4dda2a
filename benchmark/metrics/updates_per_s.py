"""Accepted updates applied to the model per second of fenced wall-clock,
over the whole window: ``TrainResult.accepted / TrainResult.elapsed_s``
(``elapsed_s`` is taken after the final model's read-back)."""

NAME = "updates_per_s"
UNIT = "updates/s"
SOURCE = "host_clock"


def read(run, trace):
    r = run["result"]
    if not r["accepted"] or r["elapsed_s"] <= 0:
        return None
    return r["accepted"] / r["elapsed_s"]
