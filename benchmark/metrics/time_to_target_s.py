"""The paper's metric of record: accepted updates until the objective first
falls to ``target_fraction`` of its value at ``w = 0`` (``target.py``),
over the window's fenced rate.  Hardware efficiency times statistical
efficiency: it catches a change that buys updates by making them staler."""

from benchmark import target

NAME = "time_to_target_s"
UNIT = "s"
SOURCE = "host_clock"


def read(run, trace):
    hit = run["target"]["updates_to_target"]
    r = run["result"]
    if hit is None or not r["accepted"]:
        return None
    return target.time_to_target_s(hit, r["accepted"], r["elapsed_s"])
