"""The table delta's share of the HBM roofline: the bytes the algorithm
needs for one delta (``roofline_saga.delta_bytes``: every sampled row read
once) over the delta's median device time, over the chip's peak bytes per
second.  With the shard stored column-major the delta reads all of it for
the sampled share, so the ceiling is about ``batch_rate`` (PERF.md section
3): a low reading is the storage, not slack in the fusion."""

from benchmark import roofline_saga
from benchmark.metrics.history_device_ms import DELTA, module_seconds

NAME = "history_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"


def read(run, trace):
    s = module_seconds(trace, DELTA)
    if s is None or not run["peaks"] or run["data"]["kind"] != "dense":
        return None
    need = roofline_saga.delta_bytes(run["data"], run["plan"]["batch_rate"])
    return 100.0 * need / s / run["peaks"]["hbm_bytes_per_s"]
