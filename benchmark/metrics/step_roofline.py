"""The worker step's share of the HBM roofline: the bytes the algorithm
needs for one step (``roofline.step_bytes``: every sampled row read once)
over the step's median device time, over the chip's peak bytes per second.
The step is bandwidth-bound (about 2 flops a byte), so HBM is the bound."""

from benchmark import roofline
from benchmark.metrics.step_device_ms import step_seconds

NAME = "step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"


def read(run, trace):
    s = step_seconds(trace)
    if s is None or not run["peaks"]:
        return None
    need = roofline.step_bytes(run["data"], run["plan"]["batch_rate"])
    return 100.0 * need / s / run["peaks"]["hbm_bytes_per_s"]
