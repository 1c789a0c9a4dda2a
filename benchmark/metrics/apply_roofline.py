"""The updater's apply as a share of the HBM roofline: the bytes one
dispatch needs (``roofline_apply.apply_bytes``: the model read and written
once, each folded gradient read once; the gradients a dispatch folds are
the run's ``accepted`` over its ``apply_dispatches``) over the window's
device seconds a dispatch (``apply_device_ms``), over the chip's peak bytes
per second.  An apply is an axpy, so HBM is the bound.  None without a
device trace or peaks, or where the program does not count its dispatches
or say its ``model_bytes``."""

from benchmark import roofline_apply
from benchmark.metrics.apply_device_ms import apply_seconds

NAME = "apply_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "steps"
MOVES = "updates_per_s"


def read(run, trace):
    s = apply_seconds(trace)
    result = run["result"]
    extras = result["extras"]
    dispatches = extras.get("apply_dispatches")
    model_bytes = extras.get("model_bytes")
    if s is None or not run["peaks"] or not dispatches or not model_bytes:
        return None
    need = roofline_apply.apply_bytes(
        model_bytes, result["accepted"] / dispatches)
    return 100.0 * need / s / run["peaks"]["hbm_bytes_per_s"]
