"""Share of the profiler window in which no operation ran on the device:
1 minus the union of device-op intervals over the window, mean over the
cell's chips.  High means the host sets the pace."""

NAME = "device_idle"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "updates_per_s"


def read(run, trace):
    if not trace:
        return None
    return 100.0 * trace["idle_share"]
