"""``memory_stats()["peak_bytes_in_use"]`` after the window, maximum over
the cell's chips."""

NAME = "peak_hbm_gb"
UNIT = "GB"
SOURCE = "program_counter"
LAYER = "device"
MOVES = "updates_per_s"


def read(run, trace):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
