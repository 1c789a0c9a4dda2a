"""The chip's published peaks, and the bytes a worker step needs.

The gradient cells are bandwidth-bound (a sampled ``X^T (X w - y)`` does
about 2 flops per byte read), so their yardstick is bytes per second
against the HBM peak and not MFU.  The byte counts are what the
*algorithm* needs for one step, from shapes alone: every sampled row read
once.  Whatever the program moves beyond that (a mask over the whole
shard, a gather copy read back twice, an argsort) is its own cost and
lowers the share.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{_PEAKS_FILE} (known: {sorted(table)})"
        )
    return dict(table[device_kind])


def dense_step_bytes(shard_rows: int, d: int, itemsize: int,
                     batch_rate: float) -> float:
    """Bytes one dense worker step needs: the sampled rows read once, one
    byte of mask a shard row, the sampled labels, ``w`` in and ``g`` out."""
    sampled = batch_rate * shard_rows
    return sampled * d * itemsize + shard_rows + sampled * 4 + 2 * d * 4


def sparse_step_bytes(shard_rows: int, width: int, d: int, batch_rate: float,
                      itemsize: int, index_itemsize: int) -> float:
    """Bytes one padded-ELL worker step needs: the sampled rows' values
    (``itemsize`` each) and columns (``index_itemsize`` each), as the shard
    stores them, read once, one byte of mask a shard row, the sampled
    labels, and the touched entries of ``w`` (read) and ``g`` (written), at
    most ``d`` each."""
    sampled = batch_rate * shard_rows
    touched = min(sampled * width, d)
    return (sampled * width * (itemsize + index_itemsize) + shard_rows
            + sampled * 4 + 2 * touched * 4)


def step_bytes(data: Dict[str, object], batch_rate: float) -> float:
    """Needed bytes of one step on the cell's (largest) shard, from the
    run record's ``data`` description."""
    rows = max(data["shard_rows"])
    if data["kind"] == "sparse":
        return sparse_step_bytes(rows, data["width"], data["d"], batch_rate,
                                 data["itemsize"], data["index_itemsize"])
    return dense_step_bytes(rows, data["d"], data["itemsize"], batch_rate)
