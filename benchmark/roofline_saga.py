"""The bytes ASAGA's history path needs on an accepted update.

By ``roofline.py``'s convention: what the *algorithm* needs, from shapes
alone, every sampled row read once.  The table delta is ``X^T (mask *
(diff - alpha))`` over one shard; whatever the program moves beyond that
(with the shard stored column-major it reads ALL of it for the sampled
hundredth, PERF.md section 3) is its own cost and lowers the share.
"""

from __future__ import annotations

from typing import Dict


def table_delta_bytes(shard_rows: int, d: int, itemsize: int,
                      batch_rate: float) -> float:
    """Bytes one dense table delta needs: the sampled rows read once, one
    byte of mask a shard row, ``diff`` and ``alpha`` (f32) at the sampled
    rows, and ``delta`` out."""
    sampled = batch_rate * shard_rows
    return sampled * d * itemsize + shard_rows + 2 * sampled * 4 + d * 4


def delta_bytes(data: Dict[str, object], batch_rate: float) -> float:
    """Needed bytes of one table delta on the cell's (largest) shard, from
    the run record's ``data`` description; dense shards only (the padded-ELL
    delta works on rows the step already compacted: no cell has it)."""
    if data["kind"] != "dense":
        raise ValueError(f"no byte count for a {data['kind']!r} table delta")
    return table_delta_bytes(max(data["shard_rows"]), data["d"],
                             data["itemsize"], batch_rate)
