"""HBM budget planning helpers.

Parity: the reference's ``UnifiedMemoryManager`` (``memory/
UnifiedMemoryManager.scala:47``) arbitrates execution vs storage memory and
decides spill; on TPU the XLA allocator owns HBM, so the useful capability
is *planning*: will this dataset + model + history table fit per device, and
how many workers per device keep it that way.  Used by the data layer before
committing shards to HBM.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax

#: planning budget for the CPU platform only, whose runtime reports no limit
CPU_PLAN_BYTES = 16 * 1024**3


def nbytes(shape: Sequence[int], dtype=np.float32) -> int:
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def device_hbm_bytes(device=None) -> int:
    """Total memory of a device as its runtime reports it.  The CPU
    platform reports nothing and plans against ``CPU_PLAN_BYTES`` (tests
    exercise the planner there); an accelerator that reports nothing is an
    error -- planning 16 GiB for an unknown chip would hide it."""
    dev = device or jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_PLAN_BYTES
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"bytes_limit in memory_stats(); cannot plan its memory"
        )
    return int(limit)


def device_hbm_in_use(device=None) -> Optional[int]:
    dev = device or jax.devices()[0]
    try:
        stats = dev.memory_stats() or {}
    except (AttributeError, NotImplementedError, jax.errors.JaxRuntimeError):
        return None
    used = stats.get("bytes_in_use")
    return int(used) if used is not None else None


@dataclass(frozen=True)
class ShardPlan:
    """Outcome of :func:`plan_dataset`: per-device residency estimate."""

    bytes_per_device: int
    budget_bytes: int
    fits: bool
    utilization: float

    def require_fits(self) -> "ShardPlan":
        if not self.fits:
            raise MemoryError(
                f"planned shard residency {self.bytes_per_device / 1e9:.2f} GB "
                f"exceeds the {self.budget_bytes / 1e9:.2f} GB device budget"
            )
        return self


def plan_dataset(
    n: int,
    d: int,
    num_workers: int,
    num_devices: int,
    dtype=np.float32,
    with_labels: bool = True,
    history_table: bool = False,
    model_versions: int = 2,
    budget_bytes: Optional[int] = None,
    headroom: float = 0.85,
) -> ShardPlan:
    """Estimate per-device HBM residency for a sharded training setup.

    Accounts for: the data shards living on the device (workers sharing a
    device stack their shards), labels, the ASAGA history slice (one f32 per
    sample) when ``history_table``, and ``model_versions`` model-sized
    buffers (``d`` f32 each: the live model, results in flight, versions
    pinned by tasks, snapshots, an evaluation's stack;
    ``solvers.base.planned_model_copies``).  ``headroom`` reserves a fraction
    of the budget for XLA workspace/fusion temporaries.
    """
    if num_devices < 1 or num_workers < 1:
        raise ValueError("num_workers and num_devices must be >= 1")
    budget = budget_bytes if budget_bytes is not None else device_hbm_bytes()
    workers_per_device = -(-num_workers // num_devices)  # ceil
    rows_per_worker = -(-n // num_workers)
    per_worker = nbytes((rows_per_worker, d), dtype)
    if with_labels:
        per_worker += nbytes((rows_per_worker,), dtype)
    if history_table:
        per_worker += nbytes((rows_per_worker,), np.float32)
    total = workers_per_device * per_worker
    total += model_versions * nbytes((d,), np.float32)
    usable = int(budget * headroom)
    return ShardPlan(
        bytes_per_device=int(total),
        budget_bytes=usable,
        fits=total <= usable,
        utilization=total / usable if usable else float("inf"),
    )


def dataset_residency_bytes(ds) -> Dict[object, int]:
    """Actual per-device bytes of an already-placed sharded dataset
    (dense or sparse): what the shards occupy in each device's HBM."""
    per_dev: Dict[object, int] = {}
    for wid in range(ds.num_workers):
        s = ds.shard(wid)
        arrays = (
            (s.cols, s.vals, s.y) if hasattr(s, "cols") else (s.X, s.y)
        )
        dev = arrays[0].device
        per_dev[dev] = per_dev.get(dev, 0) + sum(
            int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays
        )
    return per_dev


def plan_for_run(
    ds_or_shape,
    num_workers: int,
    num_devices: int,
    history_table: bool = False,
    model_versions: int = 2,
    budget_bytes: Optional[int] = None,
    headroom: float = 0.85,
) -> ShardPlan:
    """Placement plan for one training run.

    ``ds_or_shape`` is either a *placed* dataset (actual residency measured
    from its shards) or an ``(n, d)`` tuple for data not yet placed (planned
    from shapes).  Solvers call this before training and fail fast via
    :meth:`ShardPlan.require_fits` when the budget is oversubscribed.
    """
    if isinstance(ds_or_shape, tuple):
        n, d = ds_or_shape
        return plan_dataset(
            n, d, num_workers, num_devices,
            history_table=history_table, model_versions=model_versions,
            budget_bytes=budget_bytes, headroom=headroom,
        )
    ds = ds_or_shape
    budget = budget_bytes if budget_bytes is not None else device_hbm_bytes()
    per_dev = dataset_residency_bytes(ds)
    worst = max(per_dev.values()) if per_dev else 0
    extra = model_versions * nbytes((ds.d,), np.float32)
    if history_table:
        # one slice per WORKER; workers sharing a device stack their slices
        workers_per_device = -(-num_workers // num_devices)
        extra += workers_per_device * nbytes(
            (-(-ds.n // num_workers),), np.float32
        )
    total = worst + extra
    usable = int(budget * headroom)
    return ShardPlan(
        bytes_per_device=int(total),
        budget_bytes=usable,
        fits=total <= usable,
        utilization=total / usable if usable else float("inf"),
    )


def fmt_bytes(b: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(b) < 1024 or unit == "TiB":
            return f"{b:.1f} {unit}" if unit != "B" else f"{b} B"
        b /= 1024
    return f"{b:.1f} TiB"  # pragma: no cover
