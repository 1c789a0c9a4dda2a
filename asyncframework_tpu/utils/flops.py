"""Achieved-FLOP/s and MFU accounting.

The reference never reports compute efficiency (its metric is wall-clock to
target loss); the TPU build records it so "matching-or-beating on perf"
carries an absolute number: solvers count the flops of every worker gradient
they merge, and the bench divides by elapsed time and the chip's peak.

Flop model (counted, not estimated): a dense worker step is two matmuls over
the full shard -- residual ``X @ w`` and gradient ``X^T @ (mask*r)`` -- i.e.
``4 * n_p * d`` flops (2 per multiply-add).  A sparse (padded-ELL) step is the
gather/scatter pair at ``4 * n_p * K`` (padding lanes execute real FMAs).  The
trajectory evaluation runs outside the timed region and is not counted.

Peak table: dense matmul peak per chip for bf16 inputs (MXU native; the
industry-standard MFU denominator).  f32 runs are still divided by the bf16
peak -- that is deliberate: MFU answers "what fraction of the chip's usable
matmul throughput did the run extract", and on TPU the usable peak IS the
bf16 MXU rate (f32 matmuls lower to multi-pass bf16).
"""

from __future__ import annotations

from typing import Optional

#: dense-matmul bf16 peak FLOP/s per chip (public specs), keyed by the
#: ``device_kind`` string JAX reports.  "TPU v5 lite" is what a v5e chip
#: answers (chip_smoke.py on the v5e, PR 21); the other spellings are from
#: the JAX sources and have not met this repo's code.
_PEAK_BF16 = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # Trillium / v6e
}


def chip_peak_flops(device) -> Optional[float]:
    """bf16 dense-matmul peak for ``device``.  None on the CPU platform
    (no MXU peak; MFU is reported null).  A TPU whose ``device_kind`` is
    not in the table is an error, not a default: a utilization divided by
    a guessed peak is a wrong number."""
    if getattr(device, "platform", "") != "tpu":
        return None
    kind = str(getattr(device, "device_kind", ""))
    if kind not in _PEAK_BF16:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it to "
            f"utils/flops._PEAK_BF16 with its source"
        )
    return _PEAK_BF16[kind]


def dense_task_flops(n_rows: int, d: int) -> float:
    """Flops of one dense worker gradient over an ``(n_rows, d)`` shard."""
    return 4.0 * n_rows * d


def sparse_task_flops(n_rows: int, k_padded: int) -> float:
    """Flops of one padded-ELL worker gradient (gather + scatter lanes)."""
    return 4.0 * n_rows * k_padded


def mfu(total_flops: float, elapsed_s: float, device) -> Optional[float]:
    """Model FLOP utilization in [0, 1]; None when the peak is unknown."""
    peak = chip_peak_flops(device)
    if peak is None or elapsed_s <= 0:
        return None
    return total_flops / elapsed_s / peak
