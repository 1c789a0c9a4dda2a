"""Counted flops of a worker gradient.

The reference never reports compute efficiency (its metric is wall-clock to
target loss); the solvers count the flops of every worker gradient they
merge (``TrainResult.total_flops``).  What the chip could do is the
benchmark's table (``benchmark/peaks.json``), not this module's.

Flop model (counted, not estimated): a dense worker step is two matmuls over
the full shard -- residual ``X @ w`` and gradient ``X^T @ (mask*r)`` -- i.e.
``4 * n_p * d`` flops (2 per multiply-add).  A sparse (padded-ELL) step is the
gather/scatter pair at ``4 * n_p * K`` (padding lanes execute real FMAs).  The
trajectory evaluation runs outside the timed region and is not counted.
"""

from __future__ import annotations


def dense_task_flops(n_rows: int, d: int) -> float:
    """Flops of one dense worker gradient over an ``(n_rows, d)`` shard."""
    return 4.0 * n_rows * d


def sparse_task_flops(n_rows: int, k_padded: int) -> float:
    """Flops of one padded-ELL worker gradient (gather + scatter lanes)."""
    return 4.0 * n_rows * k_padded
