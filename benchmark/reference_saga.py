"""Plain reference for the ASAGA cells: the history table and its mean.

Float32 ``jax.numpy`` at precision "highest", no code of the program, the
shard walked in row blocks as ``reference.py`` walks it (a bf16 shard is
never held whole in f32).  SAGA for least squares keeps one scalar a row,
``alpha_i`` (the residual ``x_i . w - y_i`` as of the last time row ``i``
was sampled), and ``alpha_bar = sum_i alpha_i x_i / n``, the mean history
gradient.  One task over a shard, with a Bernoulli mask ``m`` and the
model ``w`` and history ``alpha_read`` it was handed:

    diff   = X w - y
    g      = X^T (m (diff - alpha_read))        what the task returns
    delta  = X^T (m (diff - alpha_cur))         on accept, against the history NOW
    alpha  = where(m, diff, alpha_cur)          the commit
    w     -= gamma g / par_recs + gamma alpha_bar ;  alpha_bar += delta / n

Departures from the reference drivers, each on purpose:

- ``SparkASAGAThread.scala:210-213`` advances ``alphaBar`` by the task's own
  ``g`` (``delta == g``).  That is exact only while the slice did not change
  between dispatch and accept; a worker dispatched again before its last
  result was committed makes ``alpha_bar`` drift from the table's mean for
  good.  Here, as in the program, ``alpha_bar`` moves by the exact change of
  the table, so ``alpha_bar == history_mean(table)`` holds after every
  update.
- The drivers' acceptance test (``k - staleness <= taw``, on the iteration
  count, not the staleness) is not modelled: a replay is given the results
  that WERE accepted, in the order they were.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import BLOCK_ROWS, _block, _dot, _f32


def _rows(a, s, block):
    return jax.lax.dynamic_slice_in_dim(a, s, block)


@functools.partial(jax.jit, static_argnames=("block",))
def _mean_block(X, alpha, start, block):
    s, live = _block(X, start, block)
    Xb = _rows(X, s, block).astype(jnp.float32)
    return _dot((_rows(alpha, s, block) * live)[None, :], Xb)[0]


@functools.partial(jax.jit, static_argnames=("block",), donate_argnums=(6,))
def _task_block(X, y, w, alpha_read, alpha_cur, mask, diff, start, block):
    """One row block of a task: its part of ``g`` and ``delta``, and
    ``diff`` with the block's own rows written in."""
    s, live = _block(X, start, block)
    Xb = _rows(X, s, block).astype(jnp.float32)
    db = _dot(Xb, w) - _rows(y, s, block)
    mb = _rows(mask, s, block) * live
    g = _dot((mb * (db - _rows(alpha_read, s, block)))[None, :], Xb)[0]
    delta = _dot((mb * (db - _rows(alpha_cur, s, block)))[None, :], Xb)[0]
    diff = jax.lax.dynamic_update_slice_in_dim(
        diff, jnp.where(live, db, _rows(diff, s, block)), s, 0
    )
    return g, delta, diff


def history_mean(shards: Sequence, alphas: Sequence, n: int,
                 block_rows: int = BLOCK_ROWS) -> np.ndarray:
    """``sum_i alpha_i x_i / n`` over every shard, float64 on the host: what
    ``alpha_bar`` must equal.  ``alphas[k]`` is the history slice of
    ``shards[k]``.  Dense shards only: a padded-ELL ``X^T alpha`` is a
    scatter, which no cell needs yet."""
    total = None
    for shard, alpha in zip(shards, alphas):
        rows = int(shard.X.shape[0])
        a = _f32(alpha, shard.X.device)
        block = min(block_rows, rows)
        for start in range(0, rows, block):
            part = np.asarray(_mean_block(shard.X, a, start, block=block),
                              np.float64)
            total = part if total is None else total + part
    return total / n


def history_drift(shards: Sequence, alphas: Sequence, alpha_bar, n: int,
                  block_rows: int = BLOCK_ROWS) -> float:
    """How far ``alpha_bar`` is from the mean of the table it summarises:
    ``max |alpha_bar - history_mean(table)|`` over ``max |X^T y / n|``, the
    mean gradient at ``w = 0``.  The data's unit and not ``max
    |alpha_bar|``: ``alpha_bar`` goes to zero as a run converges while the
    rounding of its early updates stays."""
    mean = history_mean(shards, alphas, n, block_rows)
    unit = np.max(np.abs(
        history_mean(shards, [s.y for s in shards], n, block_rows)))
    off = np.max(np.abs(np.asarray(alpha_bar, np.float64) - mean))
    return float(off / unit)


def task(shard, w, alpha_read, alpha_cur, mask,
         block_rows: int = BLOCK_ROWS) -> Dict[str, jax.Array]:
    """One task and its accept on one shard: ``g``, ``diff``, ``delta`` and
    the committed slice ``alpha``, f32 arrays on the shard's device."""
    X = shard.X
    rows = int(X.shape[0])
    dev = X.device
    w, alpha_read, alpha_cur, mask = (
        _f32(a, dev) for a in (w, alpha_read, alpha_cur, mask)
    )
    diff = jax.device_put(jnp.zeros(rows, jnp.float32), dev)
    block = min(block_rows, rows)
    g = delta = None
    for start in range(0, rows, block):
        gb, db, diff = _task_block(X, shard.y, w, alpha_read, alpha_cur,
                                   mask, diff, start, block=block)
        g = gb if g is None else g + gb
        delta = db if delta is None else delta + db
    return {"g": g, "diff": diff, "delta": delta,
            "alpha": jnp.where(mask > 0, diff, alpha_cur)}


def saga_replay(shards: Sequence, masks: Sequence, order: Sequence[int],
                gamma: float, batch_rate: float, n: int, group: int = 1,
                w0: Optional[np.ndarray] = None,
                block_rows: int = BLOCK_ROWS) -> Dict[str, object]:
    """Sequential SAGA with the exact table delta.

    ``order[i]`` is the shard (worker) of the ``i``-th accepted result and
    ``masks[i]`` its Bernoulli mask over that shard's rows.  ``group``
    consecutive results make one update: every task of a group reads the
    model and its slice as they are when the group starts, the commits
    follow in order (each delta against the slice as it is THEN), and the
    model takes one step with the summed ``g`` over ``par_recs = batch_rate
    * n * group / len(shards)``.  ``group=1`` is the asynchronous engine
    with no task in flight across an update; ``group=len(shards)`` with
    every shard once a group is the synchronous drain.  Returns ``w``,
    ``alpha_bar`` (float32 arrays on the host) and ``alpha``, a list of the
    slices."""
    if len(order) % group or len(masks) != len(order):
        raise ValueError("order and masks must hold whole groups")
    home = shards[0].X.device
    d = int(shards[0].X.shape[1])
    w = _f32(np.zeros(d) if w0 is None else w0, home)
    alpha_bar = _f32(np.zeros(d), home)
    alpha: List[jax.Array] = [
        jax.device_put(jnp.zeros(int(s.X.shape[0]), jnp.float32), s.X.device)
        for s in shards
    ]
    par_recs = batch_rate * n * group / len(shards)
    for at in range(0, len(order), group):
        read = list(alpha)  # the slices as the group's tasks were handed them
        g_sum = jnp.zeros(d, jnp.float32)
        delta_sum = jnp.zeros(d, jnp.float32)
        for k, mask in zip(order[at:at + group], masks[at:at + group]):
            out = task(shards[k], w, read[k], alpha[k], mask, block_rows)
            alpha[k] = out["alpha"]
            g_sum = g_sum + jax.device_put(out["g"], home)
            delta_sum = delta_sum + jax.device_put(out["delta"], home)
        w = w - (gamma / par_recs) * g_sum - gamma * alpha_bar
        alpha_bar = alpha_bar + delta_sum / n
    return {"w": np.asarray(w), "alpha_bar": np.asarray(alpha_bar),
            "alpha": [np.asarray(a) for a in alpha]}
