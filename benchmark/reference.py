"""Plain reference for the GLM cells: objective, gradients, data moments.

Straightforward ``jax.numpy`` in float32 at precision "highest", with no
code of the program: the same arithmetic on the same device arrays gives
the answers the program's steps and trajectory are held to.  A shard is
walked in row blocks so that a bf16 shard is never held whole in f32
(a 1,012,500 x 784 mnist8m shard would be a 3.2 GB temporary beside
12.7 GB of resident data).

Conventions, as the program's drivers print them: the objective is the
mean over the whole dataset of ``(x_i . w - y_i)^2`` for least squares (no
factor 1/2) and of ``log(1 + e^m) - y m`` for the logistic loss; a
gradient is the unnormalised sum over the selected rows.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 65536
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=_HI)


def _block(a, start, block):
    """Rows ``[start, start+block)`` of ``a``, clamped like dynamic_slice
    clamps, with the mask of rows that really belong to the block."""
    rows = a.shape[0]
    s = jnp.clip(start, 0, rows - block)
    live = (s + jnp.arange(block)) >= start
    return s, live


def _loss_terms(m, yb, loss):
    """A row's loss and its derivative by the margin ``m = x . w``."""
    if loss == "least_squares":
        r = m - yb
        return r * r, r
    if loss == "logistic":
        return jnp.logaddexp(0.0, m) - yb * m, jax.nn.sigmoid(m) - yb
    raise ValueError(f"unknown loss {loss!r}")


# Each storage has two block functions.  ``*_sums`` is what the
# whole-dataset passes after every run need (the objective, the data's
# moments) and builds no gradient: for padded ELL no scatter and no
# ``(d,)`` array, for dense no second product.  With ``w`` None it is the
# pass at ``w = 0`` and every margin is the exact zero the product would
# give: for padded ELL no ``w[cols]`` gather (7.3 ns a stored slot on the
# v5e, all of that pass's time), for dense no product at all.  ``*_grad``
# is the gradient alone, for the callers that hold a program's gradient to
# it.


# ------------------------------------------------------------------ dense
def _dense_rows(X, y, start, block):
    s, live = _block(X, start, block)
    Xb = jax.lax.dynamic_slice_in_dim(X, s, block).astype(jnp.float32)
    return s, live, Xb, jax.lax.dynamic_slice_in_dim(y, s, block)


@functools.partial(jax.jit, static_argnames=("block", "loss"))
def _dense_sums(X, y, w, start, block, loss):
    _s, live, Xb, yb = _dense_rows(X, y, start, block)
    m = jnp.zeros_like(yb) if w is None else _dot(Xb, w)
    per_row, _r = _loss_terms(m, yb, loss)
    return (
        jnp.sum(per_row * live),
        jnp.sum(jnp.sum(Xb * Xb, axis=1) * live),
        jnp.sum(yb * yb * live),
    )


@functools.partial(jax.jit, static_argnames=("block", "loss"))
def _dense_grad(X, y, w, weights, start, block, loss):
    s, live, Xb, yb = _dense_rows(X, y, start, block)
    mb = jax.lax.dynamic_slice_in_dim(weights, s, block) * live
    _per_row, r = _loss_terms(_dot(Xb, w), yb, loss)
    return _dot((mb * r)[None, :], Xb)[0]


# -------------------------------------------------------------- padded ELL
def _ell_rows(cols, vals, y, start, block):
    s, live = _block(vals, start, block)
    cb = jax.lax.dynamic_slice_in_dim(cols, s, block)
    vb = jax.lax.dynamic_slice_in_dim(vals, s, block).astype(jnp.float32)
    return s, live, cb, vb, jax.lax.dynamic_slice_in_dim(y, s, block)


@functools.partial(jax.jit, static_argnames=("block", "loss"))
def _ell_sums(cols, vals, y, w, start, block, loss):
    _s, live, cb, vb, yb = _ell_rows(cols, vals, y, start, block)
    m = jnp.zeros_like(yb) if w is None else jnp.sum(vb * w[cb], axis=1)
    per_row, _r = _loss_terms(m, yb, loss)
    return (
        jnp.sum(per_row * live),
        jnp.sum(jnp.sum(vb * vb, axis=1) * live),
        jnp.sum(yb * yb * live),
        jnp.sum(jnp.sum(vb != 0, axis=1) * live),
    )


@functools.partial(jax.jit, static_argnames=("block", "d", "loss"))
def _ell_grad(cols, vals, y, w, weights, start, block, d, loss):
    s, live, cb, vb, yb = _ell_rows(cols, vals, y, start, block)
    mb = jax.lax.dynamic_slice_in_dim(weights, s, block) * live
    _per_row, r = _loss_terms(jnp.sum(vb * w[cb], axis=1), yb, loss)
    return jnp.zeros(d, jnp.float32).at[cb.ravel()].add(
        (vb * (mb * r)[:, None]).ravel()
    )


def _f32(a, device):
    return jax.device_put(jnp.asarray(a, jnp.float32), device)


def _row_blocks(rows: int, block_rows: int):
    block = min(block_rows, rows)
    return block, range(0, rows, block)


def shard_sums(shard, w, loss: str = "least_squares",
               block_rows: int = BLOCK_ROWS) -> Dict[str, float]:
    """One shard's sums, on the shard's device: ``loss`` (unnormalised),
    ``xx`` (sum of squared entries), ``yy`` (sum of squared labels),
    ``rows`` and, for padded ELL, ``nnz``.  No gradient is built; ``w``
    None stands for ``w = 0`` and reads no model either."""
    sparse = hasattr(shard, "cols")
    rows = int(shard.y.shape[0])
    if w is not None:
        w = _f32(w, shard.y.device)
    block, starts = _row_blocks(rows, block_rows)
    acc = None
    for start in starts:
        if sparse:
            part = _ell_sums(shard.cols, shard.vals, shard.y, w, start,
                             block=block, loss=loss)
        else:
            part = _dense_sums(shard.X, shard.y, w, start, block=block,
                               loss=loss)
        acc = part if acc is None else tuple(a + b for a, b in zip(acc, part))
    out = dict(zip(("loss", "xx", "yy", "nnz"), map(float, acc)))
    out["rows"] = rows
    return out


def full_gradient(shard, w, d: int, loss: str = "least_squares",
                  weights=None, block_rows: int = BLOCK_ROWS) -> np.ndarray:
    """Unnormalised gradient sum over one shard's rows, each weighed by
    ``weights`` (every row once where None), float64 on the host."""
    sparse = hasattr(shard, "cols")
    rows = int(shard.y.shape[0])
    dev = shard.y.device
    w = _f32(w, dev)
    weights = _f32(np.ones(rows) if weights is None else weights, dev)
    block, starts = _row_blocks(rows, block_rows)
    acc = None
    for start in starts:
        if sparse:
            part = _ell_grad(shard.cols, shard.vals, shard.y, w, weights,
                             start, block=block, d=d, loss=loss)
        else:
            part = _dense_grad(shard.X, shard.y, w, weights, start,
                               block=block, loss=loss)
        acc = part if acc is None else acc + part
    return np.asarray(acc, np.float64)


def dataset_sums(shards: Iterable, w,
                 loss: str = "least_squares") -> Dict[str, float]:
    """Totals over every shard (float64 on the host): what the objective
    and the data pins are computed from."""
    tot: Dict[str, float] = {"loss": 0.0, "xx": 0.0, "yy": 0.0, "rows": 0,
                             "nnz": 0.0}
    for shard in shards:
        s = shard_sums(shard, w, loss)
        for key in tot:
            tot[key] += s.get(key, 0.0)
    return tot


def objective(shards: Iterable, w, loss: str = "least_squares") -> float:
    """Mean loss over the whole dataset at ``w``."""
    tot = dataset_sums(shards, w, loss)
    return tot["loss"] / tot["rows"]


def data_pins(shards: Iterable,
              loss: str = "least_squares") -> Tuple[Dict[str, float], float]:
    """What the generator is held to, from the device arrays, in a form that
    holds for any seed: the rows' second moment ``d * mean(x^2)`` (1 for
    both planted generators), the labels' second moment, the stored
    non-zeros a row (padded ELL), and the objective at ``w = 0``, for
    which no model is gathered or multiplied."""
    tot = dataset_sums(shards, None, loss)
    n = tot["rows"]
    pins = {
        "row_second_moment": tot["xx"] / n,
        "label_second_moment": tot["yy"] / n,
    }
    if tot["nnz"]:
        pins["nnz_per_row"] = tot["nnz"] / n
    return pins, tot["loss"] / n
