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


# ------------------------------------------------------------------ dense
@functools.partial(jax.jit, static_argnames=("block", "loss"))
def _dense_block(X, y, w, weights, start, block, loss):
    s, live = _block(X, start, block)
    Xb = jax.lax.dynamic_slice_in_dim(X, s, block).astype(jnp.float32)
    yb = jax.lax.dynamic_slice_in_dim(y, s, block)
    mb = jax.lax.dynamic_slice_in_dim(weights, s, block) * live
    m = _dot(Xb, w)
    if loss == "least_squares":
        r = m - yb
        per_row = r * r
    else:
        r = jax.nn.sigmoid(m) - yb
        per_row = jnp.logaddexp(0.0, m) - yb * m
    return (
        jnp.sum(per_row * live),
        _dot((mb * r)[None, :], Xb)[0],
        jnp.sum(jnp.sum(Xb * Xb, axis=1) * live),
        jnp.sum(yb * yb * live),
    )


# -------------------------------------------------------------- padded ELL
@functools.partial(jax.jit, static_argnames=("block", "d"))
def _ell_block(cols, vals, y, w, weights, start, block, d):
    s, live = _block(vals, start, block)
    cb = jax.lax.dynamic_slice_in_dim(cols, s, block)
    vb = jax.lax.dynamic_slice_in_dim(vals, s, block).astype(jnp.float32)
    yb = jax.lax.dynamic_slice_in_dim(y, s, block)
    mb = jax.lax.dynamic_slice_in_dim(weights, s, block) * live
    r = jnp.sum(vb * w[cb], axis=1) - yb
    g = jnp.zeros(d, jnp.float32).at[cb.ravel()].add(
        (vb * (mb * r)[:, None]).ravel()
    )
    return (
        jnp.sum(r * r * live),
        g,
        jnp.sum(jnp.sum(vb * vb, axis=1) * live),
        jnp.sum(yb * yb * live),
        jnp.sum(jnp.sum(vb != 0, axis=1) * live),
    )


def shard_sums(shard, w, d: int, loss: str = "least_squares",
               weights=None, block_rows: int = BLOCK_ROWS) -> Dict[str, object]:
    """One shard's sums, on the shard's device: ``loss`` (unnormalised),
    ``grad`` (sum over rows of ``weights_i * dloss_i/dw``; all rows where
    ``weights`` is None), ``xx`` (sum of squared entries), ``yy`` (sum of
    squared labels), ``rows`` and, for padded ELL, ``nnz``."""
    sparse = hasattr(shard, "cols")
    lead = shard.vals if sparse else shard.X
    rows = int(lead.shape[0])
    dev = lead.device
    w = jax.device_put(jnp.asarray(w, jnp.float32), dev)
    if weights is None:
        weights = jnp.ones(rows, jnp.float32)
    weights = jax.device_put(jnp.asarray(weights, jnp.float32), dev)
    block = min(block_rows, rows)
    acc = None
    for start in range(0, rows, block):
        if sparse:
            if loss != "least_squares":
                raise ValueError("padded-ELL reference: least_squares only")
            part = _ell_block(shard.cols, shard.vals, shard.y, w, weights,
                              start, block=block, d=d)
        else:
            part = _dense_block(shard.X, shard.y, w, weights, start,
                                block=block, loss=loss)
        acc = part if acc is None else tuple(a + b for a, b in zip(acc, part))
    out = {
        "loss": float(acc[0]),
        "grad": np.asarray(acc[1], np.float64),
        "xx": float(acc[2]),
        "yy": float(acc[3]),
        "rows": rows,
    }
    if sparse:
        out["nnz"] = float(acc[4])
    return out


def dataset_sums(shards: Iterable, w, d: int,
                 loss: str = "least_squares") -> Dict[str, float]:
    """Totals over every shard (float64 on the host): what the objective
    and the data pins are computed from."""
    tot: Dict[str, float] = {"loss": 0.0, "xx": 0.0, "yy": 0.0, "rows": 0,
                             "nnz": 0.0}
    for shard in shards:
        s = shard_sums(shard, w, d, loss)
        for key in tot:
            tot[key] += s.get(key, 0.0)
    return tot


def objective(shards: Iterable, w, d: int,
              loss: str = "least_squares") -> float:
    """Mean loss over the whole dataset at ``w``."""
    tot = dataset_sums(shards, w, d, loss)
    return tot["loss"] / tot["rows"]


def full_gradient(shard, w, d: int, loss: str = "least_squares",
                  weights=None) -> np.ndarray:
    """Unnormalised gradient sum over one shard's (weighted) rows."""
    return shard_sums(shard, w, d, loss, weights)["grad"]


def data_pins(shards: Iterable, d: int,
              loss: str = "least_squares") -> Tuple[Dict[str, float], float]:
    """What the generator is held to, from the device arrays, in a form that
    holds for any seed: the rows' second moment ``d * mean(x^2)`` (1 for
    both planted generators), the labels' second moment, the stored
    non-zeros a row (padded ELL), and the objective at ``w = 0``."""
    tot = dataset_sums(shards, np.zeros(d, np.float32), d, loss)
    n = tot["rows"]
    pins = {
        "row_second_moment": tot["xx"] / n,
        "label_second_moment": tot["yy"] / n,
    }
    if tot["nnz"]:
        pins["nnz_per_row"] = tot["nnz"] / n
    return pins, tot["loss"] / n
