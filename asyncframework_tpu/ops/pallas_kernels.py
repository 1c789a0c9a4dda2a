"""Pallas TPU kernels for the gradient hot path.

The reference's compute hot loop bottoms out in native BLAS through JNI
(``LeastSquaresGradient.compute`` -> ``BLAS.axpy/dot`` ->
``mllib-local/.../BLAS.scala:20-35`` netlib).  The TPU equivalent is mostly
*just XLA* -- the fused sample+gradient jit already runs on the MXU.  This
module is the layer below that for cases XLA's fusion does not cover:

- :func:`fused_masked_grad` -- one-pass tiled kernel for
  ``g = X^T (mask * (X w - y))``: streams X through VMEM row-tiles, keeps
  the residual entirely on-chip (never materialized in HBM), accumulates
  ``g`` in a VMEM-resident f32 block across grid steps.  This is the ASGD
  worker step's core contraction with the HBM round-trip for the
  n-vector residual removed -- exactly the kind of fusion worth hand-
  scheduling when ``n`` is millions of rows (mnist8m).
- :func:`chunk_attention` -- block attention with local softmax stats for
  the long-context path: a flash-style forward tiled over (query block,
  key block) with the running (m, l, o) in VMEM scratch, returning the
  (o, m, l) triple so ``parallel/ring.py`` can merge ring steps with the
  cheap rescale (``ring_attention(..., block_kernel="pallas")``).
- For rcv1-style sparse data the SURVEY-prescribed alternative (densify
  per batch, then this kernel) lives in the data layer; a scatter/gather
  CSR kernel is deliberately NOT attempted -- vector gather does not map
  onto the VPU's strided units, padding to blocked-ELL densifies anyway.

``interpret`` is an explicit argument everywhere: the CPU tests pass
``interpret=True``, every other caller gets the Mosaic-compiled kernel
(``chip_smoke.py`` phase E compiles both at full shapes on the chip and
checks them against the references below).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from asyncframework_tpu.ops.gradients import mm_f32


def _grad_kernel(x_ref, y_ref, m_ref, w_ref, g_ref):
    """One row-tile step: r = mask*(X_t w - y_t); g += X_t^T r.

    Every vector rides as a lane-dense ROW -- ``w``/``g`` (1, d),
    ``y``/``mask``/``r`` (1, T) -- so nothing is padded from one column
    to 128 lanes, and both contractions are plain row-by-matrix products
    with no transpose of the X tile.  Operands follow
    ``ops.gradients.mm_f32``: the shard's storage dtype, f32 accumulation.
    """
    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[:] = jnp.zeros_like(g_ref)

    x = x_ref[:]                                     # (T, d)
    r = jax.lax.dot_general(
        w_ref[:].astype(x.dtype), x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (1, T)
    r = (r - y_ref[:]) * m_ref[:]
    g_ref[:] += jnp.dot(
        r.astype(x.dtype), x, preferred_element_type=jnp.float32
    )                                                # (1, d)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def _fused_masked_grad_tiles(X, y2, m2, w2, row_tile: int, interpret: bool):
    """The kernel over the first ``(n // row_tile) * row_tile`` rows of
    ``X``: the grid stops short of the ragged tail, so ``X`` is read in
    place -- never padded, never cast."""
    n, d = X.shape
    return pl.pallas_call(
        _grad_kernel,
        grid=(n // row_tile,),
        in_specs=[
            pl.BlockSpec((row_tile, d), lambda i: (i, 0)),
            pl.BlockSpec((1, row_tile), lambda i: (0, i)),
            pl.BlockSpec((1, row_tile), lambda i: (0, i)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(X, y2, m2, w2)


def fused_masked_grad(
    X,
    y,
    w,
    mask: Optional[jax.Array] = None,
    row_tile: int = 256,
    interpret: bool = False,
):
    """``g = X^T (mask * (X w - y))`` in one pass over ``X``.

    ``X``: (n, d) f32 or bf16, read in its storage dtype; ``y``/``mask``:
    (n,); ``w``: (d,).  Any shape is accepted without copying ``X``: the
    feature dim rides as one full-width block (Mosaic pads it to lanes in
    VMEM), the kernel covers the row tiles that fit, and the ragged tail
    (fewer than ``row_tile`` rows) goes through the same contraction in
    plain XLA.  ``row_tile`` is rounded down to a multiple of the
    128-lane tile (the row tile is the lane dim of the y/mask blocks).
    """
    X = jnp.asarray(X)
    n, d = X.shape
    y = jnp.asarray(y, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    m = (
        jnp.ones(n, jnp.float32)
        if mask is None
        else jnp.asarray(mask, jnp.float32)
    )
    row_tile = 128 * max(row_tile // 128, 1)
    n_main = (n // row_tile) * row_tile
    g = jnp.zeros(d, jnp.float32)
    if n_main:
        g = _fused_masked_grad_tiles(
            X, y[None, :], m[None, :], w[None, :], row_tile, interpret
        )[0]
    if n_main < n:
        Xt = X[n_main:]
        r = (mm_f32(Xt, w) - y[n_main:]) * m[n_main:]
        g = g + mm_f32(Xt.T, r)
    return g


def reference_masked_grad(X, y, w, mask=None):
    """Plain-XLA oracle for the fused kernel."""
    X = jnp.asarray(X, jnp.float32)
    r = X @ jnp.asarray(w, jnp.float32) - jnp.asarray(y, jnp.float32)
    if mask is not None:
        r = r * jnp.asarray(mask, jnp.float32)
    return X.T @ r


# --------------------------------------------------------------- attention
_NEG_BIG = -1e30  # finite mask fill, same value as parallel/ring.py's _NEG

#: query/key block edge: a (512, 512) f32 score tile is 1 MiB, so the
#: whole working set (q/k/v/mask blocks double-buffered + scratch) stays
#: near 4 MiB whatever Tq and Tk are -- the whole-(Tq, Tk) kernel this
#: replaces was refused by Mosaic from Tk = 2,048 (16.6 MB of VMEM).
_ATTN_BLOCK = 512


def _chunk_attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                       m_sc, l_sc, acc_sc, *, scale: float):
    """One (batch*head, query block, key block) program of a flash-style
    forward: fold this key block into the running (m, l, acc) scratch and
    emit the un-normalized triple after the last key block, so the caller
    can merge whole chunks with the standard rescale.
    """
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_BIG)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                        # (bq, bk)
    s = jnp.where(mask_ref[:] > 0, s, _NEG_BIG)
    m_prev = m_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[:] = corr * l_sc[:] + jnp.sum(p, axis=-1, keepdims=True)
    acc_sc[:] = corr * acc_sc[:] + jnp.dot(
        p, v_ref[0], preferred_element_type=jnp.float32
    )
    m_sc[:] = m_new

    @pl.when(kv == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc_sc[:]
        m_ref[0] = m_sc[:]
        l_ref[0] = l_sc[:]


@functools.partial(
    jax.jit, static_argnames=("scale", "bq", "bk", "interpret", "vma")
)
def _chunk_attn_padded(q, k, v, mask, scale: float, bq: int, bk: int,
                       interpret: bool, vma):
    bh, tq, dp = q.shape
    tk = k.shape[1]
    kw = {} if vma is None else {"vma": frozenset(vma)}
    return pl.pallas_call(
        functools.partial(_chunk_attn_kernel, scale=scale),
        grid=(bh, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((bq, bk), lambda b, i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dp), jnp.float32, **kw),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32, **kw),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32, **kw),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, mask)


def _block_and_pad(t: int) -> "tuple[int, int]":
    """(block edge, padding) for a sequence of length ``t``: one
    sublane-rounded block while it fits, else ``_ATTN_BLOCK``-sized
    blocks (a multiple of the 128-lane tile, as the mask's minor
    dimension needs once it is no longer the full array)."""
    blk = min(_ATTN_BLOCK, 8 * ((t + 7) // 8))
    return blk, (-t) % blk


def chunk_attention(q, k, v, mask=None, interpret: bool = False, vma=None):
    """Block attention with softmax stats: ``(o, m, l)`` per query row.

    ``q``: (B, Tq, H, D); ``k``/``v``: (B, Tk, H, D); ``mask``: (Tq, Tk)
    bool/0-1 (True = attend) or None.  Returns ``o`` (B, Tq, H, D) f32
    un-normalized, ``m``/``l`` (B, H, Tq) f32 -- exactly the running-state
    triple :func:`asyncframework_tpu.parallel.ring._block_accumulate`
    folds, so a ring step can offload its block compute to this kernel
    and keep the (cheap) rescale-merge in XLA.

    Any Tq/Tk: both are tiled in ``_ATTN_BLOCK`` blocks inside the kernel,
    so VMEM use does not grow with the sequence.  Padding: Tq/Tk to a
    whole number of blocks, D to the 128-lane tile.  Padded K columns are
    masked out; padded D columns are zero so they contribute nothing;
    padded Q rows are sliced off.

    ``vma``: when called inside ``shard_map`` with vma checking, the mesh
    axes the outputs vary over (e.g. ``("sp",)``) -- pallas outputs must
    declare their varying-axes explicitly.
    """
    B, tq, H, D = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    bq, pad_q = _block_and_pad(tq)
    bk, pad_k = _block_and_pad(tk)
    pad_d = (-D) % 128

    if mask is None:
        mask_f = jnp.ones((tq, tk), jnp.float32)
    else:
        mask_f = jnp.asarray(mask, jnp.float32)
    mask_f = jnp.pad(mask_f, ((0, pad_q), (0, pad_k)))  # padded K masked

    def to_bhd(x, pad_t):
        x = jnp.asarray(x, jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, 0), (0, pad_d)))
        # (B, T, H, D) -> (B*H, T, Dp)
        return x.transpose(0, 2, 1, 3).reshape(
            B * H, x.shape[1], D + pad_d
        )

    o, m, l = _chunk_attn_padded(
        to_bhd(q, pad_q), to_bhd(k, pad_k), to_bhd(v, pad_k),
        mask_f, scale, bq, bk, interpret, tuple(vma) if vma else None,
    )
    o = o.reshape(B, H, tq + pad_q, D + pad_d)[:, :, :tq, :D]
    o = o.transpose(0, 2, 1, 3)                      # (B, Tq, H, D)
    m = m.reshape(B, H, tq + pad_q)[:, :, :tq]
    l = l.reshape(B, H, tq + pad_q)[:, :, :tq]
    return o, m, l
