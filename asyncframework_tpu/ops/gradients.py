"""Loss gradients as jitted XLA computations (the worker hot path).

Parity targets (semantics only; the implementation is batched XLA, not
per-sample JNI BLAS):

- Least-squares ``gradfun`` of the ASYNC drivers
  (``ASYNCsamples/.../SparkASGDThread.scala:420-435``):
  per sample, ``grad = (x . w - y) * x``; a partition's task result is the
  *sum* of sampled per-sample gradients (the drivers' ``comOp`` is vector add).
- MLlib ``LeastSquaresGradient`` / ``LogisticGradient``
  (``mllib/.../optimization/Gradient.scala:285,166``).
- ASAGA per-sample scalar form (``SparkASAGAThread.scala:500-515``): for least
  squares the gradient is ``scalar * x`` with ``scalar = x . w - y``, so the
  history table stores one scalar per sample.

TPU mapping: a whole shard's sampled mini-batch gradient is ``r = X @ w - y``
then ``g = X^T @ (mask * r)``.  Sampling is a Bernoulli *mask* (static
shapes; no dynamic gather), so a "sampled subset" costs one elementwise
multiply instead of a shape-changing filter -- and on the TPU a filter by
the ROW would be no saving at all: a dense shard whose ``d`` is no multiple
of 128 is stored column-major there (rows minor, PERF.md section 3), so
picking rows means relaying all of it; what can be left out is a whole
lane tile of 128 rows none of which was drawn (below).

The byte model: ONE read of the shard a step where :func:`dense_step_path`
says ``"onepass"`` (a TPU, a column-major shard): the Pallas kernel
``pallas_kernels.dense_onepass`` takes ``X.T`` (a free ``bitcast`` there)
block by block and computes both products from the block in VMEM, at
740-748 GB/s of the v5e's 819.  TWO reads elsewhere (``"two_products"``):
each product is one XLA multiply-reduce fusion over the shard (755 GB/s on
the v5e; the MXU would round f32 operands to bf16), and the second cannot
start before ``r`` is complete.  That path is the CPU's, the one for
lane-aligned widths (``d % 128 == 0``: the shard is stored row-major and
``X.T`` would be a real transpose), and the oracle the kernel is tested
against.  Callers that need a bare ``X w`` (the losses, the trajectory
evaluation) and ASAGA's table delta (``steps.make_saga_table_delta``: one
product, one read) stay XLA's.  LESS than one read where the draw is thin
(``"onepass_tiles"``, PR 49): the device's granule is a lane tile of 128
rows, a tile with no sampled row adds exact zeros, and at ``b`` = 0.01
more than a quarter of the tiles hold none:
``pallas_kernels.dense_onepass_tiles`` fetches the others, tile by tile,
and runs the same arithmetic over them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def mm_f32(A: jax.Array, v: jax.Array) -> jax.Array:
    """Matmul in ``A``'s storage dtype with f32 accumulation.

    The bf16 data path: shards stored bfloat16 hit the MXU at native rate
    while partial sums accumulate in float32 (``preferred_element_type``) --
    the standard mixed-precision recipe.  For f32 ``A`` this is the plain
    matmul at the backend's DEFAULT precision (no ``precision=`` is set),
    so every gradient below is dtype-polymorphic over the shard's storage
    dtype; ``w``/``y``/gradients stay f32 throughout.  On the v5e that
    default is exact for the matrix-VECTOR products this module makes
    (chip_smoke.py phase D: 0.0 from precision "highest" on a 50,000 x
    2,000 shard) but rounds operands to bf16 for matrix-matrix products
    (e.g. the (n, S) trajectory evaluation).
    Casting ``v`` down to ``A.dtype`` (rather than promoting ``A`` up) is
    what keeps an (n, d) bf16 shard from being materialized in f32.  One
    product promotes ``A`` on purpose and does not come through here:
    ASAGA's ``X^T (mask * (diff - alpha))`` over a dense shard
    (``steps.make_saga_table_delta`` and the ``g`` of every dense ASAGA
    step), whose f32 vector is what keeps ``alpha_bar`` the mean of the
    history table (the compiler fuses that promotion into the read, so it
    copies nothing either).
    """
    return jnp.matmul(A, v.astype(A.dtype), preferred_element_type=jnp.float32)


#: rows a block of :func:`shard_matvec` is a multiple of: 16 lane tiles
_ROW_BLOCK = 16 * 128


def shard_matvec(X: jax.Array, w: jax.Array) -> jax.Array:
    """``X w`` over a whole ``(n, d)`` shard -> ``(n,)`` f32, the ragged
    tail (``n`` mod 2,048 rows) as a product of its own.

    Row by row the same sums as ``mm_f32(X, w)``.  The split is for the TPU
    compiler: it tiles the reduce fusion's output rows into windows of
    128-row lane tiles, and where the shard's tile count has no small
    divisor (253,125 rows are 1,978 = 2 x 23 x 43 tiles) it falls back to
    one sublane group a window and runs at half the bandwidth (1.05 ms
    against 0.53 ms for ``X^T v`` over the same bytes, v5e, PERF.md section
    6, PR 24).  A main block of a multiple of 16 tiles has divisors at any
    size; both slices start on a tile edge of the stored shard (rows are
    minor there), so they are fused into the reads and nothing is copied.
    Elsewhere the split costs 0.0-0.2% of the step.
    """
    n = X.shape[0]
    k = n - n % _ROW_BLOCK
    if k in (0, n):
        return mm_f32(X, w)
    return jnp.concatenate([mm_f32(X[:k], w), mm_f32(X[k:], w)])


def _on_tpu() -> bool:
    """Whether this process computes on a TPU.  A function of its own so
    that a test which compiles for a DESCRIBED chip on the CPU
    (``tests/test_step_layout.py``) can steer the choice from the test."""
    return jax.default_backend() == "tpu"


def _kernels():
    """``ops/pallas_kernels.py``, imported where a TPU first asks for it:
    a process without one never pays for ``jax.experimental.pallas`` (0.9 s
    here), and one with one has it loaded already
    (``utils/devices._preload_kernels``)."""
    from asyncframework_tpu.ops import pallas_kernels

    return pallas_kernels


#: rows of the shard one lane tile of ``X.T`` holds: the granule of the
#: device's storage, and of the tile-list kernel's fetch
_TILE_ROWS = 128
#: expected share of a shard's lane tiles that hold a sampled row under
#: which the tile-list kernel (``pallas_kernels.dense_onepass_tiles``) is
#: chosen over the whole-shard one.  On the v5e (1.0M x 784 bf16, ASAGA's
#: step both ways over a sweep of rates; PERF.md section 6, PR 49) the two
#: break even between a share of 0.961 (``b`` 0.025: 2.309 ms against
#: 2.339) and 0.980 (0.03: 2.351): a listed tile costs 4% more than a
#: streamed one (its DMA's descriptor, a wait of its own) and the list
#: 0.09 ms a step.  Set where the gain is 2% or more (0.946: 2.275 ms);
#: at ASAGA's 0.724 it is 24%
DENSE_TILES_BREAK_EVEN = 0.95


def dense_tiles_share(batch_rate) -> float:
    """Expected share of a shard's 128-row lane tiles that hold a sampled
    row under a Bernoulli draw at ``batch_rate``: ``1 - (1 - b)^128``
    (0.724 at ASAGA's 0.01, 1.0 to six places at ASGD's 0.1); 1.0 where no
    rate is known."""
    if batch_rate is None:
        return 1.0
    return 1.0 - (1.0 - batch_rate) ** _TILE_ROWS


def dense_step_path(X, batch_rate=None) -> str:
    """``"onepass"``, ``"onepass_tiles"`` or ``"two_products"``: which
    program :func:`dense_masked_grad` traces for the shard ``X`` (an
    array, a tracer or a ``ShapeDtypeStruct``), from what can be observed
    when the step is built -- the backend, the rank, the width, the dtype
    and the rate of the draw.

    The one-pass kernels are right only where ``X.T`` is a ``bitcast``: on
    the TPU, whose compiler stores an ``(n, d)`` array with ``d % 128 !=
    0`` column-major (784, 2000, 64: ``{0,1}``) and one with ``d % 128 ==
    0`` row-major (``tests/test_step_layout.py`` reads both off compiled
    programs).  What the kernels take (f32 or bf16, ``d`` a whole number
    of sublane tiles, a block that fits VMEM) is
    ``pallas_kernels.onepass_takes``.  On the v5e one read won at every
    shape timed (PERF.md section 6, PR 26: 1.0M and 253k x 784 bf16, 1.0M
    x 784 and 50k x 2,000 f32).  WHICH one-pass kernel is the draw's
    doing: one algorithm whose fetch wants another granule at another
    rate.  Where the draw leaves enough lane tiles without a sampled row
    (:func:`dense_tiles_share` under :data:`DENSE_TILES_BREAK_EVEN`:
    ASAGA's ``b`` 0.01, and an ASGD recipe at that rate alike) the kernel
    over the list of the tiles that hold one; else, and where the caller
    knows no rate, the kernel over the whole shard.
    """
    if (_on_tpu() and len(X.shape) == 2 and X.shape[1] % 128 != 0
            and _kernels().onepass_takes(X.shape[1], X.dtype)):
        if dense_tiles_share(batch_rate) < DENSE_TILES_BREAK_EVEN:
            return "onepass_tiles"
        return "onepass"
    return "two_products"


def dense_masked_grad(X, y, w, mask, alpha=None, logistic: bool = False,
                      batch_rate=None):
    """``(g, diff)`` of a dense worker step over a whole shard: ``diff =
    link(X w) - y`` (``link`` the identity, or the sigmoid with
    ``logistic``) and ``g = X^T (mask * (diff [- alpha]))``.

    THE definition behind both dense losses' gradient sums and the ASAGA
    step, and the ONE place the program is chosen (:func:`dense_step_path`):
    one read of the shard, or of its lane tiles that hold a sampled row,
    through a Pallas kernel, or the two XLA products.  ``batch_rate``: the
    rate ``mask`` was drawn at, where the caller holds one (a Python
    number: it picks the program, it is no operand).  With ``alpha``
    (ASAGA: ``diff`` are the candidate history scalars) ``g`` keeps its
    f32 vector and promotes the shard on every path, as
    ``steps.make_saga_table_delta`` does and for its reason; without it
    the two-product path is ``mm_f32``'s, as ever.  ``diff`` at a row
    ``mask`` does not mark is the row's own value on two of the paths and
    0 or that on the third (a tile with no sampled row is never read):
    callers select or weigh it by ``mask``.
    """
    path = dense_step_path(X, batch_rate)
    if path != "two_products":
        kernel = (_kernels().dense_onepass_tiles if path == "onepass_tiles"
                  else _kernels().dense_onepass)
        with jax.named_scope("grad"):
            return kernel(X, y, w, mask, alpha, logistic=logistic)
    with jax.named_scope("residual"):
        margin = shard_matvec(X, w)
        diff = (jax.nn.sigmoid(margin) if logistic else margin) - y
    with jax.named_scope("grad"):
        if alpha is None:
            return mm_f32(X.T, mask * diff), diff
        return X.T @ (mask * (diff - alpha)), diff


@functools.partial(jax.jit, static_argnames=("batch_rate",))
def least_squares_grad_sum(
    X: jax.Array, y: jax.Array, w: jax.Array, mask: jax.Array,
    batch_rate=None,
) -> jax.Array:
    """Sum over masked samples of ``(x_i . w - y_i) x_i``.

    ``mask`` is {0,1} (or weights) of shape ``(n,)``; equivalent to the
    reference's sample-then-map-then-reduce with vector-add comOp.
    ``batch_rate``: the rate it was drawn at, where the caller holds one
    (:func:`dense_masked_grad`).
    """
    return dense_masked_grad(X, y, w, mask, batch_rate=batch_rate)[0]


@jax.jit
def least_squares_loss(X: jax.Array, y: jax.Array, w: jax.Array) -> jax.Array:
    """Mean squared error over the shard: sum_i (x_i.w - y_i)^2 (unnormalized).

    The drivers print ``sum_i (x_i.w - y_i)^2 / N`` per trajectory snapshot
    (``SparkASGDThread.scala:386-401``); normalization by N happens at the
    caller, which knows the global N.
    """
    r = mm_f32(X, w) - y
    return jnp.sum(r * r)


@functools.partial(jax.jit, static_argnames=("batch_rate",))
def logistic_grad_sum(
    X: jax.Array, y: jax.Array, w: jax.Array, mask: jax.Array,
    batch_rate=None,
) -> jax.Array:
    """Sum over masked samples of the logistic-loss gradient.

    Parity: ``LogisticGradient`` (binary case) -- labels in {0,1};
    ``grad_i = (sigmoid(x_i.w) - y_i) x_i``.
    """
    return dense_masked_grad(
        X, y, w, mask, logistic=True, batch_rate=batch_rate)[0]


@jax.jit
def logistic_loss(X: jax.Array, y: jax.Array, w: jax.Array) -> jax.Array:
    """Unnormalized logistic loss, numerically stable log1p(exp(.)) form."""
    margin = mm_f32(X, w)
    # log(1+e^m) - y*m, stable for both signs of margin
    return jnp.sum(jnp.logaddexp(0.0, margin) - y * margin)


@jax.jit
def saga_shard_step(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    alpha: jax.Array,
    mask: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One ASAGA worker computation over a shard.

    Returns ``(g, diff)`` where ``diff_i = x_i.w - y_i`` are the *candidate*
    new history scalars and
    ``g = sum_i mask_i * (diff_i - alpha_i) * x_i``
    is the history-corrected gradient contribution (parity with the worker map
    in ``SparkASAGAThread.scala:369-380``: ``gradfun`` minus
    ``scalar_hist * x`` summed by ``ASYNCaggregate``'s vector-add).

    The history ``alpha`` slice stays in device HBM; committing
    ``alpha[i] <- diff_i`` for masked i is a separate op
    (:func:`saga_commit_history`) issued by the updater only for *accepted*
    (non-stale) results -- the reference's driver-side ScalarMap merge.
    """
    return dense_masked_grad(X, y, w, mask, alpha=alpha)


# ------------------------------------------------------------------ sparse
# rcv1-class data in padded-ELL form (data/sparse.py): cols/vals are
# (n, K) with zero padding; w stays dense (the PS applies dense updates).

def row_blocks(n_rows: int, block_rows: int) -> Tuple[int, int]:
    """``(rows, blocks)`` of a pass over ``n_rows`` rows in blocks of at
    most ``block_rows``: ONE block shape, so a ragged count compiles
    nothing else; the last block is clamped (:func:`clamped_block`)."""
    rows = min(block_rows, n_rows)
    return rows, -(-n_rows // rows)


def clamped_block(i, rows: int, n_rows: int):
    """``(start, at)`` of block ``i``: the block is read at ``at``, pulled
    back so that it ends with the rows, and rows ``at .. start`` are the
    block before's.  A pass that SUMS over rows masks them
    (``steps.make_sparse_trajectory_loss_eval``); one that writes a value
    a row writes the same value twice (:func:`sparse_margins`)."""
    start = i * rows
    return start, jnp.minimum(start, n_rows - rows)


#: slots (rows x ELL width) of the sample one block of the eight-wide gather
#: holds, and under which a sample keeps the element-wise one.  On the v5e
#: the compiler keeps the ``(8, d / 8)`` table with its eight values padded
#: to a 128-lane row (64 MB at d = 1,000,000: half its VMEM) and writes a
#: block's gathered ``(slots, 8)`` rows padded alike, 512 B a slot.  The
#: block is sized so that those rows can NOT fit VMEM too (168 MB): where
#: they can, the compiler may keep THEM there and put the table out to HBM,
#: and a gather from HBM costs 9.3 ns a slot where the element-wise one
#: costs 6.9 (PERF.md section 6, PR 36; 145,472 x 40 slots, ms a pass, the
#: element-wise gather 40.2: blocks of 1,024 to 2,560 rows run 12.1 to 12.4
#: ALONE, table and rows both resident, and 54 inside the step, which has
#: other tenants; 3,072 to 4,096 rows 54 either way; 8,192 to 36,480 rows
#: 15.8 to 15.9 either way, the rows going to HBM and back, 168 to 747 MB
#: of temporaries; the whole sample at once 13.5 with 3.2 GB of them).  A
#: sample under one block is in that doubtful range and keeps the
#: element-wise gather.  The clamped last block is work done twice, so a
#: block is small beside the cell's sample (18 blocks, 1.4% over).
SPARSE_GATHER_BLOCK_SLOTS = 327_680


#: bytes of model over which no form of it can live in the v5e's 128 MiB
#: of VMEM: its gather then reads HBM by the index, whatever the program
SPARSE_VMEM_BYTES = 128 * 2**20
#: bytes of model over which the ELEMENT-WISE gather loses to the lane-row
#: one.  The compiler still prefetches such a model to VMEM for
#: ``w[c_sel]`` (webspam's 16,609,143 columns: 66 MB, just under half the
#: VMEM, ``S(1)`` in the program compiled for the v5e), and the step is
#: slower for it than with the model left in HBM and read a lane row an
#: index (PERF.md section 6, PR 39; ms a step on the narrowest and the
#: widest shard, 1,288 rows of 1,558 and of 16,384 slots: 34.5 and 418.7
#: against 28.9 and 291.5).  The bound lies between the two models
#: measured, criteo's 4 MB (6.9 ns a slot element-wise, alone) and these
#: 66 MB, nearer the one that lost: nothing between them is measured
SPARSE_ELEMENTS_BYTES = 32 * 2**20
#: slots of the sample one block of the lane-row gather holds, and under
#: which a sample keeps the element-wise one.  A block's gathered rows are
#: 512 B a slot, which stay in VMEM beside the step's other tenants (the
#: table is in HBM whatever the block).  On the v5e (PERF.md section 6,
#: PR 37; 236,640 x 16 slots from d = 54,686,452, ms a pass alone, the
#: element-wise gather 51.6): blocks of 1,024 rows 37.7, 8,192 rows 41.4,
#: 65,536 rows 49.1.  Re-measured at kdd2012's LIVE width, 236,640 x 11
#: slots, INSIDE the step (PERF.md section 6, PR 38; device ms a step, of
#: which the gather loop; rows are :func:`_block_rows`' whole tiles of 128):
#: 4,096 slots (256 rows) 54.68 / 15.28, **8,192 (640 rows) 54.09 / 14.69**,
#: 16,384 (1,408 rows) 57.51 / 18.12, 32,768 (2,944 rows) 56.38 / 16.98;
#: beside them 20,480 (1,792 rows) 54.10 / 14.70, 6,144 (512 rows) 57.51 /
#: 18.12 and 12,288 (1,024 rows) 64.46 / 25.06.  The loop is two programs
#: with a quirk each: the row gather takes 4.2 ns a slot, but 8.5 where a
#: block's slot count is a multiple of 1,024 (every block of the stored
#: width 16 is: the 9.1 ns PR 37 measured at all four of its sizes); the
#: lane's pick takes 1.9 ms a step at 256, 640 and 1,024 rows and 5.2 to 5.9
#: at 512, 1,408 and 2,944.  At the stored width 16 (what a caller that
#: passes no live width runs) 8,192 slots read 93.1 ms a step against
#: 88.9 at 16,384 in PR 37's sweep: the constant follows the cell's width.
SPARSE_LANES_BLOCK_SLOTS = 8_192


def sparse_gather_path(w, c_sel) -> str:
    """``"rows8"``, ``"lanes128"`` or ``"elements"``: which program
    :func:`sparse_margins` traces for the model ``w`` and the sampled
    columns ``c_sel`` (arrays, tracers or ``ShapeDtypeStruct``), from what
    can be observed when the step is built -- the backend, the model's
    rank, dtype and length, and the sample's slot count.

    The v5e's gather pays by the INDEX, not by the byte: 6.9 ns for one
    f32 of a ``(d,)`` vector, with model, indices and output all in VMEM,
    and 2.7 ns for a sublane tile of eight of an ``(8, d / 8)`` table
    (PERF.md section 6, PR 36).  ``"rows8"`` is that table, where ``w`` can
    be viewed as one (``d % 8 == 0``: criteo's 1,000,000 is, rcv1's 47,236
    is not) and the sample fills at least one block
    (``SPARSE_GATHER_BLOCK_SLOTS``, and why).  A model over
    ``SPARSE_VMEM_BYTES`` (kdd2012's 54,686,452 columns: 219 MB) is read
    from HBM by the index in any form, 13.6 ns for one f32 and 10.0 for
    the 128-lane row that holds it (PERF.md section 6, PR 37):
    ``"lanes128"``, whatever ``d % 8``, where the sample fills a block of
    THAT form.  So is a model over a quarter of it that has no eight-row
    view (webspam's 16,609,143 columns, ``d % 8 == 7``: 66 MB;
    ``SPARSE_ELEMENTS_BYTES``, and why).  The CPU, and everything not
    measured, keep the element-wise gather.
    """
    if not (_on_tpu() and len(w.shape) == 1 and w.dtype == jnp.float32
            and len(c_sel.shape) == 2):
        return "elements"
    slots = c_sel.shape[0] * c_sel.shape[1]
    if 4 * w.shape[0] > SPARSE_VMEM_BYTES:
        return "lanes128" if slots >= SPARSE_LANES_BLOCK_SLOTS else "elements"
    if w.shape[0] % 8 == 0 and slots >= SPARSE_GATHER_BLOCK_SLOTS:
        return "rows8"
    if (4 * w.shape[0] > SPARSE_ELEMENTS_BYTES
            and slots >= SPARSE_LANES_BLOCK_SLOTS):
        return "lanes128"
    return "elements"


def _as_indexed(c: jax.Array, d: int) -> jax.Array:
    """``c`` as ``w[c]`` reads it in a ``(d,)`` vector: a negative index
    counts from the end, and one out of range is clamped."""
    return jnp.clip(jnp.where(c < 0, c + d, c), 0, d - 1)


def _block_rows(block_slots: int, width: int) -> int:
    """Rows of a block of at most ``block_slots`` slots of a sample
    ``width`` slots wide: whole tiles of 128 rows where it holds one (a
    block is sliced from the sample along its rows, which the device
    tiles by 128: at the stored widths 16 and 40 the constants divide so
    anyway; at the live widths 11 and 39 a block of 1,489 or 8,402 rows
    cost the step 10.7 and 3.3 ms over one of 1,408 or 8,320; PERF.md
    section 6, PR 38); one row where a row is wider than the block."""
    rows = max(1, block_slots // width)
    return rows - rows % 128 if rows > 128 else rows


def _margins_in_blocks(c_sel, v_sel, dtype, block_slots, block_margins):
    """``(rows,)`` margins of a packed sample, ``block_margins(cb, vb)`` a
    block of ``block_slots`` slots at a time (ONE block shape; the last
    is clamped and writes the rows it shares with the one before twice)."""
    n_rows, width = c_sel.shape
    rows, blocks = row_blocks(n_rows, _block_rows(block_slots, width))

    def one_block(i, m):
        _, at = clamped_block(i, rows, n_rows)
        cb = jax.lax.dynamic_slice_in_dim(c_sel, at, rows)
        vb = jax.lax.dynamic_slice_in_dim(v_sel, at, rows)
        return jax.lax.dynamic_update_slice_in_dim(
            m, block_margins(cb, vb), at, 0)

    return jax.lax.fori_loop(0, blocks, one_block, jnp.zeros(n_rows, dtype))


def _gather_rows8(table: jax.Array, c: jax.Array) -> jax.Array:
    """``w[c]`` from ``table = w.reshape(8, d // 8)``, to the bit and for
    every int32 ``c`` (a negative index counts from the end and one out
    of range is clamped, as ``w[c]`` has it): ONE gather of the table's
    column ``c % (d // 8)``, eight values an index, and the row ``c //
    (d // 8)`` of it chosen by three selects on the row's bits."""
    q = table.shape[1]
    c = _as_indexed(c, 8 * q)
    row = c // q
    with jax.named_scope("gather"):
        x = table[:, c - row * q]  # (8,) + c.shape
    x = jnp.where((row & 4) > 0, x[4:], x[:4])
    x = jnp.where((row & 2) > 0, x[2:], x[:2])
    return jnp.where((row & 1) > 0, x[1], x[0])


def _model_gather(w, path):
    """``c -> w[c]`` for a block of columns of any shape, in the form
    ``path`` names (:func:`sparse_gather_path`)."""
    d = w.shape[0]
    if path == "rows8":
        table = w.reshape(8, d // 8)
        return lambda c: _gather_rows8(table, c)
    if path == "lanes128":
        q = -(-d // 128)
        table = jnp.pad(w, (0, q * 128 - d)).reshape(q, 128)
        return lambda c: _gather_lanes128(table, c, d)
    return lambda c: w[c]


def _margins_elements(c_sel, v_sel, w):
    """One model value an index: the expression every step held."""
    return jnp.sum(v_sel * w[c_sel], axis=1)


def _margins_rows8(c_sel, v_sel, w):
    """The margins in row blocks of the sample, ``(K, rows)`` with rows
    minor as a narrow ``c_sel`` is stored (its transposed block is a
    ``bitcast``): per block one eight-wide gather, the select, the
    multiply and the sum over the slots."""
    gather = _model_gather(w, "rows8")
    return _margins_in_blocks(
        c_sel, v_sel, jnp.result_type(v_sel.dtype, w.dtype),
        SPARSE_GATHER_BLOCK_SLOTS,
        lambda cb, vb: jnp.sum(vb.T * gather(cb.T), axis=0),
    )


def _gather_lanes128(table: jax.Array, c: jax.Array, d: int) -> jax.Array:
    """``w[c]`` from ``table``, ``w`` padded to whole rows of 128 lanes, for
    every int32 ``c`` (a negative index counts from the end and one out of
    range is clamped, as ``w[c]`` has it): ONE gather of the row ``c //
    128``, 512 B that lie together in HBM, and the lane ``c % 128`` of it
    kept by a compare and a maximum over the lanes (every other lane reads
    ``-inf``: the value itself, to the bit, a ``-0.0`` and a NaN too)."""
    c = _as_indexed(c, d)
    with jax.named_scope("gather"):
        x = table[c >> 7]  # c.shape + (128,)
    lane = jnp.arange(128, dtype=c.dtype)
    return jnp.max(
        jnp.where((c & 127)[..., None] == lane, x, -jnp.inf), axis=-1)


def _margins_lanes128(c_sel, v_sel, w):
    """The margins in row blocks of the sample from the model as ``(d /
    128, 128)`` lane rows (one padded copy of ``w`` a call: 0.5 ms at 219
    MB): per block one row gather, the lane's pick, the multiply and the
    sum over the slots."""
    gather = _model_gather(w, "lanes128")
    return _margins_in_blocks(
        c_sel, v_sel, jnp.result_type(v_sel.dtype, w.dtype),
        SPARSE_LANES_BLOCK_SLOTS,
        lambda cb, vb: jnp.sum(vb * gather(cb), axis=1),
    )


#: ``(R, C)``, the block of the ragged walk (:func:`walk_tile`) where the
#: step's ``(d,)`` accumulator stays in VMEM through the scatter-add's
#: loops, and where it lies in HBM (:func:`walk_accumulator_resident`): the
#: margins' lane-row gather takes the sample in it whichever program adds
#: the products, and the scatter-add does where the sum is one a block
#: (:func:`sparse_scatter_path`: the lists under the segments' bound).  On
#: the v5e (PERF.md section 6, PR 40; webspam's packed samples of 992 rows
#: from shards 1,664 to 16,384 slots wide, ``d`` 16,609,143, fenced): the
#: compiler sorts a block's (column, product) pairs in front of its
#: scatter-add from 16,384 slots a block on and not below, and the
#: UNSORTED scatter-add costs 68 to 73 ns a slot (blocks of 2,048 to 8,192
#: slots; the one-shot form behind its sort of all pairs 10.1 to 10.4).  A
#: block of 16,384 slots pays 8.9 to 9.8 ns a slot with the accumulator in
#: VMEM (16 x 1,024, 32 x 512, 64 x 256 alike), 13.8 to 14.6 at 32,768
#: slots, 10.5 to 12.7 from 65,536 on, where the accumulator is in HBM
#: whatever the shard; and with the accumulator in HBM the 16,384-slot
#: block pays about 19 (the narrowest shard's step 25.6 to 27.5 ms against
#: 20.1 at 128 x 512 and 21.2 unwalked).  The lane-row gather costs 2.2 to
#: 3.2 ns a slot at every block.  A chunk of 256 slots walks 1.05 to 1.10
#: times a sample's non-zeros, one of 512 1.07 to 1.16; 64 x 256 and 32 x
#: 512 take the same time a step from 3,840 slots a row on, and 64 x 256
#: 7% less at 2,176.  Where the sum is by sorted segments the block is the
#: margins' alone, and either serves (PERF.md section 6, PR 54: the
#: narrowest shard's step 7.88 ms at 64 x 256, 8.20 at 128 x 512).
SPARSE_WALK_TILE = (64, 256)
SPARSE_WALK_TILE_HBM = (128, 512)


def walk_accumulator_resident(d: int, shard_rows: int, width: int) -> bool:
    """Whether the compiler keeps the walked step's ``(d,)`` float32
    accumulator in VMEM through the scatter-add's loops, from the two
    things seen to decide it in the programs compiled for the v5e
    (``tests/test_step_layout.py`` holds both): it fits beside a block's
    operands (webspam's 66 MB does; half the VMEM is taken as the bound,
    nothing larger is measured), and the shard's own arrays do NOT fit
    VMEM: one that does (``shard_rows x width`` x 4 bytes under 128 MiB:
    webspam's narrowest, 109 MB) is prefetched there across programs, for
    the row gathers, and takes the accumulator's place."""
    return (4 * d <= SPARSE_VMEM_BYTES // 2
            and 4 * shard_rows * width > SPARSE_VMEM_BYTES)


def walk_tile(n_rows: int, width: int,
              resident: bool = True) -> "Tuple[int, int] | None":
    """``(R, C)``, the block a packed sample of ``n_rows`` rows read
    ``width`` slots wide is WALKED in, or ``None`` where it is read whole:
    the ONE place the walk is chosen, from the sample's shape.

    A shard stored in lane tiles (``width % 128 == 0``: what
    ``data/sparse._round_up`` gives from 121 slots on, and what
    ``SparseShardedDataset.live_widths`` reads whole) holds rows of
    UNEQUAL length, dealt to it in ascending order of length: a tile of
    consecutive packed rows has rows of nearly one length, the slots
    behind its longest row hold ``col=0, val=0``, and so does every slot
    of the unfilled tail of the capacity (``steps.sparse_step_capacity``:
    mean + 6 sigma).  The v5e pays a gather and a scatter-add by the SLOT
    whatever it holds, so the step stops at each tile's last non-zero
    (:func:`sample_walk`).  A shard stored in sublane tiles (``width`` <
    128: criteo 40, kdd2012 16, rcv1) has rows of one length and 1.2 to
    1.5% of slack: it keeps the one-shot programs to the letter.

    The block is ``SPARSE_WALK_TILE``, or ``SPARSE_WALK_TILE_HBM`` where
    the accumulator is not ``resident`` in VMEM
    (:func:`walk_accumulator_resident`), cut to the sample."""
    if width % 128:
        return None
    rows, chunk = SPARSE_WALK_TILE if resident else SPARSE_WALK_TILE_HBM
    return min(rows, n_rows), min(chunk, width)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SampleWalk:
    """How far a packed sample is walked (:func:`sample_walk`): ``chunks``
    ``(tiles,)`` int32, the chunks of ``chunk`` slots each tile of ``rows``
    rows is walked for.  The block's two sizes are static: a program a
    block shape."""

    chunks: jax.Array
    rows: int = dataclasses.field(metadata=dict(static=True))
    chunk: int = dataclasses.field(metadata=dict(static=True))


def sample_walk(v_sel: jax.Array, tile) -> "SampleWalk | None":
    """The walk of the packed sample ``v_sel`` in blocks of ``tile``
    (:func:`walk_tile`; ``None``: the sample is read whole, and so is the
    answer).

    A tile's bound is the position after the LAST non-zero of its rows,
    read from the values themselves: a zero in the middle of a row lies
    inside it, rows in any order are walked to their longest, and a tile
    of the unfilled tail (``v_sel`` is validity-zeroed) reads 0.  One
    compare and two maxima over the sample: vector work, not by the
    index."""
    if tile is None:
        return None
    rows, chunk = tile
    n_rows, width = v_sel.shape
    with jax.named_scope("walk.bounds"):
        slot = jnp.arange(1, width + 1, dtype=jnp.int32)
        length = jnp.max(jnp.where(v_sel != 0, slot, 0), axis=1)
        _, tiles = row_blocks(n_rows, rows)
        length = jnp.pad(length, (0, tiles * rows - n_rows))
        chunks = -(-jnp.max(length.reshape(tiles, rows), axis=1) // chunk)
    return SampleWalk(chunks, rows, chunk)


def _walk(c_sel, v_sel, walk, one_tile, carry):
    """The walk itself, once for the margins and once for the scatter-add:
    ``carry = one_tile(carry, at, own_rows, over_chunks)`` for every row
    tile of the packed sample, where ``over_chunks(f, init)`` folds ``f(acc,
    cb, vb)`` over THAT tile's ``walk.chunks[t]`` column chunks (a loop with
    a traced bound).  ONE block shape ``(R, C)``: the last tile and the last
    chunk are pulled back so that they end with the sample
    (:func:`clamped_block`); the rows such a tile shares with the one before
    are not its own (``own_rows``), and the slots such a chunk shares read 0
    in ``vb``."""
    n_rows, width = c_sel.shape
    rows, chunk = walk.rows, walk.chunk
    slot = jnp.arange(chunk, dtype=jnp.int32)
    row = jnp.arange(rows, dtype=jnp.int32)

    def tile(t, carry):
        start, at = clamped_block(t, rows, n_rows)

        def over_chunks(f, init):
            def one_chunk(j, acc):
                start_c, at_c = clamped_block(j, chunk, width)
                cb = jax.lax.dynamic_slice(c_sel, (at, at_c), (rows, chunk))
                vb = jax.lax.dynamic_slice(v_sel, (at, at_c), (rows, chunk))
                return f(acc, cb, jnp.where(at_c + slot >= start_c, vb, 0))

            return jax.lax.fori_loop(0, walk.chunks[t], one_chunk, init)

        return one_tile(carry, at, at + row >= start, over_chunks)

    return jax.lax.fori_loop(0, walk.chunks.shape[0], tile, carry)


def _margins_walked(c_sel, v_sel, w, walk, path):
    """The margins of a walked sample: per ``(R, C)`` block the model's
    gather in ``path``'s form, the product and the row sum, summed over a
    tile's chunks and written a tile at a time.  A row's slots beyond its
    tile's bound hold zeros and are not read."""
    gather = _model_gather(w, path)
    dtype = jnp.result_type(v_sel.dtype, w.dtype)

    def one_tile(m, at, own_rows, over_chunks):
        m_tile = over_chunks(
            lambda acc, cb, vb: acc + jnp.sum(vb * gather(cb), axis=1),
            jnp.zeros(walk.rows, dtype))
        kept = jax.lax.dynamic_slice_in_dim(m, at, walk.rows)
        return jax.lax.dynamic_update_slice_in_dim(
            m, jnp.where(own_rows, m_tile, kept), at, 0)

    return _walk(c_sel, v_sel, walk, one_tile,
                 jnp.zeros(c_sel.shape[0], dtype))


def sparse_margins(c_sel: jax.Array, v_sel: jax.Array, w: jax.Array,
                   walk: "SampleWalk | None" = None):
    """``m_i = sum_k v_sel[i, k] * w[c_sel[i, k]]``: the ``(rows,)`` margins
    ``x_i . w`` of padded-ELL rows (a padding slot's value is 0).

    THE definition behind every sparse step's residual and the ONE place
    its gather's program is chosen (:func:`sparse_gather_path`).  Every
    program gathers the same values; only the order of a margin's
    ``K``-term sum may differ.  With ``walk`` (:func:`sample_walk` of a
    PACKED sample) the sample is walked tile by tile up to each tile's
    last non-zero, in the same form of gather.
    """
    path = sparse_gather_path(w, c_sel)
    if walk is not None:
        return _margins_walked(c_sel, v_sel, w, walk, path)
    if path == "rows8":
        return _margins_rows8(c_sel, v_sel, w)
    if path == "lanes128":
        return _margins_lanes128(c_sel, v_sel, w)
    return _margins_elements(c_sel, v_sel, w)


@jax.jit
def sparse_residual(
    cols: jax.Array, vals: jax.Array, y: jax.Array, w: jax.Array
) -> jax.Array:
    """Per-sample ``x_i . w - y_i`` via gather: padding contributes 0."""
    return sparse_margins(cols, vals, w) - y


#: slots a tile of the sorted-segment kernel's columns
#: (``pallas_kernels.SEGMENT_TILE``, 4,096) must hold, on average over the
#: tiles of ``g``, for the sum by sorted segments to be chosen over the
#: scatter-add (:func:`sparse_scatter_path`): one group of the kernel, the
#: least a tile with a slot is read for.  On the v5e (PERF.md section 6,
#: PR 52; ms alone, scatter-add / segments): criteo's 5,673,408 pairs into
#: 1,000,000 columns (23,157 a tile) 39.0 / 10.1, its 1,156,584 under
#: ASAGA (4,721) 8.5 / 2.5, a whole shard's 55,868,280 (228,034) 376 /
#: 164; 2,603,040 pairs with uniform columns into 4,000,000 columns (2,665
#: a tile) 18.3 / 5.2, into 16,000,000 (666) 27.0 / 6.7, into kdd2012's
#: 54,686,452 (195) 27.2 / 11.8.  The constant is NOT the break-even,
#: which lies under 195 (between 61 and 102 for a walked sample): it
#: stands between kdd2012's 195 slots a tile and the 407 of webspam's
#: narrowest shard, and what it still holds is kdd2012's cell alone,
#: whose faster step would buy snapshots its 14.15 of 16 GB have no room
#: for, on the program it was admitted with until a ``benchmark`` PR has
#: moved its ``printer_freq`` (ROADMAP Speed 1(a), Design 2).  Until PR 57
#: it stood at 1,024 and held webspam's five narrowest shards too, by the
#: set-up a kernel's program cost a process; a step is now built once a
#: machine (``ops/program_store.py``)
SPARSE_SEGMENT_TILE_SLOTS = 256


def sparse_scatter_path(d: int, slots: int, dtype=jnp.float32) -> str:
    """``"segments"`` or ``"scatter"``: which program
    :func:`make_sparse_grad_sum` traces to add ``slots`` (column, product)
    pairs of ``dtype`` into a ``(d,)`` gradient, from what can be observed
    when the step is built -- the backend, the dtype, and the mean run of
    slots a tile of ``g``.  Whether the sample is walked no longer
    decides (PR 54); it says which scatter-add ``"scatter"`` means.

    The v5e's scatter-add pays by the INDEX, 6.7 to 10.5 ns a slot
    whatever it holds and in whatever order, while a sort of the pairs
    costs 2.0 ns a pair and the sum of a sorted run into a tile of columns
    is dense work, 0.5 ns a slot (``pallas_kernels.segment_tiles_sum``;
    PERF.md section 6, PR 52).  That kernel pays a grid step for every
    tile of ``g`` and a group of 1,024 slots for every tile that holds
    one, so it is chosen where the tiles' mean run reaches
    :data:`SPARSE_SEGMENT_TILE_SLOTS` (and why there): criteo's 1,000,000
    columns hold 23,157 slots a tile under ASGD and 4,721 under ASAGA,
    kdd2012's 54,686,452 hold 195.

    A WALKED sample (the blocks of a shard stored in lane tiles,
    webspam's) is held to the same bound since PR 54, ``slots`` its whole
    ``capacity x width`` list: all eight of webspam's shards (407, 532,
    658, 783, 939, 1,159, 1,534 and 4,008 a tile of 4,055) sort ONE list
    since PR 57; a shorter list adds a block of the walk at a time.  For
    it too the bound is NOT the break-even, which the chip read between
    61 and 102 slots a tile (PERF.md section 6, PR 54; the first rows of one packed sample,
    ms alone, walked scatter-adds / segments: 1,664 slots wide 992 rows,
    407 a tile, 12.59 / 4.68, 248 rows (102) 3.50 / 3.03, 128 (53) 1.94 /
    2.89; 3,840 wide 992 rows (939) 29.67 / 7.78, 128 (121) 4.57 / 3.06,
    64 (61) 2.62 / 2.79; 16,384 wide 992 rows (4,008) 79.23 / 29.62, 64
    (259) 4.57 / 3.60).  What held five of webspam's shards off it until
    PR 57 was the SET-UP: every program that holds the kernel costs 0.64 s
    of Python before its first step (the kernel traced and lowered by
    Mosaic once a step SHAPE), and all eight shapes on the segments read
    59.8 updates/s for 28.4 and ``setup_s`` +14%, where the benchmark
    refuses a PR at +10%.  A step now pays its tracing once a MACHINE
    (``steps._sized_by_capacity``), so the bound answers for kdd2012's
    memory alone.  The CPU and every other dtype keep the scatter-add.
    """
    if not (_on_tpu() and jnp.dtype(dtype) == jnp.dtype(jnp.float32)):
        return "scatter"
    tiles = -(-d // _kernels().SEGMENT_TILE)
    return ("segments" if slots >= SPARSE_SEGMENT_TILE_SLOTS * tiles
            else "scatter")


def sparse_sorted_pairs(d: int, slots: int, dtype=jnp.float32) -> int:
    """The (column, product) pairs :func:`make_sparse_grad_sum` SORTS to
    add ``slots`` of them into a ``(d,)`` gradient, from the host's
    integers: the whole list, padded to the kernel's blocks, where
    :func:`sparse_scatter_path` says ``"segments"``; 0 where the program
    holds a scatter-add and no sort of its own."""
    if sparse_scatter_path(d, slots, dtype) != "segments":
        return 0
    return _kernels().segment_list_pairs(slots)


def make_sparse_grad_sum(d: int):
    """jit (cols, vals, coeff[, walk]) -> dense (d,) gradient: the sum of
    every slot's product at its column, by sorted segments, by ONE
    scatter-add over the whole sample, or by one a block of the walk
    (:func:`sparse_scatter_path`: the ONE place the program is chosen).

    ``g = sum_i coeff_i * x_i`` -- the sparse analog of ``X.T @ coeff``:
    every slot's product ``vals * coeff`` is added into ``g`` at its
    column.  Padding slots add 0 to column 0 and a column id outside
    ``[0, d)`` is dropped.  A scatter-add pays for
    every slot it is GIVEN, 6.7 to 8.8 ns each whatever the value, so the
    steps give it as few empty ones as the storage lets them tell: a shard
    stored in sublane tiles at its live width (``steps._live_columns``: the
    ELL columns that are padding in every row never reach it; the padding
    left is that of rows shorter than the longest), and a packed sample of a
    shard stored in lane tiles with ``walk`` (:func:`sample_walk`): ``g``
    is then the carry of the walk's loops and takes one ``(R, C)`` block a
    scatter-add, each row tile up to its last non-zero, so the slots behind
    it and the unfilled tail of the capacity are never given.  By sorted
    segments a walked sample is ONE list, its ``capacity x width`` pairs
    (PERF.md section 6, PR 54): a pair whose product is 0 -- behind a
    row's end, in the unfilled tail -- goes under the column beyond every
    tile, where a dropped pair goes, so that the kernel reads the live
    pairs only and column 0's tile holds no run of zeros; the walk's
    bounds are not read.  Only the order of a column's terms differs
    between the forms.

    The scatter-adds take the slots in the order they are stored.  On the
    v5e a sort is cheap and an element-wise gather or scatter is dear
    (PERF.md section 6, PR 33; 5,818,880 slots into d = 1,000,000): the
    scatter-add costs 6.9 ns a slot, with Zipf(1) columns (7.4% of the
    slots on one column) as with uniform ones, and a sort in FRONT of it
    buys nothing: the scatter behind an argsort and two permutation
    gathers (``indices_are_sorted=True``) cost 30.3 ns a slot, behind
    ``lax.sort`` with the products as payload 11.5.  What pays is a sort
    with NO scatter behind it (``"segments"``; PERF.md section 6, PR 52):
    the pairs sorted once by column, 2.0 ns a pair, and each tile of
    ``g``'s columns summed from its contiguous run of them by a kernel,
    0.5 ns a slot: 10.1 ms for criteo's 5,673,408 slots where the
    scatter-add takes 39.0, and a column's terms summed by group of
    1,024, not one after the other (3.4e-7 of ``max |g|`` off the float64
    sum where the scatter-add is 6.2e-6 off).

    The eight-wide form that halved the gather (:func:`sparse_margins`)
    does NOT pay here (PERF.md section 6, PR 36; the same slots, 40.2 ms
    this way): updates as rows of eight added into an ``(8, d / 8)``
    accumulator took 81.3 ms in blocks of 8,192 rows and 126.3 in blocks of
    65,536, into an ``(8, d)`` one 246.5 and 328.5.  The scatter stays
    element-wise.
    """

    def add(g, cols, products):
        return g.at[cols.ravel()].add(products.ravel(), mode="drop")

    @jax.jit
    def grad_sum(cols, vals, coeff, walk=None):
        with jax.named_scope("grad"):
            if sparse_scatter_path(d, cols.size, vals.dtype) == "segments":
                products = vals * coeff[:, None]
                if walk is not None:
                    # the slots behind a row's end and the unfilled tail
                    cols = jnp.where(products != 0, cols, d)
                return _kernels().segment_tiles_sum(
                    cols.ravel(), products.ravel(), d)
            g = jnp.zeros(d, vals.dtype)
            if walk is None:
                return add(g, cols, vals * coeff[:, None])

            def one_tile(g, at, own_rows, over_chunks):
                r = jnp.where(own_rows, jax.lax.dynamic_slice_in_dim(
                    coeff, at, walk.rows), 0)
                return over_chunks(
                    lambda g, cb, vb: add(g, cb, vb * r[:, None]), g)

            return _walk(cols, vals, walk, one_tile, g)

    return grad_sum


@functools.partial(jax.jit, donate_argnums=(1,))
def saga_commit_history(
    alpha: jax.Array, diff: jax.Array, mask: jax.Array
) -> jax.Array:
    """alpha[i] <- diff[i] where mask_i else unchanged (accepted update).

    ``diff`` (the worker's candidate scalars) is donated -- it is dead after
    the commit, and the new table slice is written into its buffer.  ``alpha``
    is NOT donated: an in-flight worker task dispatched before this commit may
    still hold the old slice's handle (routine under async overlap).
    """
    with jax.named_scope("history.commit"):
        return jnp.where(mask > 0, diff, alpha)
