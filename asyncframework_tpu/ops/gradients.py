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
multiply instead of a shape-changing filter -- and on the TPU the filter
would be no saving at all: a dense shard whose ``d`` is no multiple of 128
is stored column-major there (rows minor, PERF.md section 3), so picking
rows means relaying all of it and no step reads less than the whole shard.

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
product, one read) stay XLA's.
"""

from __future__ import annotations

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


def dense_step_path(X) -> str:
    """``"onepass"`` or ``"two_products"``: which program
    :func:`dense_masked_grad` traces for the shard ``X`` (an array, a
    tracer or a ``ShapeDtypeStruct``), from what can be observed when the
    step is built -- the backend, the rank, the width and the dtype.

    The one-pass kernel is right only where ``X.T`` is a ``bitcast``: on
    the TPU, whose compiler stores an ``(n, d)`` array with ``d % 128 !=
    0`` column-major (784, 2000, 64: ``{0,1}``) and one with ``d % 128 ==
    0`` row-major (``tests/test_step_layout.py`` reads both off compiled
    programs).  What the kernel itself takes (f32 or bf16, ``d`` a whole
    number of sublane tiles, a block that fits VMEM) is
    ``pallas_kernels.onepass_takes``.  On the v5e it won at every shape timed
    (PERF.md section 6, PR 26: 1.0M and 253k x 784 bf16, 1.0M x 784 and
    50k x 2,000 f32), so nothing else is asked.
    """
    if (_on_tpu() and len(X.shape) == 2 and X.shape[1] % 128 != 0
            and _kernels().onepass_takes(X.shape[1], X.dtype)):
        return "onepass"
    return "two_products"


def dense_masked_grad(X, y, w, mask, alpha=None, logistic: bool = False):
    """``(g, diff)`` of a dense worker step over a whole shard: ``diff =
    link(X w) - y`` (``link`` the identity, or the sigmoid with
    ``logistic``) and ``g = X^T (mask * (diff [- alpha]))``.

    THE definition behind both dense losses' gradient sums and the ASAGA
    step, and the ONE place the program is chosen (:func:`dense_step_path`):
    one read of the shard through the Pallas kernel, or the two XLA
    products.  With ``alpha`` (ASAGA: ``diff`` are the candidate history
    scalars) ``g`` keeps its f32 vector and promotes the shard on either
    path, as ``steps.make_saga_table_delta`` does and for its reason;
    without it the two-product path is ``mm_f32``'s, as ever.
    """
    if dense_step_path(X) == "onepass":
        with jax.named_scope("grad"):
            return _kernels().dense_onepass(
                X, y, w, mask, alpha, logistic=logistic)
    with jax.named_scope("residual"):
        margin = shard_matvec(X, w)
        diff = (jax.nn.sigmoid(margin) if logistic else margin) - y
    with jax.named_scope("grad"):
        if alpha is None:
            return mm_f32(X.T, mask * diff), diff
        return X.T @ (mask * (diff - alpha)), diff


@jax.jit
def least_squares_grad_sum(
    X: jax.Array, y: jax.Array, w: jax.Array, mask: jax.Array
) -> jax.Array:
    """Sum over masked samples of ``(x_i . w - y_i) x_i``.

    ``mask`` is {0,1} (or weights) of shape ``(n,)``; equivalent to the
    reference's sample-then-map-then-reduce with vector-add comOp.
    """
    return dense_masked_grad(X, y, w, mask)[0]


@jax.jit
def least_squares_loss(X: jax.Array, y: jax.Array, w: jax.Array) -> jax.Array:
    """Mean squared error over the shard: sum_i (x_i.w - y_i)^2 (unnormalized).

    The drivers print ``sum_i (x_i.w - y_i)^2 / N`` per trajectory snapshot
    (``SparkASGDThread.scala:386-401``); normalization by N happens at the
    caller, which knows the global N.
    """
    r = mm_f32(X, w) - y
    return jnp.sum(r * r)


@jax.jit
def logistic_grad_sum(
    X: jax.Array, y: jax.Array, w: jax.Array, mask: jax.Array
) -> jax.Array:
    """Sum over masked samples of the logistic-loss gradient.

    Parity: ``LogisticGradient`` (binary case) -- labels in {0,1};
    ``grad_i = (sigmoid(x_i.w) - y_i) x_i``.
    """
    return dense_masked_grad(X, y, w, mask, logistic=True)[0]


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

@jax.jit
def sparse_residual(
    cols: jax.Array, vals: jax.Array, y: jax.Array, w: jax.Array
) -> jax.Array:
    """Per-sample ``x_i . w - y_i`` via gather: padding contributes 0."""
    return jnp.sum(vals * w[cols], axis=1) - y


def make_sparse_grad_sum(d: int):
    """jit (cols, vals, coeff) -> dense (d,) gradient via ONE scatter-add.

    ``g = sum_i coeff_i * x_i`` -- the sparse analog of ``X.T @ coeff``:
    every slot's product ``vals * coeff`` is added into ``g`` at its column,
    in the order the slots are stored.  Padding slots add 0 to column 0 and
    a column id outside ``[0, d)`` is dropped.

    Nothing puts the slots in order first.  On the v5e a sort is cheap and
    an element-wise gather or scatter is dear (PERF.md section 6, PR 33;
    5,818,880 slots into d = 1,000,000): this scatter-add costs 6.9 ns a
    slot, with Zipf(1) columns (7.4% of the slots on one column) as with
    uniform ones; sorting the slots by column in front of it (an argsort
    and two permutation gathers, then ``indices_are_sorted=True``) cost
    30.3, and carrying the products through ``lax.sort`` as a payload
    11.5.  The scatter adds a column's terms in the order they are stored,
    which is the order a stable sort left them in: ``g`` is the sorted
    form's to the bit.
    """

    @jax.jit
    def grad_sum(cols, vals, coeff):
        with jax.named_scope("grad"):
            return jnp.zeros(d, vals.dtype).at[cols.ravel()].add(
                (vals * coeff[:, None]).ravel(), mode="drop"
            )

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
