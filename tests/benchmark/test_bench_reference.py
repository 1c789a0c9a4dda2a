"""The program's gradients and objective held to the benchmark's reference.

``benchmark/reference.py`` is plain float32 ``jax.numpy`` at precision
"highest" and shares no code with the program; here the program's
full-shard gradients (dense in both storage types, padded ELL) and its
trajectory objective must agree with it at a tiny size on the CPU.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from asyncframework_tpu.data.sharded import ShardedDataset  # noqa: E402
from asyncframework_tpu.data.sparse import SparseShardedDataset, densify  # noqa: E402
from asyncframework_tpu.ops import gradients, steps  # noqa: E402
from benchmark import reference  # noqa: E402

N, D, WORKERS = 2048, 48, 4

# Why these tolerances (relative to the gradient's norm):
# - float32: program and reference do the same f32 arithmetic in another
#   order (the CPU's default precision is exact f32): rounding only.
# - bfloat16 storage: the program casts the f32 residual ``mask * r`` to the
#   shard's bf16 before ``X^T r`` (``mm_f32`` casts the vector down), an
#   error of 2^-9 relative an entry that averages down over the rows; the
#   reference keeps it in f32.  1e-2 would still fail a gradient computed
#   from bf16 *accumulation* (which loses whole digits over 512 rows).
TOL = {"float32": 2e-5, "bfloat16": 1e-2}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def w():
    return np.random.default_rng(7).normal(size=D).astype(np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dense_full_shard_gradient_matches_reference(dtype, w):
    ds = ShardedDataset.generate_on_device(
        N, D, WORKERS, jax.devices()[:1], seed=3, dtype=jnp.dtype(dtype)
    )
    step = steps.make_asgd_worker_step(1.0)  # Bernoulli(1): every row
    for wid in range(WORKERS):
        sh = ds.shard(wid)
        want = reference.full_gradient(sh, w, D)
        ones = jnp.ones(sh.size, jnp.float32)
        got = gradients.least_squares_grad_sum(sh.X, sh.y, jnp.asarray(w), ones)
        assert _rel(got, want) < TOL[dtype]
        got_step, _key = step(sh.X, sh.y, jnp.asarray(w), jax.random.PRNGKey(0))
        assert _rel(got_step, want) < TOL[dtype]


def test_dense_weighted_rows_match_reference(w):
    ds = ShardedDataset.generate_on_device(N, D, WORKERS, jax.devices()[:1], seed=4)
    sh = ds.shard(0)
    mask = (np.random.default_rng(1).random(sh.size) < 0.1).astype(np.float32)
    want = reference.full_gradient(sh, w, D, weights=mask)
    got = gradients.least_squares_grad_sum(
        sh.X, sh.y, jnp.asarray(w), jnp.asarray(mask)
    )
    assert _rel(got, want) < TOL["float32"]


def test_padded_ell_gradient_matches_reference_and_dense_algebra():
    d, nnz = 256, 9
    ds = SparseShardedDataset.generate_on_device(
        N, d, nnz, WORKERS, jax.devices()[:1], seed=5
    )
    w = np.random.default_rng(8).normal(size=d).astype(np.float32)
    step = steps.make_sparse_asgd_worker_step(1.0, d)
    X, y = densify(ds)  # host, float32: the third opinion
    for wid in range(WORKERS):
        sh = ds.shard(wid)
        want = reference.full_gradient(sh, w, d)
        got, _key = step(sh.cols, sh.vals, sh.y, jnp.asarray(w),
                         jax.random.PRNGKey(0))
        # scatter-add order differs; f32 rounding only
        assert _rel(got, want) < TOL["float32"]
        Xs = X[sh.start:sh.start + sh.size].astype(np.float64)
        ys = y[sh.start:sh.start + sh.size].astype(np.float64)
        dense = Xs.T @ (Xs @ w.astype(np.float64) - ys)
        assert _rel(want, dense) < TOL["float32"]


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_objective_matches_the_programs_trajectory_eval(kind):
    if kind == "dense":
        d = D
        ds = ShardedDataset.generate_on_device(N, d, WORKERS, jax.devices()[:1], seed=6)
        ev = steps.make_trajectory_loss_eval("least_squares")
        part = lambda sh, W: ev(sh.X, sh.y, W)  # noqa: E731
    else:
        d = 256
        ds = SparseShardedDataset.generate_on_device(
            N, d, 9, WORKERS, jax.devices()[:1], seed=6
        )
        ev = steps.make_sparse_trajectory_loss_eval()
        part = lambda sh, W: ev(sh.cols, sh.vals, sh.y, W)  # noqa: E731
    w = np.random.default_rng(9).normal(size=d).astype(np.float32) * 0.1
    shards = [ds.shard(i) for i in range(WORKERS)]
    W = jnp.stack([jnp.zeros(d, jnp.float32), jnp.asarray(w)])
    prog = sum(np.asarray(part(sh, W), np.float64) for sh in shards) / N
    assert abs(reference.objective(shards, np.zeros(d), d) - prog[0]) < 1e-5 * prog[0]
    assert abs(reference.objective(shards, w, d) - prog[1]) < 1e-5 * prog[1]


def test_data_pins_hold_for_both_generators():
    dense = ShardedDataset.generate_on_device(4096, 64, 4, jax.devices()[:1], seed=11)
    pins, f0 = reference.data_pins([dense.shard(i) for i in range(4)], 64)
    assert abs(pins["row_second_moment"] - 1.0) < 0.01
    assert abs(f0 - pins["label_second_moment"]) < 1e-6 * f0
    sparse = SparseShardedDataset.generate_on_device(
        4096, 512, 12, 4, jax.devices()[:1], seed=11
    )
    pins, _f0 = reference.data_pins([sparse.shard(i) for i in range(4)], 512)
    assert pins["nnz_per_row"] == 12
    assert abs(pins["row_second_moment"] - 1.0) < 0.02


def test_block_walk_covers_a_ragged_tail():
    """Rows that are no multiple of the block are counted once each."""
    ds = ShardedDataset.generate_on_device(1000, 16, 1, jax.devices()[:1], seed=2)
    sh = ds.shard(0)
    whole = reference.shard_sums(sh, np.ones(16), 16, block_rows=1000)
    ragged = reference.shard_sums(sh, np.ones(16), 16, block_rows=384)
    assert ragged["rows"] == whole["rows"] == 1000
    for key in ("loss", "xx", "yy"):
        assert abs(ragged[key] - whole[key]) < 1e-5 * abs(whole[key])
    assert _rel(ragged["grad"], whole["grad"]) < 1e-5
