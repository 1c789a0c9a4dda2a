"""The program's gradients and objective held to the benchmark's reference.

``benchmark/reference.py`` is plain float32 ``jax.numpy`` at precision
"highest" and shares no code with the program; here the program's
full-shard gradients (dense in both storage types, padded ELL) and its
trajectory objective must agree with it at a tiny size on the CPU.
"""

import functools
import os
import re
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
    assert abs(reference.objective(shards, np.zeros(d)) - prog[0]) < 1e-5 * prog[0]
    assert abs(reference.objective(shards, w) - prog[1]) < 1e-5 * prog[1]


def test_data_pins_hold_for_both_generators():
    dense = ShardedDataset.generate_on_device(4096, 64, 4, jax.devices()[:1], seed=11)
    pins, f0 = reference.data_pins([dense.shard(i) for i in range(4)])
    assert abs(pins["row_second_moment"] - 1.0) < 0.01
    assert abs(f0 - pins["label_second_moment"]) < 1e-6 * f0
    sparse = SparseShardedDataset.generate_on_device(
        4096, 512, 12, 4, jax.devices()[:1], seed=11
    )
    pins, _f0 = reference.data_pins([sparse.shard(i) for i in range(4)])
    assert pins["nnz_per_row"] == 12
    assert abs(pins["row_second_moment"] - 1.0) < 0.02


def test_block_walk_covers_a_ragged_tail():
    """Rows that are no multiple of the block are counted once each."""
    ds = ShardedDataset.generate_on_device(1000, 16, 1, jax.devices()[:1], seed=2)
    sh = ds.shard(0)
    whole = reference.shard_sums(sh, np.ones(16), block_rows=1000)
    ragged = reference.shard_sums(sh, np.ones(16), block_rows=384)
    assert ragged["rows"] == whole["rows"] == 1000
    for key in ("loss", "xx", "yy"):
        assert abs(ragged[key] - whole[key]) < 1e-5 * abs(whole[key])
    g_whole = reference.full_gradient(sh, np.ones(16), 16, block_rows=1000)
    g_ragged = reference.full_gradient(sh, np.ones(16), 16, block_rows=384)
    assert _rel(g_ragged, g_whole) < 1e-5


# ------------------------------------------------- sums without a gradient
#
# Until PR 29 one block function computed the sums AND the gradient, and the
# whole-dataset passes after every run threw the gradient away: for padded
# ELL an unsorted scatter-add of every stored slot into a ``(d,)`` array.
# The two block functions of the parent commit, verbatim, are the loop
# version the split is held to.


@functools.partial(jax.jit, static_argnames=("block", "loss"))
def _parent_dense_block(X, y, w, weights, start, block, loss):
    s, live = reference._block(X, start, block)
    Xb = jax.lax.dynamic_slice_in_dim(X, s, block).astype(jnp.float32)
    yb = jax.lax.dynamic_slice_in_dim(y, s, block)
    mb = jax.lax.dynamic_slice_in_dim(weights, s, block) * live
    m = reference._dot(Xb, w)
    if loss == "least_squares":
        r = m - yb
        per_row = r * r
    else:
        r = jax.nn.sigmoid(m) - yb
        per_row = jnp.logaddexp(0.0, m) - yb * m
    return (
        jnp.sum(per_row * live),
        reference._dot((mb * r)[None, :], Xb)[0],
        jnp.sum(jnp.sum(Xb * Xb, axis=1) * live),
        jnp.sum(yb * yb * live),
    )


@functools.partial(jax.jit, static_argnames=("block", "d"))
def _parent_ell_block(cols, vals, y, w, weights, start, block, d):
    s, live = reference._block(vals, start, block)
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


def _parent_shard_sums(shard, w, d, loss, weights, block_rows):
    sparse = hasattr(shard, "cols")
    rows = int(shard.y.shape[0])
    w = jnp.asarray(w, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    block = min(block_rows, rows)
    acc = None
    for start in range(0, rows, block):
        if sparse:
            part = _parent_ell_block(shard.cols, shard.vals, shard.y, w,
                                     weights, start, block=block, d=d)
        else:
            part = _parent_dense_block(shard.X, shard.y, w, weights, start,
                                       block=block, loss=loss)
        acc = part if acc is None else tuple(a + b for a, b in zip(acc, part))
    out = {"loss": float(acc[0]), "grad": np.asarray(acc[1], np.float64),
           "xx": float(acc[2]), "yy": float(acc[3]), "rows": rows}
    if sparse:
        out["nnz"] = float(acc[4])
    return out


def _tiny_dataset(name, workers=3):
    from test_bench_harness import _tiny_config

    from benchmark import run

    config = _tiny_config(name)
    # three workers: 1,366-row shards, which 384-row blocks do not divide
    return config, run.build_dataset(config, workers, jax.devices()[:1], seed=17)


@pytest.mark.parametrize("name,loss", [
    ("tiny-dense-f32", "least_squares"), ("tiny-dense-f32", "logistic"),
    ("tiny-dense-bf16", "least_squares"), ("tiny-dense-bf16", "logistic"),
    ("tiny-sparse", "least_squares"),
])
def test_the_split_paths_equal_the_parents_one_block_to_the_bit(name, loss):
    """``shard_sums`` (no gradient) and ``full_gradient`` (nothing else)
    against the parent's one block function, ragged last block included:
    the same expressions on the same rows in the same order, so the same
    bits.  The parent had no logistic padded-ELL block: that pairing is held
    to the dense block in the next test instead."""
    config, ds = _tiny_dataset(name)
    d = config["d"]
    rs = np.random.default_rng(3)
    w = (0.3 * rs.standard_normal(d)).astype(np.float32)
    for wid in range(ds.num_workers):
        sh = ds.shard(wid)
        rows = int(sh.y.shape[0])
        assert rows % 384
        weights = (rs.random(rows) < 0.4).astype(np.float32)
        want = _parent_shard_sums(sh, w, d, loss, weights, 384)
        got = reference.shard_sums(sh, w, loss, block_rows=384)
        assert set(got) == set(want) - {"grad"}
        for key in got:
            assert got[key] == want[key], key
        grad = reference.full_gradient(sh, w, d, loss, weights, block_rows=384)
        assert np.array_equal(grad, want["grad"])
    # and over the dataset: the objective and the pins read those sums
    shards = [ds.shard(i) for i in range(ds.num_workers)]
    tot = reference.dataset_sums(shards, w, loss)
    assert tot["rows"] == config["n"]
    assert reference.objective(shards, w, loss) == tot["loss"] / config["n"]


def test_padded_ell_logistic_sums_equal_the_dense_blocks_on_the_same_rows():
    """The logistic loss the padded-ELL block gained in PR 29 (``log(1 +
    e^m) - y m``, residual ``sigmoid(m) - y``) against the dense block on
    ``densify()`` of the same shards, labels cut to {0, 1}."""
    import types

    config, ds = _tiny_dataset("tiny-sparse")
    d = config["d"]
    X, y = densify(ds)
    y01 = (y > 0).astype(np.float32)
    w = (2.0 * np.random.default_rng(4).standard_normal(d)).astype(np.float32)
    for wid in range(ds.num_workers):
        sh = ds.shard(wid)
        rows = slice(sh.start, sh.start + sh.size)
        ell = types.SimpleNamespace(cols=sh.cols, vals=sh.vals,
                                    y=jnp.asarray(y01[rows]))
        dense = types.SimpleNamespace(X=jnp.asarray(X[rows], jnp.float32),
                                      y=ell.y)
        got = reference.shard_sums(ell, w, "logistic", block_rows=384)
        want = reference.shard_sums(dense, w, "logistic", block_rows=384)
        # not ``xx``: a row that draws one column twice stores two squares
        # where the dense cell holds the square of their sum
        for key in ("loss", "yy", "rows"):
            assert abs(got[key] - want[key]) <= 1e-6 * abs(want[key]), key
        g = reference.full_gradient(ell, w, d, "logistic", block_rows=384)
        g_dense = reference.full_gradient(dense, w, d, "logistic",
                                          block_rows=384)
        assert _rel(g, g_dense) < TOL["float32"]
    with pytest.raises(ValueError, match="hinge"):
        reference.shard_sums(ds.shard(0), w, "hinge")


_HLO = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (?P<type>\S+) "
                  r"(?P<op>[a-z][a-z0-9\-]*)\(", re.M)


@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
def test_the_padded_ell_sums_block_builds_no_gradient(loss):
    """Read on the COMPILED program (the CPU's here; compiled for a
    described v5e by hand, PERF.md section 6, PR 29): the sums-only block
    holds no ``scatter`` and no array of ``d`` elements but ``w`` itself,
    where the gradient block holds both."""
    rows, width, d, block = 1366, 16, 1000, 384
    args = (
        jax.ShapeDtypeStruct((rows, width), jnp.int32),
        jax.ShapeDtypeStruct((rows, width), jnp.float32),
        jax.ShapeDtypeStruct((rows,), jnp.float32),
        jax.ShapeDtypeStruct((d,), jnp.float32),
    )
    start = jax.ShapeDtypeStruct((), jnp.int32)
    sums = reference._ell_sums.lower(
        *args, start, block=block, loss=loss).compile().as_text()
    instrs = [(m["type"], m["op"]) for m in _HLO.finditer(sums)]
    assert len(instrs) > 10
    assert not [op for _t, op in instrs if "scatter" in op]
    assert "scatter" not in sums
    of_d = [op for typ, op in instrs if re.match(rf"f32\[{d}\]", typ)]
    assert of_d and set(of_d) == {"parameter"}, of_d
    weights = jax.ShapeDtypeStruct((rows,), jnp.float32)
    grad = reference._ell_grad.lower(
        *args, weights, start, block=block, d=d, loss=loss
    ).compile().as_text()
    assert "scatter" in grad


# ------------------------------------------------- the pins gather nothing
#
# Until PR 30 ``data_pins`` walked the dataset with a model of zeros: for
# padded ELL a ``w[cols]`` gather of every stored slot, multiplied by zeros.
# The parent's pass is still here: it is ``dataset_sums`` at that model.


def _parent_data_pins(shards, d, loss):
    tot = reference.dataset_sums(shards, np.zeros(d, np.float32), loss)
    n = tot["rows"]
    pins = {"row_second_moment": tot["xx"] / n,
            "label_second_moment": tot["yy"] / n}
    if tot["nnz"]:
        pins["nnz_per_row"] = tot["nnz"] / n
    return pins, tot["loss"] / n


@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
@pytest.mark.parametrize("name", ["tiny-dense-f32", "tiny-dense-bf16",
                                  "tiny-sparse"])
def test_data_pins_without_a_model_equal_the_parents_to_the_bit(name, loss):
    """A margin of exact zeros gives the same ``per_row`` whether it was
    multiplied out or written down: the pins and the objective at ``w = 0``
    are the parent's bits, whole shards and ragged last block alike."""
    config, ds = _tiny_dataset(name)
    shards = [ds.shard(i) for i in range(ds.num_workers)]
    assert reference.data_pins(shards, loss) == _parent_data_pins(
        shards, config["d"], loss)
    zeros = np.zeros(config["d"], np.float32)
    for sh in shards:
        assert int(sh.y.shape[0]) % 384
        got = reference.shard_sums(sh, None, loss, block_rows=384)
        want = reference.shard_sums(sh, zeros, loss, block_rows=384)
        assert got == want and got["loss"] > 0


@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
def test_the_pins_blocks_gather_and_multiply_no_model(loss):
    """Read on the CPU's compiled program, beside the test above it: with
    no model the padded-ELL block holds no ``gather`` (the objective's
    block does), and the dense block no ``dot``."""
    rows, width, d, block = 1366, 16, 1000, 384
    cols, vals, y, w = (
        jax.ShapeDtypeStruct((rows, width), jnp.int32),
        jax.ShapeDtypeStruct((rows, width), jnp.float32),
        jax.ShapeDtypeStruct((rows,), jnp.float32),
        jax.ShapeDtypeStruct((d,), jnp.float32),
    )
    start = jax.ShapeDtypeStruct((), jnp.int32)

    def ops(fn, *args):
        text = fn.lower(*args, start, block=block,
                        loss=loss).compile().as_text()
        found = {m["op"] for m in _HLO.finditer(text)}
        assert len(found) > 5
        return found

    def has(found, word):
        return sorted(op for op in found if word in op)

    pins = ops(reference._ell_sums, cols, vals, y, None)
    assert not has(pins, "gather") and not has(pins, "scatter")
    assert has(ops(reference._ell_sums, cols, vals, y, w), "gather")
    X = jax.ShapeDtypeStruct((rows, d), jnp.float32)
    pins = ops(reference._dense_sums, X, y, None)
    assert not has(pins, "dot") and not has(pins, "custom-call")
    objective = ops(reference._dense_sums, X, y, w)
    assert has(objective, "dot") or has(objective, "custom-call")
