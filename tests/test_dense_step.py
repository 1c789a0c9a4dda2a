"""The dense worker step: same sample, same sum.

For one key the step must sum exactly the rows that
``bernoulli(split(key)[1], b, (n,))`` draws and hand back ``split(key)[0]``
-- the sample stream every recorded trajectory rests on -- whatever the
storage dtype, the loss or the rate; and the engine step and the fused
rounds must run ONE definition of it (``steps._dense_sampled_gradient``).
The reference below is NumPy float64 over the rows the mask selects.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.ops import gradients, steps

# two row blocks and a ragged tail for gradients.shard_matvec (2,048 rows a block)
N, D = 5000, 24


def _problem(dtype, loss, seed=0):
    rs = np.random.default_rng(seed)
    X = jnp.asarray(rs.normal(size=(N, D)) / np.sqrt(D), dtype)
    if loss == "logistic":
        y = (rs.random(N) < 0.5).astype(np.float32)
    else:
        y = rs.normal(size=N).astype(np.float32)
    w = rs.normal(size=D).astype(np.float32)
    return X, jnp.asarray(y), jnp.asarray(w)


def _reference(X, y, w, mask, loss):
    """``X[m]^T r(X[m] w, y[m])`` in float64 from the STORED values, and
    each sampled row's weight in it, ``|r_i| |x_i|``."""
    Xm = np.asarray(X.astype(jnp.float32), np.float64)[mask]
    z = Xm @ np.asarray(w, np.float64)
    ym = np.asarray(y, np.float64)[mask]
    r = (1.0 / (1.0 + np.exp(-z)) - ym) if loss == "logistic" else (z - ym)
    return Xm.T @ r, np.abs(r) * np.linalg.norm(Xm, axis=1)


@pytest.mark.parametrize("batch_rate", [0.05, 0.1, 0.3, 0.5, 0.9])
@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_step_sums_the_rows_its_key_draws(dtype, loss, batch_rate):
    X, y, w = _problem(dtype, loss)
    key = jax.random.PRNGKey(1234)
    g, new_key = steps.make_asgd_worker_step(batch_rate, loss)(X, y, w, key)

    kept, sub = jax.random.split(key)
    mask = np.asarray(jax.random.bernoulli(sub, batch_rate, (N,)))
    assert 0 < mask.sum() < N
    assert np.array_equal(np.asarray(new_key), np.asarray(kept))

    ref, row_weights = _reference(X, y, w, mask, loss)
    assert g.dtype == jnp.float32 and g.shape == (D,)
    # f32: accumulation order only.  bf16 storage: mm_f32 rounds the vector
    # operand of each product (w, then mask * r) to bf16, 2^-9 relative
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    err = np.linalg.norm(np.asarray(g, np.float64) - ref)
    assert err <= tol * np.linalg.norm(ref), (err, np.linalg.norm(ref))
    # one row more or less than the mask's would show: a sampled row of
    # middling weight is far above the error
    if dtype == jnp.float32:
        assert err < 1e-2 * np.median(row_weights)


@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_rounds_draw_and_sum_what_the_engine_step_does(dtype, loss):
    """One worker, one round, ``gamma`` chosen so that the round's update is
    ``w - 1.0 * g``: the fused scan's snapshot then shows its
    ``one_gradient``.  Same key chain bit for bit; the same sum up to the
    order XLA adds it in inside a scan (the definition is shared:
    next test)."""
    batch_rate = 0.1
    X, y, w = _problem(dtype, loss, seed=3)
    key = jax.random.PRNGKey(99)
    g, new_key = steps.make_asgd_worker_step(batch_rate, loss)(X, y, w, key)

    par_recs = batch_rate * N  # one worker
    rounds = steps.make_fused_asgd_rounds(
        gamma=par_recs, batch_rate=batch_rate, n=N, shards=[(X, y)],
        loss=loss, rounds_per_call=1,
    )
    w2, k2, keys2, W_snap = rounds(w, jnp.float32(0.0), key[None, :])
    assert np.array_equal(np.asarray(keys2[0]), np.asarray(new_key))
    assert float(k2) == 1.0
    g_fused = np.asarray(w) - np.asarray(W_snap[0])
    scale = np.linalg.norm(np.asarray(g))
    assert np.linalg.norm(g_fused - np.asarray(g)) <= 1e-5 * scale
    assert np.array_equal(np.asarray(w2), np.asarray(W_snap[0]))


def test_engine_step_and_fused_rounds_share_one_definition(monkeypatch):
    """Both builders trace ``steps._dense_sampled_gradient`` and nothing
    else for a dense shard, and the engine step IS that function jitted:
    bit for bit on the same shard and key."""
    calls = []
    real = steps._dense_sampled_gradient

    def spy(X, y, w, key, batch_rate, grad_sum):
        calls.append((X.shape, batch_rate, grad_sum))
        return real(X, y, w, key, batch_rate, grad_sum)

    monkeypatch.setattr(steps, "_dense_sampled_gradient", spy)
    X, y, w = _problem(jnp.float32, "least_squares", seed=5)
    key = jax.random.PRNGKey(5)
    g, new_key = steps.make_asgd_worker_step(0.3)(X, y, w, key)
    assert calls == [((N, D), 0.3, steps.least_squares_grad_sum)]
    half = N // 2
    rounds = steps.make_fused_asgd_rounds(
        gamma=1.0, batch_rate=0.3, n=N,
        shards=[(X[:half], y[:half]), (X[half:], y[half:])],
        rounds_per_call=2,
    )
    rounds(w, jnp.float32(0.0), jnp.stack([key, key]))
    # the scan body is traced once: one call a shard
    assert calls[1:] == [((half, D), 0.3, steps.least_squares_grad_sum)] * 2

    g1, k1 = jax.jit(real, static_argnums=(4, 5))(
        X, y, w, key, 0.3, steps.least_squares_grad_sum
    )
    assert np.array_equal(np.asarray(g1), np.asarray(g))
    assert np.array_equal(np.asarray(k1), np.asarray(new_key))


@pytest.mark.parametrize("batch_rate", [0.05, 0.5, 0.9])
def test_the_rate_does_not_change_the_program(batch_rate):
    """No gather, no scatter, no row packing at any rate: two products over
    the whole shard, the mask folded into the second."""
    X, y, w = _problem(jnp.float32, "least_squares")
    key = jax.random.PRNGKey(0)

    def prims(b):
        jaxpr = jax.make_jaxpr(steps.make_asgd_worker_step(b))(X, y, w, key)
        out = []

        def walk(jp):
            for eqn in jp.eqns:
                out.append(eqn.primitive.name)
                for v in eqn.params.values():
                    if hasattr(v, "jaxpr"):
                        walk(v.jaxpr)
        walk(jaxpr.jaxpr)
        return out

    names = prims(batch_rate)
    assert names == prims(0.1)
    # X w over the main row block and over the tail, then X^T (mask * r)
    assert names.count("dot_general") == 3
    assert not [p for p in names
                if "gather" in p or "scatter" in p or p in ("sort", "cumsum")]


@pytest.mark.parametrize(
    "build,compacts",
    [
        (lambda b: steps.make_asgd_worker_step(b), False),
        (lambda b: steps.make_asgd_worker_step(b, "logistic"), False),
        (lambda b: steps.make_saga_worker_step(b), False),
        (lambda b: steps.make_sparse_asgd_worker_step(b, 64), True),
        (lambda b: steps.make_sparse_saga_worker_step(b, 64), True),
    ],
    ids=["asgd", "asgd-logistic", "saga", "sparse-asgd", "sparse-saga"],
)
@pytest.mark.parametrize("batch_rate", [0.1, 0.9])
def test_the_step_says_how_many_rows_its_products_run_over(
    build, compacts, batch_rate
):
    """``task_rows`` is what the solvers' flop accounting reads
    (``FlopsAccountingMixin._task_flops``): the whole shard for every dense
    step at every rate, the static capacity for the compacting sparse ones."""
    step = build(batch_rate)
    want = (steps.sparse_step_capacity(batch_rate, 4096) if compacts
            else 4096)
    assert step.task_rows(4096) == want


@pytest.mark.parametrize("n", [100, 2048, 4096, 4097, 5000, 10000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_shard_matvec_is_the_plain_product_row_by_row(dtype, n):
    """The split at a multiple of 2,048 rows is for the TPU compiler's
    tiling only: every row's sum is the one ``mm_f32`` gives."""
    rs = np.random.default_rng(n)
    X = jnp.asarray(rs.normal(size=(n, D)), dtype)
    w = jnp.asarray(rs.normal(size=D), jnp.float32)
    got = gradients.shard_matvec(X, w)
    assert got.shape == (n,) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(gradients.mm_f32(X, w)),
        rtol=1e-6, atol=1e-6,
    )
    split = n > 2048 and n % 2048 != 0
    jaxpr = str(jax.make_jaxpr(gradients.shard_matvec)(X, w))
    assert jaxpr.count("dot_general") == (2 if split else 1)
