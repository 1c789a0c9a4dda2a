"""How the sparse step prepares its indices (ISSUE 33): the gradient's
scatter-add takes the slots in the order they are stored, and the sampled
row ids are packed by one sort of the mask.

``make_sparse_grad_sum`` against the dense ``X.T @ coeff`` on the cases an
unsorted scatter-add could get wrong (many slots on one column, padding
slots, a column id out of range), and ``steps._pack_rows`` against
``jnp.nonzero(mask, size=cap, fill_value=0)`` TO THE BIT, so that the same
key samples the same rows as it did while ``jnp.nonzero`` packed them: the
engine step, the fused rounds and every sampling-parity test rest on it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.ops import gradients, pallas_kernels, steps

ROWS, K, D = 96, 8, 40


def _ell(case: str):
    """``(cols, vals, dense X)`` of a seeded ``ROWS x K`` padded-ELL block
    over ``D`` columns; ``X`` adds up what each row stores a column."""
    rs = np.random.default_rng(11)
    cols = rs.integers(0, D, (ROWS, K)).astype(np.int32)
    vals = rs.standard_normal((ROWS, K)).astype(np.float32)
    if case == "colliding":  # half of ALL slots on one column
        cols[:, : K // 2] = 17
    elif case == "padding":  # the generator's padding: column 0, value 0
        cols[:, K - 3:] = 0
        vals[:, K - 3:] = 0.0
    elif case == "out_of_range":  # dropped, not wrapped and not clipped
        cols[::3, 1] = D
        cols[1::3, 2] = D + 12345
    X = np.zeros((ROWS, D), np.float64)
    kept = cols < D
    np.add.at(X, (np.nonzero(kept)[0], cols[kept]), vals[kept])
    return cols, vals, X


@pytest.mark.parametrize(
    "case", ["random", "colliding", "padding", "out_of_range"])
def test_grad_sum_is_the_dense_product_in_any_slot_order(case):
    cols, vals, X = _ell(case)
    coeff = np.random.default_rng(3).standard_normal(ROWS).astype(np.float32)
    g = np.asarray(gradients.make_sparse_grad_sum(D)(cols, vals, coeff))
    assert g.shape == (D,) and g.dtype == np.float32
    want = X.T @ coeff.astype(np.float64)
    assert np.max(np.abs(g - want)) < 1e-5 * np.max(np.abs(want))


def _segment_case(case: str):
    """``(cols, vals, dense X, d)``: the four blocks of :func:`_ell`, and
    three shaped for the sorted-segment kernel at tiles of 1,024 columns
    and groups of 1,024 slots (ISSUE 52)."""
    if case in ("random", "colliding", "padding", "out_of_range"):
        return _ell(case) + (D,)
    rs = np.random.default_rng(52)
    rows, k = 512, 8
    if case == "long_column":  # one column's run spans more than two groups
        d = 3_000
        cols = rs.integers(0, d, (rows, k)).astype(np.int32)
        cols[:, :5] = 1_500  # 2,560 of the 4,096 slots
    elif case == "empty_tiles":  # tiles 1, 2 and 4 of five hold no slot
        d = 5 * 1_024
        cols = np.where(rs.random((rows, k)) < 0.5,
                        rs.integers(0, 1_024, (rows, k)),
                        rs.integers(3 * 1_024, 4 * 1_024, (rows, k))
                        ).astype(np.int32)
    elif case == "ragged_width":  # d % T != 0; a negative id counts back
        d = 2_500
        cols = rs.integers(0, d, (rows, k)).astype(np.int32)
        cols[::7, 0] -= d
        cols[3::7, 1] = -d - 1  # still outside: dropped
    vals = rs.standard_normal((rows, k)).astype(np.float32)
    X = np.zeros((rows, d), np.float64)
    at = np.where(cols < 0, cols + d, cols)
    kept = (at >= 0) & (at < d)
    np.add.at(X, (np.nonzero(kept)[0], at[kept]), vals[kept])
    return cols, vals, X, d


@pytest.mark.parametrize(
    "case", ["random", "colliding", "padding", "out_of_range",
             "long_column", "empty_tiles", "ragged_width"])
def test_segment_tiles_sum_is_the_dense_product(case):
    """``pallas_kernels.segment_tiles_sum`` (interpreted: the CPU) against
    ``X.T @ coeff`` in float64, and against the scatter-add it replaces on
    a TPU: the same slots kept and dropped, every product float32."""
    cols, vals, X, d = _segment_case(case)
    coeff = np.random.default_rng(3).standard_normal(
        cols.shape[0]).astype(np.float32)
    products = (vals * coeff[:, None]).ravel()
    g = np.asarray(pallas_kernels.segment_tiles_sum(
        jnp.asarray(cols.ravel()), jnp.asarray(products), d,
        tile=1_024, block_rows=8, interpret=True))
    assert g.shape == (d,) and g.dtype == np.float32
    want = X.T @ coeff.astype(np.float64)
    assert np.max(np.abs(g - want)) < 2e-6 * np.max(np.abs(want))
    scattered = np.asarray(jnp.zeros(d, jnp.float32).at[
        jnp.asarray(cols.ravel())].add(jnp.asarray(products), mode="drop"))
    assert np.max(np.abs(g - scattered)) < 1e-5 * np.max(np.abs(want))
    np.testing.assert_array_equal(g == 0, scattered == 0)


def test_a_product_is_its_three_bf16_parts_exactly():
    """What makes the MXU's sum a float32 sum: each part is a bfloat16
    value to the bit, and the three add up to the float32 product, over
    twenty orders of magnitude and both signs."""
    rs = np.random.default_rng(8)
    p = (rs.standard_normal(4_096) * 10.0 ** rs.integers(-10, 10, 4_096)
         ).astype(np.float32)
    parts = [np.asarray(x) for x in
             pallas_kernels._bf16_parts(jnp.asarray(p))]
    for part in parts:
        assert part.dtype == np.float32
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(part).astype(jnp.bfloat16).astype(
                jnp.float32)), part)
    total = sum(part.astype(np.float64) for part in parts)
    np.testing.assert_array_equal(total, p.astype(np.float64))


def test_segment_tiles_sum_refuses_a_tile_it_cannot_store():
    with pytest.raises(ValueError):
        pallas_kernels.segment_tiles_sum(
            jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.float32), D, tile=256)


def test_grad_sum_takes_the_slots_as_they_are_stored():
    """The CPU's program (ISSUE 33; on a TPU ``sparse_scatter_path`` may
    choose the sorted segments, ISSUE 52): the lowered program holds a
    scatter and no sort; nothing carries the slots into another order
    first."""
    text = gradients.make_sparse_grad_sum(D).lower(
        jnp.zeros((ROWS, K), jnp.int32), jnp.zeros((ROWS, K), jnp.float32),
        jnp.zeros(ROWS, jnp.float32)).as_text()
    assert "stablehlo.scatter" in text
    assert "stablehlo.sort" not in text and "stablehlo.gather" not in text
    assert "indices_are_sorted = false" in text


# ------------------------------------------------------------- the packing

def _mask(kind: str, n: int, cap: int):
    m = np.zeros(n, bool)
    rs = np.random.default_rng(n + cap)
    if kind == "full":
        m[:] = True
    elif kind in ("at_cap", "one_over_cap", "under_cap"):
        count = {"at_cap": cap, "one_over_cap": cap + 1,
                 "under_cap": cap - 1}[kind]
        m[rs.choice(n, count, replace=False)] = True
    elif kind == "random":
        m = rs.random(n) < 0.3
    elif kind == "last_row_only":
        m[-1] = True
    return m


_KINDS = ["empty", "full", "at_cap", "one_over_cap", "under_cap", "random",
          "last_row_only"]


@pytest.mark.parametrize(
    "kind,n,cap",
    [(kind, n, cap) for n, cap in [(200, 64), (1003, 8), (64, 64)]
     for kind in _KINDS
     if not (kind == "one_over_cap" and cap == n)])  # at most n rows are set
def test_packed_rows_are_nonzeros_to_the_bit(kind, n, cap):
    mask = jnp.asarray(_mask(kind, n, cap))
    idx, valid = jax.jit(steps._pack_rows, static_argnums=1)(mask, cap)
    (want,) = jnp.nonzero(mask, size=cap, fill_value=0)
    assert idx.dtype == want.dtype and idx.shape == want.shape == (cap,)
    assert np.asarray(idx).tobytes() == np.asarray(want).tobytes()
    # ``valid`` as the parent computed it: the filled slots, by the count
    count = int(np.asarray(mask).sum())
    np.testing.assert_array_equal(np.asarray(valid), np.arange(cap) < count)
    assert valid.dtype == jnp.bool_
    # ascending over the filled slots: what the commit's sorted scatter needs
    filled = np.asarray(idx)[: min(count, cap)]
    assert (np.diff(filled) > 0).all()


def _ell_shard(n):
    rs = np.random.default_rng(n)
    return (jnp.asarray(rs.integers(0, D, (n, K)), jnp.int32),
            jnp.asarray(rs.standard_normal((n, K)), jnp.float32),
            jnp.asarray(rs.standard_normal(n), jnp.float32))


@pytest.mark.parametrize("batch_rate", [0.05, 0.5, 1.0])
def test_the_step_samples_the_rows_nonzero_packed(batch_rate):
    """The same key, the same rows: ``idx`` and ``valid`` of the ASAGA step
    (which returns them) are the Bernoulli draw packed by ``jnp.nonzero``,
    and the ASGD step's gradient is the sum over exactly those rows."""
    n = 777
    cols, vals, y = _ell_shard(n)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(D), jnp.float32)
    key = jax.random.PRNGKey(2_147_483_659 % 1000)
    cap = steps.sparse_step_capacity(batch_rate, n)
    _next, sub = jax.random.split(key)
    mask = jax.random.bernoulli(sub, batch_rate, (n,))
    (want,) = jnp.nonzero(mask, size=cap, fill_value=0)

    saga = steps.make_sparse_saga_worker_step(batch_rate, D)
    g_saga, _diff, idx, valid, _c, _v, next_key = saga(
        cols, vals, y, w, jnp.zeros(n, jnp.float32), key)
    assert np.asarray(idx).tobytes() == np.asarray(want).tobytes()
    count = int(mask.sum())
    np.testing.assert_array_equal(
        np.asarray(valid), (np.arange(cap) < count).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(next_key), np.asarray(_next))

    g, _key = steps.make_sparse_asgd_worker_step(batch_rate, D)(
        cols, vals, y, w, key)
    r = gradients.sparse_residual(cols, vals, y, w) * mask
    want_g = np.asarray(gradients.make_sparse_grad_sum(D)(cols, vals, r))
    assert np.max(np.abs(np.asarray(g) - want_g)) < 1e-5 * np.abs(want_g).max()
    # a zero history table: ASAGA's corrected gradient is ASGD's
    np.testing.assert_array_equal(np.asarray(g_saga), np.asarray(g))


def test_both_cores_and_the_fused_rounds_pack_through_the_one_helper(
        monkeypatch):
    calls = []
    real = steps._pack_rows

    def spy(mask, cap):
        calls.append((mask.shape[0], cap))
        return real(mask, cap)

    monkeypatch.setattr(steps, "_pack_rows", spy)
    monkeypatch.setattr(
        jnp, "nonzero", lambda *a, **k: pytest.fail("jnp.nonzero packs rows"))
    n = 333  # a shape no other test traces
    cols, vals, y = _ell_shard(n)
    w, alpha = jnp.zeros(D, jnp.float32), jnp.zeros(n, jnp.float32)
    key = jax.random.PRNGKey(0)
    cap = steps.sparse_step_capacity(0.25, n)
    steps.make_sparse_asgd_worker_step(0.25, D).lower(cols, vals, y, w, key)
    assert calls == [(n, cap)]
    steps.make_sparse_saga_worker_step(0.25, D).lower(
        cols, vals, y, w, alpha, key)
    assert calls == [(n, cap)] * 2
    shards = [(cols, vals, y)]
    keys = jnp.stack([key])
    steps.make_fused_asgd_rounds(0.1, 0.25, n, shards, rounds_per_call=2,
                                 sparse_d=D).lower(w, jnp.float32(0.0), keys)
    assert calls == [(n, cap)] * 3
    steps.make_fused_saga_rounds(0.1, 0.25, n, shards, rounds_per_call=2,
                                 sparse_d=D).lower(w, w, (alpha,), keys)
    assert calls == [(n, cap)] * 4
