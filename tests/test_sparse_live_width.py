"""A padded-ELL shard is read at its LIVE width (ISSUE 38).

``data/sparse.py`` pads a row to a multiple of 8 slots and packs its values
to the left, so the ELL columns from the shard's live width on (the most
slots any row fills: kdd2012's 11 of 16, criteo's 39 of 40) hold ``col=0,
val=0.0`` in EVERY row.  The builders record that integer where they pack
the rows, the dataset holds the maximum over its shards, and the sparse
programs (the ASGD and ASAGA steps, the fused rounds, the blocked
evaluation) are BUILT with it and read the stored array's first
``live_width`` ELL columns: a dead slot's product is ``0.0 * w[0]`` added
into ``g[0]``, so leaving it out changes no value, and the v5e pays a
gather, a sort and a scatter-add by the slot.  What the TPU's compiled
program looks like (the prefix is a ``bitcast`` of a shard stored rows
minor: no copy) is ``tests/test_step_layout.py``'s.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.data.sparse import SparseShardedDataset
from asyncframework_tpu.ops import gradients, steps
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig


def _generated(nnz, n=1_003, d=512, workers=2, **kw):
    return SparseShardedDataset.generate_on_device(
        n, d, nnz, workers, jax.devices()[:1], seed=3, noise=0.01, **kw)


def _ragged_csr(row_nnz, d=64, seed=0):
    """CSR arrays of rows with the given numbers of values."""
    rs = np.random.default_rng(seed)
    row_nnz = np.asarray(row_nnz)
    indptr = np.concatenate([[0], np.cumsum(row_nnz)])
    total = int(indptr[-1])
    indices = rs.integers(0, d, total).astype(np.int32)
    values = rs.standard_normal(total).astype(np.float32) + 3.0  # none 0
    y = rs.standard_normal(len(row_nnz)).astype(np.float32)
    return indptr, indices, values, y


# ------------------------------------------------- (a) the integer is right
@pytest.mark.parametrize(
    "build,stored,live",
    [
        (lambda: _generated(11), [16, 16], 11),   # kdd2012's row
        (lambda: _generated(39), [40, 40], 39),   # criteo's
        (lambda: _generated(8), [8, 8], 8),       # nothing padded
        (lambda: SparseShardedDataset(            # the densest row holds 13
            *_ragged_csr([3, 13, 1, 0, 7, 2]), d=64, num_workers=1,
            devices=jax.devices()[:1]), [16], 13),
        (lambda: SparseShardedDataset(            # sorted by row nnz: shard
            *_ragged_csr([1, 2, 3, 2, 20, 1, 19, 3]), d=64, num_workers=2,
            devices=jax.devices()[:1], nnz_partition=True), [8, 24], 20),
    ],
    ids=["generated-11", "generated-39", "generated-8", "csr-ragged-13",
         "csr-nnz-partition"],
)
def test_both_builders_record_the_live_width(build, stored, live):
    """The shard's: the most slots any of its rows fills; the dataset's:
    the maximum over its shards, ONE integer; and every slot at or beyond
    a shard's own live width is padding, in every row."""
    ds = build()
    assert [ds.shard(w).cols.shape[1] for w in range(ds.num_workers)] == stored
    assert ds.live_width == live == ds.checked_live_width()
    for w in range(ds.num_workers):
        s = ds.shard(w)
        filled = np.count_nonzero(np.asarray(s.vals), axis=1)
        assert s.live_width == (filled.max() if len(filled) else 1)
        assert s.live_width <= s.vals.shape[1]
        assert not np.asarray(s.vals)[:, s.live_width:].any()
        assert not np.asarray(s.cols)[:, s.live_width:].any()
    if len(set(stored)) > 1:  # nnz_partition: the light shard is narrower
        assert ds.shard(0).live_width == 2 < live


def test_a_rehomed_shard_keeps_its_live_width():
    from asyncframework_tpu.engine.recovery import ShardRecovery

    ds = _generated(11)
    rec = ShardRecovery(ds, jax.devices()[:1])
    moved = rec.move_shard(1, 0)
    assert moved.live_width == 11 and moved.size == ds.shard(1).size
    assert np.array_equal(np.asarray(moved.cols), np.asarray(ds.shard(1).cols))


# ---------------------------------- (b) the steps: same gradient, same key
def _shard_model_key(nnz=11, d=512, seed=5, **kw):
    ds = _generated(nnz, d=d, workers=1, **kw)
    s = ds.shard(0)
    w = jnp.asarray(np.random.default_rng(seed).standard_normal(d),
                    jnp.float32)
    return ds, s, w, jax.random.PRNGKey(seed)


@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
def test_the_asgd_step_at_the_live_width_gives_the_stored_widths_gradient(
        loss):
    """On the element-wise path (the CPU's) a dead slot adds ``+0.0`` after
    a row's live terms and into ``g[0]``: the margins, and so every
    coefficient and every product, are the stored width's to the bit."""
    ds, s, w, key = _shard_model_key()
    assert (s.cols.shape[1], ds.live_width) == (16, 11)
    at_stored = steps.make_sparse_asgd_worker_step(0.2, ds.d, loss)
    at_live = steps.make_sparse_asgd_worker_step(
        0.2, ds.d, loss, live_width=ds.live_width)
    g0, k0 = at_stored(s.cols, s.vals, s.y, w, key)
    g1, k1 = at_live(s.cols, s.vals, s.y, w, key)
    assert np.array_equal(np.asarray(k0), np.asarray(k1))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-6,
                               atol=1e-6 * float(jnp.max(jnp.abs(g0))))
    if loss == "least_squares":
        assert np.array_equal(np.asarray(g1), np.asarray(g0))
    assert float(jnp.max(jnp.abs(g0))) > 0
    # a live width at or over the stored one is the stored width
    wide = steps.make_sparse_asgd_worker_step(0.2, ds.d, loss, live_width=16)
    g2, _ = wide(s.cols, s.vals, s.y, w, key)
    assert np.array_equal(np.asarray(g2), np.asarray(g0))


def test_the_asaga_step_at_the_live_width_gives_the_stored_widths_outputs():
    """The ASAGA core shares the sample and the read: ``g``, the candidate
    scalars, the packed ids and ``valid`` are the stored width's, and the
    sample that rides to the table delta is ``(capacity, live_width)``,
    the stored one's first columns."""
    ds, s, w, key = _shard_model_key()
    alpha = jnp.asarray(
        np.random.default_rng(9).standard_normal(s.size), jnp.float32)
    out0 = steps.make_sparse_saga_worker_step(0.2, ds.d)(
        s.cols, s.vals, s.y, w, alpha, key)
    out1 = steps.make_sparse_saga_worker_step(0.2, ds.d, live_width=11)(
        s.cols, s.vals, s.y, w, alpha, key)
    g0, diff0, idx0, valid0, c0, v0, k0 = out0
    g1, diff1, idx1, valid1, c1, v1, k1 = out1
    cap = steps.sparse_step_capacity(0.2, s.size)
    assert c0.shape == (cap, 16) and c1.shape == v1.shape == (cap, 11)
    for a, b in ((g0, g1), (diff0, diff1), (idx0, idx1), (valid0, valid1),
                 (c0[:, :11], c1), (v0[:, :11], v1), (k0, k1)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the exact table delta scatter-adds what the step hands it
    delta = steps.make_sparse_table_delta(ds.d)
    d0 = delta(c0, v0, diff0, alpha, idx0)
    d1 = delta(c1, v1, diff1, alpha, idx1)
    assert np.array_equal(np.asarray(d0), np.asarray(d1))


@pytest.mark.parametrize("solver", ["asgd", "asaga"])
def test_the_fused_rounds_read_the_live_width_and_stay_the_engine_steps(
        solver, monkeypatch):
    """Both builders trace the ONE core with the same live width (the
    fused path's sampling-parity claim), and one fused round of one worker
    is the engine step's gradient."""
    ds, s, w, key = _shard_model_key()
    core = ("_sparse_compacted_gradient" if solver == "asgd"
            else "_sparse_saga_compacted")
    widths = []
    real = getattr(steps, core)

    def spy(*args, **kwargs):
        bound = dict(zip(real.__code__.co_varnames, args), **kwargs)
        widths.append(bound.get("live_width"))
        return real(*args, **kwargs)

    monkeypatch.setattr(steps, core, spy)
    batch_rate = 0.2
    par_recs = batch_rate * s.size  # one worker: w' = w - 1.0 * g
    shards = [(s.cols, s.vals, s.y)]
    if solver == "asgd":
        g, new_key = steps.make_sparse_asgd_worker_step(
            batch_rate, ds.d, live_width=11)(s.cols, s.vals, s.y, w, key)
        rounds = steps.make_fused_asgd_rounds(
            par_recs, batch_rate, s.size, shards, rounds_per_call=1,
            sparse_d=ds.d, live_width=11)
        _w2, _k2, keys2, W_snap = rounds(w, jnp.float32(0.0), key[None, :])
        g_fused = np.asarray(w) - np.asarray(W_snap[0])
    else:
        alpha = jnp.zeros(s.size, jnp.float32)
        g, diff, idx, valid, _c, _v, new_key = (
            steps.make_sparse_saga_worker_step(batch_rate, ds.d,
                                               live_width=11)(
                s.cols, s.vals, s.y, w, alpha, key))
        rounds = steps.make_fused_saga_rounds(
            par_recs, batch_rate, s.size, shards, rounds_per_call=1,
            sparse_d=ds.d, live_width=11)
        _w2, _ab2, alphas2, keys2, W_snap = rounds(
            w, jnp.zeros(ds.d, jnp.float32), (alpha,), key[None, :])
        g_fused = np.asarray(w) - np.asarray(W_snap[0])
        committed = steps.make_sparse_saga_commit()(alpha, diff, idx, valid)
        assert np.array_equal(np.asarray(alphas2[0]), np.asarray(committed))
    assert widths == [11, 11]
    assert np.array_equal(np.asarray(keys2[0]), np.asarray(new_key))
    scale = float(np.linalg.norm(np.asarray(g)))
    assert np.linalg.norm(g_fused - np.asarray(g)) <= 1e-5 * scale


def test_fused_runs_of_a_solver_are_the_same_at_both_widths():
    """``run_fused`` hands the dataset's live width to the fused rounds:
    the run's final model is the one the stored width gives, to the bit
    (least squares on the element-wise path)."""
    cfg = SolverConfig(num_workers=2, num_iterations=8, gamma=0.5,
                       taw=2**31 - 1, batch_rate=0.3, bucket_ratio=1.0,
                       printer_freq=4, seed=5, calibration_iters=4,
                       run_timeout_s=60.0)
    ds = _generated(11)
    res = ASGD(ds, None, cfg, devices=jax.devices()[:1]).run_fused()
    # the same arrays in a dataset that says nothing is padding
    full = _generated(11)
    full.shards = {w: dataclasses.replace(s, live_width=16)
                   for w, s in ds.shards.items()}
    assert full.live_width == 16
    res_full = ASGD(full, None, cfg, devices=jax.devices()[:1]).run_fused()
    assert np.array_equal(res.final_w, res_full.final_w)
    assert res.accepted == res_full.accepted == 8


# ------------------------------------------- (c) the blocked evaluation
@pytest.mark.parametrize("snapshots", [8, 9])
@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
def test_the_evaluation_at_the_live_width_gives_the_stored_widths_sums(
        snapshots, loss, monkeypatch):
    monkeypatch.setattr(steps, "SPARSE_EVAL_BLOCK_ROWS", 256)  # ragged last
    ds, s, _w, _key = _shard_model_key()
    W = jnp.asarray(np.random.default_rng(2).standard_normal(
        (snapshots, ds.d)), jnp.float32).at[0].set(0.0)
    at_stored = steps.make_sparse_trajectory_loss_eval(loss)
    at_live = steps.make_sparse_trajectory_loss_eval(loss, live_width=11)
    assert (at_stored.width(16), at_live.width(16), at_live.width(8)) == (
        16, 11, 8)
    assert at_live.blocks(s.size) == at_stored.blocks(s.size) == 4
    a, b = at_stored(s.cols, s.vals, s.y, W), at_live(s.cols, s.vals, s.y, W)
    assert a.shape == b.shape == (snapshots,)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6)


# ------------------------- (d) the program's shapes: the mechanism engaged
def _ops(text, op):
    """The lines of a lowered program that hold ``stablehlo.<op>``."""
    return [ln.strip() for ln in text.splitlines()
            if f"stablehlo.{op}" in ln]


def _scatter_types(text):
    """``(operand, indices, updates) -> result`` of every scatter (the
    types close its region, lines below the op)."""
    return re.findall(
        r"\}\) : (\(tensor<\w+>, tensor<\w+>, tensor<\w+>\) -> tensor<\w+>)",
        text)


def _prefix_views(text, n_p, K, L):
    """The lowered program's ``(n_p, L)`` arrays: each the static slice
    ``[0:n_p, 0:L]`` of a stored ``(n_p, K)`` argument (on the TPU, where
    the shard is stored rows minor, a ``bitcast``), and nothing else."""
    made = [ln for ln in text.splitlines()
            if re.search(r"-> tensor<%dx%dx\w+>$" % (n_p, L), ln)]
    for ln in made:
        assert re.search(
            r"stablehlo\.slice %%arg\d+ \[0:%d, 0:%d\] : "
            r"\(tensor<%dx%dx" % (n_p, L, n_p, K), ln), ln
    return made


def test_the_lowered_step_of_a_kdd2012_shaped_shard_holds_live_slots_only():
    """Width 16, live 11 (kdd2012's row): the step gathers ``(capacity,
    11)`` columns and values from the first 11 ELL columns of the stored
    ``(n_p, 16)`` arrays (a prefix view of each argument is all it makes of
    the shard's height) and scatter-adds ``capacity * 11`` updates.  With
    the mechanism off it gathers ``(capacity, 16)`` and scatters ``capacity
    * 16``: the second half of this test."""
    n_p, K, L, d = 20_000, 16, 11, 4_096
    cap = steps.sparse_step_capacity(0.05, n_p)
    S = jax.ShapeDtypeStruct
    specs = (S((n_p, K), jnp.int32), S((n_p, K), jnp.float32),
             S((n_p,), jnp.float32), S((d,), jnp.float32),
             S((2,), jnp.uint32))

    def program(live_width):
        step = steps.make_sparse_asgd_worker_step(
            0.05, d, "logistic", live_width=live_width)
        return step.lower(*specs).as_text()

    text = program(L)
    assert len(_prefix_views(text, n_p, K, L)) == 2  # cols and vals
    row_gathers = [g for g in _ops(text, "gather")
                   if f"(tensor<{n_p}x{L}x" in g]
    assert len(row_gathers) == 2, _ops(text, "gather")
    for g in row_gathers:
        assert f"-> tensor<{cap}x{L}x" in g, g
    assert len(_ops(text, "scatter")) == 1
    assert _scatter_types(text) == [
        f"(tensor<{d}xf32>, tensor<{cap * L}x1xi32>, "
        f"tensor<{cap * L}xf32>) -> tensor<{d}xf32>"]
    assert f"tensor<{cap * K}x" not in text and f"tensor<{cap}x{K}x" not in text

    off = program(None)
    assert not _prefix_views(off, n_p, K, L)
    assert f"-> tensor<{cap}x{K}x" in "".join(_ops(off, "gather"))
    assert f"tensor<{cap * K}xf32>) -> tensor<{d}xf32>" in _scatter_types(
        off)[0]
    assert f"tensor<{cap * L}x" not in off


def test_the_lowered_evaluation_walks_live_blocks_of_the_stored_shard(
        monkeypatch):
    monkeypatch.setattr(steps, "SPARSE_EVAL_BLOCK_ROWS", 4_096)
    n_p, K, L, d = 20_000, 16, 11, 4_096
    S = jax.ShapeDtypeStruct
    ev = steps.make_sparse_trajectory_loss_eval("logistic", live_width=L)
    text = ev.lower(S((n_p, K), jnp.int32), S((n_p, K), jnp.float32),
                    S((n_p,), jnp.float32), S((8, d), jnp.float32)).as_text()
    assert len(_prefix_views(text, n_p, K, L)) == 2
    rows = ev.block_rows(n_p)
    assert rows == 4_096
    blocks = [ln for ln in _ops(text, "dynamic_slice")
              if f"-> tensor<{rows}x{L}x" in ln]
    assert len(blocks) == 2, _ops(text, "dynamic_slice")
    assert f"tensor<{rows}x{K}x" not in text
    # eight snapshots an index over the live slots of a block only
    assert f"tensor<8x{L}x{rows}xf32>" in "".join(_ops(text, "gather"))


# ---------------------------------- the gathers' blocks at an odd width
@pytest.mark.parametrize(
    "slots,width,rows",
    [
        (327_680, 40, 8_192),   # criteo as stored: as the constant divides
        (327_680, 39, 8_320),   # its live width: 8,402 -> 65 tiles of 128
        (8_192, 16, 512),       # kdd2012 as stored
        (8_192, 11, 640),       # its live width: 744 -> five tiles
        (16_384, 11, 1_408),
        (2_048, 16, 128),
        (1_100, 11, 100),       # under one tile: as it divides
        (64, 8, 8),
    ],
)
def test_a_block_holds_whole_tiles_of_128_rows(slots, width, rows):
    assert gradients._block_rows(slots, width) == rows
    assert rows * width <= slots


@pytest.mark.parametrize("form", ["rows8", "lanes128"])
def test_blocked_margins_at_an_odd_width_are_the_element_wise_ones(
        form, monkeypatch):
    """Width 11 against a block of 3,000 slots: 272 rows rounded to two
    tiles of 128, five blocks over 1,100 rows with a clamped last one."""
    const = {"rows8": "SPARSE_GATHER_BLOCK_SLOTS",
             "lanes128": "SPARSE_LANES_BLOCK_SLOTS"}[form]
    monkeypatch.setattr(gradients, const, 3_000)
    assert gradients._block_rows(3_000, 11) == 256
    rs = np.random.default_rng(4)
    d, rows = 4_096, 1_100
    c = jnp.asarray(rs.integers(0, d, (rows, 11)).astype(np.int32))
    v = jnp.asarray(rs.standard_normal((rows, 11)).astype(np.float32))
    w = jnp.asarray(rs.standard_normal(d).astype(np.float32))
    blocked = jax.jit(getattr(gradients, "_margins_" + form))(c, v, w)
    plain = gradients._margins_elements(c, v, w)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(plain),
                               rtol=0, atol=1e-5)
    text = str(jax.make_jaxpr(getattr(gradients, "_margins_" + form))(c, v, w))
    assert "256,11" in text.replace(" ", "") or "11,256" in text.replace(" ", "")


# --------------------------------------------- (e) what a run says of itself
def _cfg(**kw):
    base = dict(
        num_workers=2, num_iterations=10, gamma=0.5, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.7, printer_freq=4, seed=5,
        calibration_iters=4, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


def test_an_asgd_run_counts_stored_and_live_slots():
    ds = _generated(11, n=2_001)
    res = ASGD(ds, None, _cfg(), devices=jax.devices()[:1]).run()
    ex = res.extras
    cap = steps.sparse_step_capacity(0.3, 1_001)
    assert ex["sparse_step_capacity"] == cap
    assert ex["sparse_live_width"] == 11
    assert ex["live_slots_per_step"] == cap * 11
    assert ex["sampled_slots_per_step"] == cap * 16  # STORED: the benchmark's
    # every row once a call, at both widths (one block a shard)
    calls = ex["eval_calls"]
    assert ex["eval_slots"] == calls * 2_001 * 16
    assert ex["eval_live_slots"] == calls * 2_001 * 11


def test_a_run_with_nothing_padded_did_not_engage_the_mechanism():
    ds = _generated(8, n=2_001)
    ex = ASGD(ds, None, _cfg(), devices=jax.devices()[:1]).run().extras
    assert ex["sparse_live_width"] == 8
    assert ex["live_slots_per_step"] == ex["sampled_slots_per_step"]
    assert ex["eval_live_slots"] == ex["eval_slots"]


def test_an_asaga_run_reads_the_live_width_too():
    ds = _generated(11, n=2_001)
    solver = ASAGA(ds, None, _cfg(gamma=0.05), devices=jax.devices()[:1])
    res = solver.run()
    assert res.accepted == 10
    assert res.extras["sparse_live_width"] == 11
    assert res.extras["eval_live_slots"] * 16 == res.extras["eval_slots"] * 11


def test_the_chooser_is_asked_about_the_live_sample(monkeypatch):
    """``sparse_gather_path`` sees ``(capacity, live_width)``: the slots
    its thresholds count are the ones the step gathers."""
    seen = []
    real = gradients.sparse_gather_path

    def chooser(w, c_sel):
        seen.append(tuple(c_sel.shape))
        return real(w, c_sel)

    monkeypatch.setattr(gradients, "sparse_gather_path", chooser)
    ds, s, w, key = _shard_model_key()
    steps.make_sparse_asgd_worker_step(0.2, ds.d, live_width=11)(
        s.cols, s.vals, s.y, w, key)
    assert seen == [(steps.sparse_step_capacity(0.2, s.size), 11)]


# ---------------------------------- the guard: no value beyond the live width
@pytest.mark.parametrize("solver", [ASGD, ASAGA], ids=["asgd", "asaga"])
def test_a_shard_with_a_value_beyond_its_live_width_is_refused(solver):
    """A program built with the live width never reads the ELL columns
    beyond it: arrays swapped for ones that hold a value there would lose
    it silently, so the solver that builds its steps from the dataset
    refuses them."""
    ds = _generated(11)
    s = ds.shard(1)
    ds.shards[1] = dataclasses.replace(
        s, vals=s.vals.at[7, 12].set(0.5), cols=s.cols.at[7, 12].set(3))
    with pytest.raises(ValueError, match="live width 11"):
        solver(ds, None, _cfg(), devices=jax.devices()[:1])
    ds.shards[1] = s
    solver(ds, None, _cfg(), devices=jax.devices()[:1])  # sound again


@pytest.mark.parametrize("row_nnz", [[3, 13, 1], [0, 0], [8, 8, 8, 8]])
def test_the_csr_constructor_packs_no_row_beyond_the_width_it_records(
        row_nnz):
    ds = SparseShardedDataset(*_ragged_csr(row_nnz), d=64, num_workers=1,
                              devices=jax.devices()[:1])
    s = ds.shard(0)
    assert s.live_width == max(1, max(row_nnz))
    assert np.count_nonzero(np.asarray(s.vals)) == sum(row_nnz)
    assert ds.checked_live_width() == s.live_width
