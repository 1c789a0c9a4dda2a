"""Rows of UNEQUAL length on the sparse path (ISSUE 39).

LIBSVM ``webspam``'s rows hold 256 to 16,384 real values around a mean of
3,728.  ``data/sparse.py`` deals such rows to the shards in order of their
length (a generator given a row-length law, ``row_nnz``, as
``nnz_partition=True`` deals a loaded file), so that a shard is ONE
``(rows, K)`` pair as wide as ITS longest row; ASGD then builds its step and
its evaluation once for every shard SHAPE and reads each shard at its own
live width.  Everything here is held to ``benchmark/reference.py`` (float32
``jax.numpy`` at precision "highest", no program code) on seeded weights.
What the TPU's compiled programs look like at webspam's widths is
``tests/test_step_layout.py``'s.
"""

import glob
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.data import sparse as sparse_mod
from asyncframework_tpu.data.sparse import SparseShardedDataset, _row_lengths
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.ops import gradients, steps
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig
from asyncframework_tpu.solvers import base as solver_base
from benchmark import reference

LAW = {"law": "lognormal", "sigma": 0.6, "min": 8, "max": 384}
VALUES = {"law": "lognormal", "sigma": 0.5}
LABELS = {"scale": 3.0, "positive_share": 0.6}
PAD_BOUND = 1.75  # the cell's: stored slots over non-zeros
D = 40_007  # d % 8 == 7, as webspam's 16,609,143


def _ragged(seed=3, n=4_096, workers=8, loss="logistic", **kw):
    kw = {"column_skew": 0.5, "row_nnz": LAW, "row_values": VALUES, **kw}
    if loss == "logistic":
        kw.setdefault("bernoulli_labels", LABELS)
    return SparseShardedDataset.generate_on_device(
        n, D, 96, workers, jax.devices()[:1], seed=seed, noise=0.0, **kw)


def _shards(ds):
    return [ds.shard(w) for w in range(ds.num_workers)]


def _filled(shard):
    return np.count_nonzero(np.asarray(shard.vals), axis=1)


def _read(shard):
    """The width a program built with ``ds.live_widths`` reads a shard at:
    its live width where it is stored in sublane tiles, all of it where it
    is stored in lane tiles."""
    stored = shard.vals.shape[1]
    return stored if stored % 128 == 0 else shard.live_width


# ------------------------------------------------------- (a) the generator
@pytest.mark.parametrize("seed", [3, 11, 2_147_483_659])
def test_the_generator_holds_its_pins(seed):
    """The mean length is ``nnz_per_row`` after clipping, every length lies
    inside the clip, every row has unit length, the shards hold equal row
    counts (a worker's ``b`` is the same number of rows everywhere), each is
    as wide as ITS longest row and the stored slots stay under the bound."""
    ds = _ragged(seed)
    shards = _shards(ds)
    filled = np.concatenate([_filled(s) for s in shards])
    assert abs(filled.mean() - 96) < 0.01 * 96
    assert LAW["min"] <= filled.min() and filled.max() <= LAW["max"]
    assert [s.size for s in shards] == [512] * 8
    assert [s.vals.shape[0] for s in shards] == [512] * 8
    for s in shards:
        rows = _filled(s)
        assert s.live_width == rows.max() and s.nnz == rows.sum()
        # whole sublane tiles, and whole lane tiles from one lane tile on
        assert s.vals.shape[1] == sparse_mod._round_up(s.live_width)
        assert s.vals.shape[1] % (8 if s.live_width <= 120 else 128) == 0
        v = np.asarray(s.vals, np.float64)
        np.testing.assert_allclose((v * v).sum(axis=1), 1.0, rtol=1e-5)
        # values packed to the left; padding is col 0, val 0
        live = np.arange(v.shape[1])[None, :] < rows[:, None]
        assert not v[~live].any() and not np.asarray(s.cols)[~live].any()
        assert (v[live] > 0).all()  # term frequencies
    # dealt by length: no row of a shard is shorter than one of the shard before
    for a, b in zip(shards, shards[1:]):
        assert _filled(a).max() <= _filled(b).min()
    widths = [s.vals.shape[1] for s in shards]
    assert widths == sorted(widths) and len(set(widths)) >= 5
    # a program reads a lane-tiled shard whole: its shape alone decides it
    assert ds.live_widths == {
        k: max(_read(s) for s in shards if s.vals.shape[1] == k)
        for k in set(widths)}
    assert all(ds.live_widths[k] == k for k in widths if k % 128 == 0)
    assert max(ds.live_widths.values()) >= max(s.live_width for s in shards)
    assert ds.checked_live_widths() == ds.live_widths
    assert ds.padded_nnz() / sum(s.nnz for s in shards) < PAD_BOUND
    pins, f0 = reference.data_pins(shards, "logistic")
    assert pins["row_second_moment"] == pytest.approx(1.0, rel=1e-5)
    assert pins["nnz_per_row"] == pytest.approx(96, rel=0.01)
    assert 0.54 < pins["label_second_moment"] < 0.66
    assert f0 == pytest.approx(np.log(2.0), rel=1e-6)


@pytest.mark.parametrize(
    "kw,digest",
    [
        (dict(n=1_003, d=512, nnz_per_row=11, num_workers=3,
              seed=2_147_483_659, noise=0.01), "40efd5bddd6dbbff"),
        (dict(n=1_003, d=40_004, nnz_per_row=39, num_workers=4, seed=7,
              noise=0.0, column_skew=1.0, unit_values=True,
              bernoulli_labels={"scale": 3.0, "positive_share": 0.25}),
         "9412cc859207e475"),
    ],
    ids=["planted", "click-log"],
)
def test_without_the_new_arguments_the_arrays_are_the_old_ones(kw, digest):
    """Byte for byte: the digests were taken on the parent commit (PR 38),
    whose generator had neither ``row_nnz`` nor ``row_values``."""
    def sha(ds):
        m = hashlib.sha256()
        for s in _shards(ds):
            for a in (s.cols, s.vals, s.y):
                m.update(np.asarray(a).tobytes())
        return m.hexdigest()[:16]

    devs = jax.devices()[:1]
    assert sha(SparseShardedDataset.generate_on_device(
        devices=devs, **kw)) == digest
    assert sha(SparseShardedDataset.generate_on_device(
        devices=devs, row_nnz=None, row_values=None, **kw)) == digest


def test_row_lengths_average_the_mean_after_the_clip():
    law = {"law": "lognormal", "sigma": 0.6, "min": 256, "max": 16_384}
    lengths = _row_lengths(175_000, 3_728, law, seed=5)
    assert abs(lengths.mean() - 3_728) < 0.01  # to a slot in 175,000 rows
    assert lengths.min() >= 256 and lengths.max() == 16_384
    assert 0 < (lengths == 16_384).mean() < 0.01  # the clip holds few rows
    assert np.array_equal(lengths, _row_lengths(175_000, 3_728, law, seed=5))
    assert not np.array_equal(lengths, _row_lengths(175_000, 3_728, law, 6))
    # eight classes of equal row count, each its longest row in lane tiles
    top = np.sort(lengths)[21_875 - 1::21_875]
    stored = sum(sparse_mod._round_up(int(k)) for k in top) * 21_875
    assert 1.3 < stored / lengths.sum() < 1.45
    with pytest.raises(ValueError, match="cannot average"):
        _row_lengths(100, 500, dict(law, max=400), seed=1)
    with pytest.raises(ValueError, match="unknown row-length law"):
        _row_lengths(100, 500, dict(law, law="zipf"), seed=1)
    with pytest.raises(ValueError, match="row_values"):
        _ragged(unit_values=True)


def test_the_other_value_laws_go_by_the_rows_own_length():
    unit = _ragged(row_values=None, unit_values=True)
    for s in (unit.shard(0), unit.shard(7)):
        v = np.asarray(s.vals)
        rows = _filled(s)
        np.testing.assert_allclose(
            v[:, 0], rows.astype(np.float64) ** -0.5, rtol=1e-6)
        np.testing.assert_allclose((v * v).sum(axis=1), 1.0, rtol=1e-5)
    planted = _ragged(row_values=None, loss="least_squares")
    pins, _f0 = reference.data_pins(_shards(planted), "least_squares")
    assert pins["row_second_moment"] == pytest.approx(1.0, rel=0.02)


def test_a_loaded_file_dealt_by_length_gets_the_generators_widths():
    """The same rows as CSR, in the file's own order, through
    ``SparseShardedDataset(..., nnz_partition=True)``: the shards are the
    generator's, width for width and row for row."""
    ds = _ragged(seed=11, n=1_027, workers=4)
    order = np.argsort(ds.row_perm)  # shard order -> the file's order
    cols = [np.asarray(s.cols) for s in _shards(ds)]
    vals = [np.asarray(s.vals) for s in _shards(ds)]
    rows_c = [c[j, :n] for c, s in zip(cols, _shards(ds))
              for j, n in enumerate(_filled(s))]
    rows_v = [v[j, :n] for v, s in zip(vals, _shards(ds))
              for j, n in enumerate(_filled(s))]
    y = np.concatenate([np.asarray(s.y) for s in _shards(ds)])
    lengths = np.array([len(rows_c[i]) for i in order])
    indptr = np.concatenate([[0], np.cumsum(lengths)])
    loaded = SparseShardedDataset(
        indptr, np.concatenate([rows_c[i] for i in order]),
        np.concatenate([rows_v[i] for i in order]), y[order], D, 4,
        devices=jax.devices()[:1], nnz_partition=True)
    assert np.array_equal(loaded.row_perm, ds.row_perm)
    assert loaded.live_widths == ds.live_widths
    for a, b in zip(_shards(loaded), _shards(ds)):
        assert (a.live_width, a.nnz, a.size) == (b.live_width, b.nnz, b.size)
        for name in ("cols", "vals", "y"):
            assert np.array_equal(np.asarray(getattr(a, name)),
                                  np.asarray(getattr(b, name))), name
    assert len({s.vals.shape[1] for s in _shards(loaded)}) == 4


def test_a_value_beyond_a_shards_own_live_width_is_refused():
    import dataclasses

    ds = _ragged(n=1_027, workers=4)
    s = ds.shard(0)
    assert s.vals.shape[1] % 128 and (
        s.live_width < s.vals.shape[1] < max(ds.live_widths.values()))
    assert ds.checked_live_widths()[s.vals.shape[1]] == s.live_width
    ds.shards[0] = dataclasses.replace(
        s, vals=s.vals.at[3, s.live_width].set(1.0))
    with pytest.raises(ValueError, match=r"shards \[0\]"):
        ds.checked_live_widths()
    # BOTH solvers: each builds its programs from the one mapping (ASAGA
    # read the dataset's one integer until PR 43, and took this shard whole)
    with pytest.raises(ValueError, match=r"shards \[0\]"):
        ASGD(ds, None, _cfg(num_workers=4), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match=r"shards \[0\]"):
        ASAGA(ds, None, _cfg(num_workers=4, loss="least_squares"),
              devices=jax.devices()[:1])


# ---------------------------- (b) the step and the evaluation, by shard shape
def _cfg(**kw):
    base = dict(
        num_workers=8, num_iterations=64, gamma=8.0, taw=2**31 - 1,
        batch_rate=0.1, bucket_ratio=0.7, printer_freq=8, seed=5,
        loss="logistic", run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


@pytest.fixture(scope="module")
def solved():
    ds = _ragged()
    return ds, ASGD(ds, None, _cfg(), devices=jax.devices()[:1])


@pytest.mark.parametrize("wid", [0, 7], ids=["narrowest", "widest"])
def test_the_step_on_a_ragged_shard_gives_the_references_gradient(
        solved, wid):
    """The solver's ONE ``_step`` on the narrowest and on the widest shard
    (two shapes, two live widths, one jitted callable) against
    ``reference.full_gradient`` weighed by the step's own Bernoulli draw.
    Tolerance 2e-6 of the gradient's largest entry: both are float32, and
    they differ in the order of a margin's terms (up to 384 of them) and of
    a column's (a few rows of a sample of 50)."""
    ds, solver = solved
    s = ds.shard(wid)
    w = jnp.asarray(np.random.default_rng(7).standard_normal(D), jnp.float32)
    key = jax.random.PRNGKey(wid)
    g, new_key = solver._step(s.cols, s.vals, s.y, w, key)
    _next, sub = jax.random.split(key)
    assert np.array_equal(np.asarray(new_key), np.asarray(_next))
    mask = jax.random.bernoulli(sub, 0.1, (s.size,))
    assert 20 < int(mask.sum()) <= solver._task_rows(s.size)
    want = reference.full_gradient(s, w, D, "logistic",
                                   weights=mask.astype(jnp.float32))
    off = np.max(np.abs(np.asarray(g, np.float64) - want))
    assert off <= 2e-6 * np.max(np.abs(want)), off
    assert np.max(np.abs(want)) > 0


@pytest.mark.parametrize("wid", [0, 7], ids=["narrowest", "widest"])
@pytest.mark.parametrize("block_slots", [None, 4_096],
                         ids=["one-block", "wide-row-blocks"])
def test_the_evaluation_on_a_ragged_shard_gives_the_references_objective(
        solved, wid, block_slots, monkeypatch):
    """Eight seeded models (the first ``w = 0``) a call against
    ``reference.objective`` of each, relative 1e-5: float32 sums of 512
    rows in another order.  With a block of 4,096 slots the widest shard
    (384 slots a row: stored a whole number of lane tiles, so its blocks
    are taken slots minor) is walked in blocks of 8 rows and the narrowest
    (40 slots: rows minor) in blocks of 96, the last clamped."""
    ds, solver = solved
    ev = solver._eval
    if block_slots:
        monkeypatch.setattr(steps, "SPARSE_EVAL_BLOCK_SLOTS", block_slots)
        ev = steps.make_sparse_trajectory_loss_eval(
            "logistic", live_width=ds.live_widths)
    s = ds.shard(wid)
    stored = s.vals.shape[1]
    assert (stored % 128 == 0) == (wid == 7)
    rs = np.random.default_rng(8)
    W = np.stack([0.25 * j * rs.standard_normal(D)
                  for j in range(ev.snapshots_per_call)]).astype(np.float32)
    got = np.asarray(ev(s.cols, s.vals, s.y, jnp.asarray(W)), np.float64)
    want = np.array([reference.objective([s], w, "logistic") for w in W])
    np.testing.assert_allclose(got / s.size, want, rtol=1e-5)
    if block_slots:
        rows = ev.block_rows(s.size, stored)
        assert rows == {0: 96, 7: 8}[wid] and rows % 8 == 0
        assert ev.blocks(s.size, stored) == -(-s.size // rows)
        assert rows * ev.width(stored) <= block_slots
        assert ev.width(stored) == _read(s)
    else:
        assert ev.blocks(s.size, stored) == 1
    # asked without a width (benchmark/check_sparse.py does): the row bound
    assert ev.block_rows(s.size) == s.size and ev.blocks(s.size) == 1


def test_one_step_and_one_evaluation_are_traced_once_a_shard_shape(solved):
    ds = solved[0]
    solver = ASGD(ds, None, _cfg(), devices=jax.devices()[:1])  # untraced yet
    shapes = {s.cols.shape for s in _shards(ds)}
    assert len(shapes) == 8
    solver._warm_hot_path()
    # (off the store, as every test here: the step is its jitted function)
    assert solver._step._jitted._cache_size() == len(shapes)
    assert solver._programs.extras["sparse_step_shapes"] == len(shapes)
    # shards of one shape share one: criteo's and kdd2012's ONE step
    same = SparseShardedDataset.generate_on_device(
        2_001, 512, 11, 4, jax.devices()[:1], seed=3, noise=0.01)
    one = ASGD(same, None, _cfg(num_workers=4, loss="least_squares"),
               devices=jax.devices()[:1])
    ex = one._programs.extras
    assert same.live_widths == {16: 11}
    assert (ex["sparse_step_shapes"], ex["sparse_live_width"],
            ex["sparse_width_min"], ex["sparse_width_max"]) == (2, 11, 11, 11)
    # (2,001 rows over 4 workers: 501 and 500 rows, two shapes of one width)
    assert ex["sparse_stored_slots"] == 2_001 * 16
    assert ex["sparse_nonzero_slots"] == 2_001 * 11


def test_a_lane_block_narrower_than_a_row_is_one_row(monkeypatch):
    """``gradients._block_rows``: 8,192 slots are less than one row of
    16,384; the lane-row gather then walks the sample a row at a time and
    gives the element-wise margins."""
    assert gradients._block_rows(8_192, 16_384) == 1
    assert gradients._block_rows(327_680, 16_384) == 20
    assert gradients._block_rows(327_680, 1_567) == 128
    monkeypatch.setattr(gradients, "_on_tpu", lambda: True)
    monkeypatch.setattr(gradients, "SPARSE_VMEM_BYTES", 1_024)
    monkeypatch.setattr(gradients, "SPARSE_LANES_BLOCK_SLOTS", 64)
    rs = np.random.default_rng(4)
    c = jnp.asarray(rs.integers(0, D, (9, 200)), jnp.int32)
    v = jnp.asarray(rs.standard_normal((9, 200)), jnp.float32)
    w = jnp.asarray(rs.standard_normal(D), jnp.float32)
    assert gradients.sparse_gather_path(w, c) == "lanes128"
    np.testing.assert_allclose(
        np.asarray(gradients.sparse_margins(c, v, w)),
        np.asarray(gradients._margins_elements(c, v, w)), rtol=1e-5,
        atol=1e-5)


# ------------------------------------- (b') the walk of a packed sample
def _hand_shard(lengths, K, seed=0):
    """A padded-ELL shard made by hand: row ``i`` fills its first
    ``lengths[i]`` slots (values never 0, columns anywhere in ``D``)."""
    import types

    rs = np.random.default_rng(seed)
    n = len(lengths)
    live = np.arange(K)[None, :] < np.asarray(lengths)[:, None]
    cols = np.where(live, rs.integers(0, D, (n, K)), 0).astype(np.int32)
    vals = np.where(live, np.exp(0.5 * rs.standard_normal((n, K))) / 8,
                    0).astype(np.float32)
    y = (rs.random(n) < 0.6).astype(np.float32)
    return types.SimpleNamespace(
        cols=jnp.asarray(cols), vals=jnp.asarray(vals), y=jnp.asarray(y),
        size=n)


def _walk_case(case):
    """``(shard, (R, C), capacity or None)`` of one case of the walk."""
    n, K = 600, 384
    rs = np.random.default_rng(11)
    ascending = np.sort(rs.integers(8, K - 40, n))
    if case == "ascending":  # the order the data has
        return _hand_shard(ascending, K), (8, 128), None
    if case == "shuffled":  # correct for ANY order, only slower
        return _hand_shard(rs.permutation(ascending), K), (8, 128), None
    if case == "zero-inside-a-row":
        s = _hand_shard(ascending, K)
        vals = np.array(s.vals)
        vals[:, 3] = 0.0
        vals[np.arange(n), ascending // 2] = 0.0
        s.vals = jnp.asarray(vals)
        return s, (8, 128), None
    if case == "a-row-fills-K":  # and 384 is no multiple of the chunk, and
        # 112 packed rows none of the tile: both last blocks are pulled back
        lengths = ascending.copy()
        lengths[-60:] = K
        return _hand_shard(lengths, K), (24, 256), None
    if case == "overflow":  # more rows drawn than the capacity packs
        return _hand_shard(ascending, K), (8, 128), 40
    if case == "unfilled-last-tile":
        return _hand_shard(ascending, K), (16, 128), None
    if case == "one-lane-tile":
        return _hand_shard(np.sort(rs.integers(1, 129, n)), 128), (8, 512), None
    if case == "a-column-twice-in-a-row":  # and in rows of one block
        s = _hand_shard(ascending, K)
        cols = np.array(s.cols)
        cols[:, 5] = cols[:, 2]
        cols[1::2, 1] = cols[0:-1:2, 1]
        s.cols = jnp.asarray(cols)
        return s, (8, 128), None
    if case == "column-0-live":  # the column the dead slots are stored under
        s = _hand_shard(ascending, K)
        cols = np.array(s.cols)
        cols[::3, 0] = 0
        cols[:, 4] = np.where(ascending > 4, D - 1, 0)
        s.cols = jnp.asarray(cols)
        return s, (8, 128), None
    raise ValueError(case)


@pytest.fixture(params=["scatter", "segments"])
def sum_form(request, monkeypatch):
    """The two programs that add a walked sample's products into ``g``
    (``gradients.sparse_scatter_path``): the CPU's walked scatter-adds, and
    the TPU's sum by sorted segments, traced as on a TPU with the kernel
    interpreted (``conftest.segments_interpreted``; the jitted functions
    of the modules forget what they traced under it)."""
    if request.param == "segments":
        monkeypatch.setattr(gradients, "_on_tpu", lambda: True)
        request.getfixturevalue("segments_interpreted")
    yield request.param
    if request.param == "segments":
        jax.clear_caches()


@pytest.mark.parametrize("case", [
    "ascending", "shuffled", "zero-inside-a-row", "a-row-fills-K",
    "overflow", "unfilled-last-tile", "one-lane-tile",
    "a-column-twice-in-a-row", "column-0-live"])
def test_the_walked_step_gives_the_references_gradient(
        monkeypatch, case, sum_form):
    """A sample of a shard stored in lane tiles is WALKED (ISSUE 40): the
    model's gather takes it in ``(R, C)`` blocks, each row tile up to its
    last non-zero, and so does the scatter-add where the products are
    added by one (the CPU); on the TPU they are added by sorted segments
    (ISSUE 54): the ``capacity x K`` pairs in ONE list, a pair whose
    product is 0 (behind a row's end, in the unfilled tail, a zero inside
    a row) under the column beyond every tile.  Every non-zero is still
    gathered and added exactly once: the step's ``g`` is
    ``reference.full_gradient``'s under the step's own Bernoulli draw, to
    the tolerance the step on a ragged shard is held to above, whatever
    the order of the rows, with a zero inside a row, a row that fills the
    width, last blocks pulled back, a capacity that overflows, tiles that
    hold nothing, a column drawn twice in a row and in two rows of a
    block, and column 0 (what a dead slot is stored under) live."""
    s, (R, C), cap = _walk_case(case)
    b, K = 0.1, s.cols.shape[1]
    for tile in ("SPARSE_WALK_TILE", "SPARSE_WALK_TILE_HBM"):
        monkeypatch.setattr(gradients, tile, (R, C))
    if cap is not None:
        monkeypatch.setattr(steps, "sparse_step_capacity", lambda b, n: cap)
    cap = steps.sparse_step_capacity(b, s.size)
    assert steps.sparse_walk_tile(b, D, s.size, K) == (R, min(C, K))
    step = steps.make_sparse_asgd_worker_step(b, D, "logistic")
    assert step.scatter_path(s.size, K) == sum_form
    w = jnp.asarray(np.random.default_rng(7).standard_normal(D), jnp.float32)
    key = jax.random.PRNGKey(5)
    g, _key = step(s.cols, s.vals, s.y, w, key)
    mask = np.array(
        jax.random.bernoulli(jax.random.split(key)[1], b, (s.size,)))
    drawn = np.flatnonzero(mask)
    assert (len(drawn) > cap) == (case == "overflow")
    mask[drawn[cap:]] = False  # the packing keeps the first ``cap``
    want = reference.full_gradient(s, w, D, "logistic",
                                   weights=mask.astype(np.float32))
    off = np.max(np.abs(np.asarray(g, np.float64) - want))
    assert off <= 2e-6 * np.max(np.abs(want)), off
    # what the walk took of the capacity x width the one-shot forms take
    idx = np.pad(drawn[:cap], (0, max(0, cap - len(drawn))))
    filled = (np.arange(cap) < len(drawn))[:, None]
    chunks = np.asarray(gradients.sample_walk(
        jnp.where(filled, s.vals[idx], 0), (R, min(C, K))).chunks)
    assert len(chunks) == -(-cap // R)
    walked = int(chunks.sum()) * R * min(C, K)
    if case == "unfilled-last-tile":
        assert chunks[-1] == 0 and chunks[0] > 0
    if case == "overflow":
        assert chunks.min() > 0
    if case in ("ascending", "zero-inside-a-row", "unfilled-last-tile"):
        assert walked < 0.5 * cap * K, (walked, cap * K)
        assert list(chunks) == sorted(chunks[chunks > 0]) + [0] * int(
            (chunks == 0).sum())


def test_a_sample_stored_in_sublane_tiles_is_read_whole():
    """The chooser is the stored shape: ``K % 128 != 0`` (criteo 40,
    kdd2012 16, rcv1) keeps the one-shot programs, whatever the rows."""
    for width in (11, 16, 39, 40, 120, 200):
        for resident in (True, False):
            assert gradients.walk_tile(992, width, resident) is None
        assert steps.sparse_walk_tile(0.05, 16_609_143, 16_406, width) is None
    assert gradients.sample_walk(jnp.ones((16, 40)), None) is None
    # a block of 16,384 slots where the (d,) accumulator stays in VMEM, of
    # 65,536 where the shard's own arrays fit there and take its place
    # (webspam's narrowest shard) or the model is too large to
    assert gradients.walk_tile(992, 16_384) == (64, 256)
    assert gradients.walk_tile(992, 16_384, resident=False) == (128, 512)
    assert steps.sparse_walk_tile(0.05, 16_609_143, 16_406, 16_384) == (64, 256)
    assert steps.sparse_walk_tile(0.05, 16_609_143, 16_406, 2_176) == (64, 256)
    assert steps.sparse_walk_tile(0.05, 16_609_143, 16_407, 1_664) == (128, 512)
    assert steps.sparse_walk_tile(0.05, 54_686_452, 16_406, 16_384) == (128, 512)
    assert gradients.walk_tile(8, 128) == (8, 128)


#: the four sparse cells' steps as ``(d, packed rows, width read, walked)``
CRITEO, KDD2012, WEBSPAM = 1_000_000, 54_686_452, 16_609_143
WEBSPAM_WIDTHS = (1_664, 2_176, 2_688, 3_200, 3_840, 4_736, 6_272, 16_384)


@pytest.mark.parametrize("d,rows,width,want", [
    (CRITEO, 145_472, 39, "segments"),   # 5.67M pairs, ASGD
    (CRITEO, 29_656, 39, "segments"),    # 1.16M pairs, ASAGA
    (KDD2012, 236_640, 11, "scatter"),   # 195 a tile: the constant
    # webspam's eight walked samples, 407, 532, 658, 783, 939, 1,159,
    # 1,534 and 4,008 slots a tile of the list: all sort ONE list since
    # ISSUE 57 (the five narrowest kept a scatter-add a block until a
    # step was built once a machine)
    *[(WEBSPAM, 992, k, "segments") for k in WEBSPAM_WIDTHS],
    # the bound to the slot: 256 a tile of 4,055 tiles
    (WEBSPAM, 4_055, 256, "segments"),
    (WEBSPAM, 4_054, 256, "scatter"),
], ids=lambda v: str(v))
def test_the_sum_is_chosen_from_the_four_cells_shapes(
        monkeypatch, d, rows, width, want):
    """``gradients.sparse_scatter_path`` over the steps the four sparse
    cells run (ISSUE 54, ISSUE 57): criteo's two by sorted segments,
    kdd2012's by its scatter-add, all eight of webspam's shards by sorted
    segments, each from the backend, the dtype, the list's length and
    ``d`` against ONE constant (``SPARSE_SEGMENT_TILE_SLOTS``: between
    kdd2012's 195 slots a tile and webspam's narrowest 407, held to
    kdd2012's memory, not to a break-even); the CPU keeps the scatter-add
    everywhere.  The next change to the chooser cannot move a cell
    unseen."""
    assert gradients.SPARSE_SEGMENT_TILE_SLOTS == 256
    assert 195 < gradients.SPARSE_SEGMENT_TILE_SLOTS <= 407
    assert gradients.sparse_scatter_path(d, rows * width) == "scatter"
    assert gradients.sparse_sorted_pairs(d, rows * width) == 0
    monkeypatch.setattr(gradients, "_on_tpu", lambda: True)
    assert gradients.sparse_scatter_path(d, rows * width) == want
    pairs = gradients.sparse_sorted_pairs(d, rows * width)
    if want == "scatter":
        assert pairs == 0
    else:  # the whole list, in blocks of the kernel's DMA
        assert rows * width <= pairs < rows * width + 8_192
        assert pairs % 8_192 == 0
    for dtype in (jnp.bfloat16, jnp.float64):
        assert gradients.sparse_scatter_path(
            d, rows * width, dtype) == "scatter"


def test_walked_slots_are_what_a_seeded_step_walks(monkeypatch):
    """``extras["walked_slots_per_step_mean"]`` is reckoned from the
    shards' own row lengths, on the host: on the widest shard (stored in
    lane tiles) it is what exact counts over seeded draws average, within
    the draw's spread, and well under capacity x width; on a shard stored
    in sublane tiles it IS capacity x the width read.  (Blocks of 16 x 128
    slots: the chip's blocks would hold these 52 packed rows in one.)"""
    for tile in ("SPARSE_WALK_TILE", "SPARSE_WALK_TILE_HBM"):
        monkeypatch.setattr(gradients, tile, (16, 128))
    ds = _ragged()
    solver = ASGD(ds, None, _cfg(), devices=jax.devices()[:1])
    s = ds.shard(7)
    K = s.vals.shape[1]
    assert K % 128 == 0 and np.array_equal(s.row_lengths, _filled(s))
    cap = solver._task_rows(s.size)
    R, C = steps.sparse_walk_tile(0.1, ds.d, s.size, K)
    assert (R, C) == (16, 128)
    counts = []
    for k in range(24):
        idx, valid = steps._sampled_rows(
            jax.random.PRNGKey(k), 0.1, s.size, jnp.float32)
        walk = gradients.sample_walk(s.vals[idx] * valid[:, None], (R, C))
        counts.append(int(walk.chunks.sum()) * R * C)
    said = steps.sparse_walked_slots(0.1, ds.d, s.size, K, s.row_lengths)
    walked = solver._programs.step_walked
    assert said == walked[7]
    assert abs(said - np.mean(counts)) < 2 * np.std(counts), (said, counts)
    assert 0.1 * s.nnz < said < 0.8 * cap * K
    narrow = ds.shard(0)
    assert narrow.vals.shape[1] % 128
    assert walked[0] == solver._task_rows(narrow.size) * _read(narrow)
    shares = [wk / (solver._task_rows(sh.size) * sh.vals.shape[1])
              for wk, sh in zip(walked, _shards(ds))]
    assert solver._programs.extras["walked_slots_share_max"] == max(shares)
    assert shares[7] < 0.8 < max(shares) <= 1.0
    # rows of ONE length in sublane tiles: every step walks what it reads
    same = SparseShardedDataset.generate_on_device(
        2_000, 512, 11, 4, jax.devices()[:1], seed=3, noise=0.01)
    res = ASGD(same, None, _cfg(num_workers=4, num_iterations=4,
                                loss="least_squares"),
               devices=jax.devices()[:1]).run_sync()
    assert res.extras["walked_slots_per_step_mean"] == (
        res.extras["live_slots_per_step"])


def test_sorted_pairs_are_what_a_seeded_steps_list_holds(
        monkeypatch, sum_form):
    """``extras["sorted_pairs_per_step_mean"]`` (ISSUE 54), beside
    ``walked_slots_per_step_mean``: the (column, product) pairs a mean
    accepted step SORTS, from the host's integers.  Traced as on a TPU, a
    worker's count is the length of the ONE list its step's program sorts
    and hands the kernel, the whole ``capacity x width`` in blocks of
    8,192, on a walked shard and on one stored in sublane tiles alike; a
    step that adds by a scatter-add sorts none, and on the CPU every step
    does.  A lockstep run accepts every worker once a round, so its mean
    is the workers' mean, to the digit."""
    ds = _ragged(n=2_054, workers=4)
    solver = ASGD(ds, None, _cfg(num_workers=4, num_iterations=3),
                  devices=jax.devices()[:1])
    programs = solver._programs
    w = jnp.zeros(ds.d, jnp.float32)
    assert len(programs.step_sorted) == 4
    for wid, said in enumerate(programs.step_sorted):
        s = ds.shard(wid)
        slots = solver._task_rows(s.size) * _read(s)
        text = str(jax.make_jaxpr(programs.step)(
            *s.operands, w, jax.random.PRNGKey(wid)))
        if solver._step.scatter_path(s.size, _read(s)) == "scatter":
            assert said == 0 and "segment_tiles_sum" not in text
            assert "scatter-add" in text or "scatter_add" in text
            continue
        assert sum_form == "segments"
        assert said == -(-slots // 8_192) * 8_192
        # the pair the sort takes and the rows of 128 the kernel reads
        assert f"i32[{int(said)}]" in text and f"f32[{int(said)}]" in text
        assert f"i32[{int(said) // 128},128]" in text
        assert "scatter-add" not in text and "scatter_add" not in text
    if sum_form == "scatter":
        assert programs.step_sorted == (0.0,) * 4
    else:
        assert programs.extras["sparse_scatter_path"] in (
            "segments", "scatter+segments")
        assert max(programs.step_sorted) > 0
    ex = solver.run_sync().extras
    assert ex["accepted_by_worker_min"] == ex["accepted_by_worker_max"] == 3
    assert ex["sorted_pairs_per_step_mean"] == pytest.approx(
        sum(programs.step_sorted) / 4)
    if sum_form == "segments":  # list length over non-zeros, from any run
        assert ex["sorted_pairs_per_step_mean"] > (
            ex["nonzero_slots_per_step_mean"])
        return
    dense = np.random.default_rng(0).standard_normal((64, 8)).astype("f4")
    res = ASGD(dense, dense[:, 0].copy(), _cfg(
        num_workers=4, num_iterations=8, loss="least_squares", gamma=0.1),
        devices=jax.devices()[:1]).run()
    assert "sorted_pairs_per_step_mean" not in res.extras


# ------------------------------------------------------ (c) the engine's run
def test_a_short_asgd_run_agrees_with_the_reference_and_counts_its_shards(
        solved):
    ds, solver = solved
    res = solver.run()
    assert res.accepted == 64
    f_ref = reference.objective(_shards(ds), res.final_w, "logistic")
    f0 = res.trajectory[0][1]
    # float32 sums of 4,096 rows in another order: 1e-5 relative
    assert res.trajectory[-1][1] == pytest.approx(f_ref, rel=1e-5)
    assert f0 == pytest.approx(np.log(2.0), rel=1e-6) and f_ref < 0.99 * f0
    ex = res.extras
    shards = _shards(ds)
    cap = steps.sparse_step_capacity(0.1, 512)
    assert ex["sparse_step_capacity"] == cap
    assert ex["sparse_step_shapes"] == 8
    assert ex["sparse_width_min"] == _read(shards[0]) == shards[0].live_width
    assert ex["sparse_width_max"] == _read(shards[7]) == 384
    assert ex["sparse_live_width"] == 384
    assert ex["live_slots_per_step"] == cap * 384
    assert ex["sampled_slots_per_step"] == cap * shards[7].vals.shape[1]
    assert ex["sparse_stored_slots"] == ds.padded_nnz() == sum(
        512 * s.vals.shape[1] for s in shards)
    assert ex["sparse_nonzero_slots"] == sum(s.nnz for s in shards) == ds.nnz()
    assert ex["sparse_gather_path"] == "elements"
    # every accepted result counted once, by its worker; the mean step's
    # non-zeros from the shards' own counts
    assert 0 <= ex["accepted_by_worker_min"] <= 64 // 8
    assert 64 // 8 <= ex["accepted_by_worker_max"] <= 64
    lo, hi = 0.1 * shards[0].nnz, 0.1 * shards[7].nnz
    assert lo <= ex["nonzero_slots_per_step_mean"] <= hi
    # each shard at its OWN live width, every row once a call
    calls = ex["eval_calls"]
    assert ex["eval_live_slots"] == calls * sum(
        512 * _read(s) for s in shards)
    assert ex["eval_slots"] == calls * ds.padded_nnz()


def test_dispatch_turns_keep_the_order_the_tasks_were_built_in():
    """Eight tasks built in order and started in the reverse one, a
    thread each: their dispatches run in the order they were built.  A
    ticket whose task never runs holds the ones behind it ``patience_s``
    and no longer, and a task run a second time (a retry) goes at once."""
    import threading
    import time

    from asyncframework_tpu.solvers.engine_loop import DispatchTurns
    from asyncframework_tpu.solvers.instrumentation import worker_task

    class _Ready:
        def block_until_ready(self):
            pass

    turns = DispatchTurns(patience_s=5.0)
    ran = []
    tasks = [worker_task(lambda mine, i=i: ran.append(i) or (_Ready(), i),
                         turns=turns) for i in range(8)]
    threads = [threading.Thread(target=t) for t in tasks]
    for th in reversed(threads):
        th.start()
        time.sleep(0.002)
    for th in threads:
        th.join(timeout=10)
    assert ran == list(range(8))
    assert tasks[3]()[1] == 3 and ran[-1] == 3  # again: its turn is past
    lost = DispatchTurns(patience_s=0.05)
    _never_run = worker_task(lambda mine: (_Ready(), 0), turns=lost)
    t0 = time.monotonic()
    assert worker_task(lambda mine: (_Ready(), 1), turns=lost)()[1] == 1
    assert 0.04 <= time.monotonic() - t0 < 2.0


def test_shards_of_unequal_width_are_dispatched_in_the_cohorts_order(solved):
    """Only there, and the RUN decides it (from the widths its solver's
    programs read the shards at): shards of one width keep the executor
    threads' race, and a task with an injected delay takes no turn."""
    from asyncframework_tpu.engine.straggler import DelayModel
    from asyncframework_tpu.solvers.engine_loop import EngineRun

    ds, solver = solved
    EngineRun(solver).shutdown(True)
    assert set(solver._turns) == set(solver.devices)
    same = SparseShardedDataset.generate_on_device(
        2_001, 512, 11, 4, jax.devices()[:1], seed=3, noise=0.01)
    one = ASGD(same, None, _cfg(num_workers=4, loss="least_squares"),
               devices=jax.devices()[:1])
    assert one._turns == {}  # none before a run, and none in one
    EngineRun(one).shutdown(True)
    assert one._turns == {}
    turns = solver._turns[solver.devices[0]]
    before = turns._issued
    key = jax.random.PRNGKey(0)
    w = jnp.zeros(D, jnp.float32)
    quiet = DelayModel(0.0, 8, 5)
    fns = [solver._make_task(wid, w, key, quiet) for wid in (2, 5)]
    assert turns._issued == before + 2
    g5, _ = fns[1]()  # waits out ticket `before` (patience), then runs
    g2, _ = fns[0]()
    assert g2.shape == g5.shape == (D,)

    class Late(DelayModel):
        def delay_ms(self, wid):
            return 1.0

    solver._make_task(3, w, key, Late(0.0, 8, 5))
    assert turns._issued == before + 2


def test_a_lockstep_run_reads_the_mean_step_from_the_shards_counts():
    """``run_sync`` accepts every worker once a round: the mean step's
    non-zeros are ``batch_rate`` x the mean shard's, to the digit."""
    ds = _ragged(n=1_027, workers=4)
    solver = ASGD(ds, None, _cfg(num_workers=4, num_iterations=5),
                  devices=jax.devices()[:1])
    ex = solver.run_sync().extras
    assert ex["accepted_by_worker_min"] == ex["accepted_by_worker_max"] == 5
    assert ex["nonzero_slots_per_step_mean"] == pytest.approx(
        0.1 * ds.nnz() / 4)
    dense = np.random.default_rng(0).standard_normal((64, 8)).astype("f4")
    res = ASGD(dense, dense[:, 0].copy(), _cfg(
        num_workers=4, num_iterations=8, loss="least_squares", gamma=0.1),
        devices=jax.devices()[:1]).run()
    assert "nonzero_slots_per_step_mean" not in res.extras
    assert res.extras["accepted_by_worker_max"] >= 2


def test_asaga_reads_a_ragged_dataset_by_the_mapping_as_asgd_does():
    """ASAGA's programs are built from the dataset's one width API, the
    mapping by stored width (until PR 43: ONE integer, a narrower shard
    read whole): every shard is read at its own width, the widest says
    ``sparse_live_width``, and the run still ends where the reference
    says."""
    ds = _ragged(n=1_027, workers=4, loss="least_squares")
    solver = ASAGA(ds, None, _cfg(
        num_workers=4, num_iterations=24, loss="least_squares", gamma=0.05),
        devices=jax.devices()[:1])
    res = solver.run()
    assert res.accepted == 24
    assert solver._programs.widths == tuple(_read(s) for s in _shards(ds))
    assert res.extras["sparse_live_width"] == max(solver._programs.widths)
    assert res.extras["sparse_width_min"] == _read(ds.shard(0))
    f_ref = reference.objective(_shards(ds), res.final_w, "least_squares")
    assert res.trajectory[-1][1] == pytest.approx(f_ref, rel=1e-5)


def test_the_fused_rounds_take_ragged_shards_in_one_program():
    """``run_fused`` unrolls the workers' steps into ONE executable: with
    the mapping of live widths each shard's step inside it is read at its
    own (four shapes here; never timed on a chip at webspam's eight)."""
    ds = _ragged(n=1_027, workers=4, loss="least_squares")
    solver = ASGD(ds, None, _cfg(
        num_workers=4, num_iterations=16, loss="least_squares", gamma=0.5),
        devices=jax.devices()[:1])
    res = solver.run_fused()
    assert res.accepted == 16
    f_ref = reference.objective(_shards(ds), res.final_w, "least_squares")
    assert res.trajectory[-1][1] == pytest.approx(f_ref, rel=1e-5)
    assert f_ref < res.trajectory[0][1]


def test_the_plan_counts_the_wide_steps_temporaries():
    ds = _ragged(n=1_027, workers=4)
    cfg = _cfg(num_workers=4)
    cap = steps.sparse_step_capacity(0.1, 257)
    want = 20 * sum(
        steps.sparse_step_capacity(0.1, s.size) * s.vals.shape[1]
        for s in _shards(ds))
    programs = steps.worker_programs(ds, cfg.batch_rate, cfg.loss)
    assert programs.workspace_bytes == want
    assert want >= 20 * cap * 384
    devs = jax.devices()[:1]
    solver_base.check_hbm_plan(ds, cfg, devs, False, programs)
    import dataclasses

    from asyncframework_tpu.utils.hbm import dataset_residency_bytes

    held = max(dataset_residency_bytes(ds).values()) + (
        solver_base.planned_model_copies(
            cfg, programs.eval_stack_rows) * 4 * D)
    # a budget that holds the shards and the model-sized state and not the
    # steps' temporaries is refused (the planner's headroom is 0.85)
    tight = dataclasses.replace(
        cfg, hbm_budget_bytes=int((held + want // 2) / 0.85))
    with pytest.raises(MemoryError):
        solver_base.check_hbm_plan(ds, tight, devs, False, programs)
    roomy = dataclasses.replace(
        cfg, hbm_budget_bytes=int((held + 2 * want) / 0.85))
    solver_base.check_hbm_plan(ds, roomy, devs, False, programs)


@pytest.mark.parametrize("chips", [1, 4])
def test_the_plan_charges_every_chip_the_model_sized_state(chips):
    """ISSUE 47: shards of unequal width dealt over ``chips`` devices: the
    plan charges the device with the most shard bytes its shards, the
    steps' temporaries AND the whole of ``planned_model_copies``, because
    every chip holds the live model, each worker's pinned version, each
    result and each snapshot; over one device that is the parent's plan."""
    import dataclasses

    from asyncframework_tpu.utils.hbm import dataset_residency_bytes

    devs = jax.devices()[:chips]
    ds = SparseShardedDataset.generate_on_device(
        1_027, D, 96, 4, devs, seed=3, noise=0.0, column_skew=0.5,
        row_nnz=LAW, row_values=VALUES, bernoulli_labels=LABELS)
    cfg = _cfg(num_workers=4)
    programs = steps.worker_programs(ds, cfg.batch_rate, cfg.loss)
    per_dev = dataset_residency_bytes(ds)
    assert len(per_dev) == chips
    copies = solver_base.planned_model_copies(cfg, programs.eval_stack_rows)
    assert copies == 1 + 2 * 4 + solver_base.planned_snapshots(cfg) + (
        programs.eval_stack_rows)
    held = (max(per_dev.values()) + copies * 4 * D
            + programs.workspace_bytes)
    fits = dataclasses.replace(cfg, hbm_budget_bytes=int(held / 0.85) + 1)
    solver_base.check_hbm_plan(ds, fits, devs, False, programs)
    # no room for the live model and a pinned version a worker on the
    # fullest chip: refused, although three other chips would hold them
    short = dataclasses.replace(
        cfg, hbm_budget_bytes=int((held - (1 + 4) * 4 * D) / 0.85))
    with pytest.raises(MemoryError):
        solver_base.check_hbm_plan(ds, short, devs, False, programs)


# ------------------------------------------------ on the profiler's clock
def test_the_dispatchs_annotation_says_the_shards_width(tmp_path):
    from jax.profiler import ProfileData

    ds = _ragged(n=1_027, workers=4)
    solver = ASGD(ds, None, _cfg(num_workers=4, num_iterations=16),
                  devices=jax.devices()[:1])
    trace_dir = str(tmp_path / "xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        res = solver.run()
    finally:
        jax.profiler.stop_trace()
    assert res.accepted == 16
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    seen = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.ANNOTATION_PREFIX + trace.TASK_DISPATCH:
                    stats = dict(ev.stats)
                    seen.add((stats["worker"], stats["width"]))
    assert seen == {(w, _read(ds.shard(w))) for w in range(4)}
