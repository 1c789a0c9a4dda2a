"""The sparse steps' margins ``m = sum_k v_sel * w[c_sel]`` (ISSUE 36): ONE
helper, ``gradients.sparse_margins``, and ONE chooser of its gather's
program, ``gradients.sparse_gather_path``.

``"elements"`` is the expression every step held until PR 36, one model
value an index; ``"rows8"`` views the model as an ``(8, d / 8)`` table and
gathers eight values an index in row blocks of the sample, then selects the
one; ``"lanes128"`` (ISSUE 37) views a model too large for VMEM as rows of
128 lanes, gathers the row and keeps the lane.  A gather is a copy: the
forms called directly give the same
VALUES to the bit, for every int32 index ``w[c]`` takes, and the margins
differ by the order of a ``K``-term float32 sum at most.  The chooser
answers from the backend and the shapes alone, so the CPU suite runs
``"elements"`` everywhere and no test's numbers moved; what the TPU's
program looks like is ``tests/test_step_layout.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.data.sparse import SparseShardedDataset
from asyncframework_tpu.ops import gradients, steps
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig

BLOCK = 256  # rows a block: SPARSE_GATHER_BLOCK_SLOTS patched to BLOCK x k


def _small_blocks(monkeypatch, k):
    monkeypatch.setattr(gradients, "SPARSE_GATHER_BLOCK_SLOTS", BLOCK * k)


def _sample(d, rows, k, case, seed=0):
    """``(c_sel, v_sel, w)`` of a seeded packed sample: columns anywhere in
    ``[0, d)``, the generator's padding (column 0, value 0) in the last
    three slots of a row, and with ``invalid_tail`` the last third of the
    rows unfilled as ``steps._pack_rows`` leaves them (row 0's columns,
    values zeroed by ``valid``)."""
    rs = np.random.default_rng(seed + d + rows)
    c = rs.integers(0, d, (rows, k)).astype(np.int32)
    v = rs.standard_normal((rows, k)).astype(np.float32)
    w = rs.standard_normal(d).astype(np.float32)
    w[::97] = -0.0  # a select keeps the sign a sum of zeros would lose
    c[:, k - 3:] = 0
    v[:, k - 3:] = 0.0
    if case == "invalid_tail":
        tail = rows - rows // 3
        c[tail:] = c[0]
        v[tail:] = 0.0
    elif case == "any_int32":  # w[c]: negatives count from the end, then clamp
        c[::5, 0] = -1
        c[1::5, 1] = -d
        c[2::5, 2] = -d - 7
        c[3::5, 3] = d
        c[4::5, 4] = np.iinfo(np.int32).max
        c[::7, 5] = np.iinfo(np.int32).min
    return jnp.asarray(c), jnp.asarray(v), jnp.asarray(w)


def _bits(a):
    return np.asarray(a).view(np.uint32)


_SIZES = {"under_a_block": 100, "one_block": BLOCK, "ragged_last_block": 777,
          "two_blocks": 2 * BLOCK}


@pytest.mark.parametrize("case", ["padding", "invalid_tail", "any_int32"])
@pytest.mark.parametrize("size", list(_SIZES))
@pytest.mark.parametrize("d,k", [(1_000_000, 40), (4_096, 7)])
def test_the_two_forms_gather_the_same_values(monkeypatch, d, k, size, case):
    _small_blocks(monkeypatch, k)
    rows = _SIZES[size]
    c, v, w = _sample(d, rows, k, case)
    picked = gradients._gather_rows8(w.reshape(8, d // 8), c)
    assert picked.shape == c.shape and picked.dtype == w.dtype
    np.testing.assert_array_equal(_bits(picked), _bits(w[c]))

    m8 = jax.jit(gradients._margins_rows8)(c, v, w)
    m1 = jax.jit(gradients._margins_elements)(c, v, w)
    assert m8.shape == m1.shape == (rows,) and m8.dtype == m1.dtype
    # the same k products in another order: a few ulp of their size
    terms = np.abs(np.asarray(v, np.float64) * np.asarray(w[c], np.float64))
    ulp = np.finfo(np.float32).eps * terms.sum(axis=1)
    assert np.all(np.abs(np.asarray(m8, np.float64) - np.asarray(m1)) <= 4 * ulp)
    exact = (np.asarray(v, np.float64) * np.asarray(w[c], np.float64)).sum(1)
    assert np.all(np.abs(np.asarray(m8) - exact) <= 8 * ulp)
    if case == "invalid_tail":  # an unfilled slot's margin is the exact 0
        assert not np.asarray(m8)[rows - rows // 3:].any()


@pytest.mark.parametrize("case", ["padding", "invalid_tail", "any_int32"])
@pytest.mark.parametrize("size", list(_SIZES))
@pytest.mark.parametrize("d,k", [(1_000_000, 40), (40_004, 16), (4_096, 7)])
def test_the_lane_row_form_gathers_the_same_values(
        monkeypatch, d, k, size, case):
    """``"lanes128"`` (ISSUE 37): the model as rows of 128 lanes, whatever
    ``d % 8`` (40,004 is no multiple of 8, nor of 128: the last row is
    padded), the value picked by its lane: ``w[c]`` to the bit."""
    monkeypatch.setattr(gradients, "SPARSE_LANES_BLOCK_SLOTS", BLOCK * k)
    rows = _SIZES[size]
    c, v, w = _sample(d, rows, k, case)
    q = -(-d // 128)
    table = jnp.pad(w, (0, q * 128 - d)).reshape(q, 128)
    picked = gradients._gather_lanes128(table, c, d)
    assert picked.shape == c.shape and picked.dtype == w.dtype
    np.testing.assert_array_equal(_bits(picked), _bits(w[c]))

    ml = jax.jit(gradients._margins_lanes128)(c, v, w)
    m1 = jax.jit(gradients._margins_elements)(c, v, w)
    assert ml.shape == m1.shape == (rows,) and ml.dtype == m1.dtype
    terms = np.abs(np.asarray(v, np.float64) * np.asarray(w[c], np.float64))
    ulp = np.finfo(np.float32).eps * terms.sum(axis=1)
    assert np.all(np.abs(np.asarray(ml, np.float64) - np.asarray(m1)) <= 4 * ulp)
    if case == "invalid_tail":  # an unfilled slot's margin is the exact 0
        assert not np.asarray(ml)[rows - rows // 3:].any()
    text = str(jax.make_jaxpr(gradients._margins_lanes128)(c, v, w))
    block = min(rows, BLOCK)
    assert f"f32[{block},{k},128]" in text and f"f32[{q},128]" in text
    if rows > BLOCK:
        assert "scan" in text and f"f32[{rows},{k},128]" not in text


def test_a_sample_is_walked_in_clamped_blocks_of_one_shape(monkeypatch):
    """777 rows in blocks of 256: four blocks, the last read at row 521 so
    that it ends with the sample (the evaluation's arithmetic, shared)."""
    assert gradients.row_blocks(777, BLOCK) == (BLOCK, 4)
    assert gradients.row_blocks(100, BLOCK) == (100, 1)
    assert gradients.row_blocks(BLOCK, BLOCK) == (BLOCK, 1)
    got = [tuple(int(x) for x in gradients.clamped_block(i, BLOCK, 777))
           for i in range(4)]
    assert got == [(0, 0), (256, 256), (512, 512), (768, 521)]
    _small_blocks(monkeypatch, 7)
    c, v, w = _sample(4_096, 777, 7, "padding")
    text = str(jax.make_jaxpr(gradients._margins_rows8)(c, v, w))
    # a ``fori_loop`` of a known trip count is a ``scan`` in the jaxpr
    assert "scan" in text and f"f32[8,7,{BLOCK}]" in text, text[:2000]
    assert "f32[8,7,777]" not in text


# ------------------------------------------------------------- the chooser

def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize(
    "on_tpu,w,c_sel,want",
    [
        # the criteo cell's step: 145,472 packed rows of 40 slots
        (True, ((1_000_000,), jnp.float32), (145_472, 40), "rows8"),
        (False, ((1_000_000,), jnp.float32), (145_472, 40), "elements"),
        # rcv1's width is no multiple of eight: no (8, d / 8) view
        (True, ((47_236,), jnp.float32), (145_472, 40), "elements"),
        # one whole block of 327,680 slots, at two widths
        (True, ((1_000_000,), jnp.float32), (8_192, 40), "rows8"),
        (True, ((4_096,), jnp.float32), (20_480, 16), "rows8"),
        # under one block: its gathered rows could take the table's VMEM
        (True, ((1_000_000,), jnp.float32), (8_191, 40), "elements"),
        (True, ((1_000_000,), jnp.float32), (128, 16), "elements"),
        (True, ((1_000_000,), jnp.bfloat16), (145_472, 40), "elements"),
        (True, ((8, 125_000), jnp.float32), (145_472, 40), "elements"),
        (True, ((1_000_000,), jnp.float32), (5_818_880,), "elements"),
        # the kdd2012 cell's step: 236,640 packed rows of 16 slots against
        # a 219 MB model, which no form of keeps in VMEM (ISSUE 37)
        (True, ((54_686_452,), jnp.float32), (236_640, 16), "lanes128"),
        (False, ((54_686_452,), jnp.float32), (236_640, 16), "elements"),
        # one whole block of 8,192 slots of THAT form, and one row under
        (True, ((54_686_452,), jnp.float32), (512, 16), "lanes128"),
        (True, ((54_686_452,), jnp.float32), (511, 16), "elements"),
        # over VMEM an eight-row view is no help: 40M columns, 160 MB
        (True, ((40_000_000,), jnp.float32), (236_640, 16), "lanes128"),
        # 128 MiB to the byte is not over it
        (True, ((2**25,), jnp.float32), (236_640, 16), "rows8"),
        (True, ((2**25 + 8,), jnp.float32), (236_640, 16), "lanes128"),
        (True, ((54_686_452,), jnp.bfloat16), (236_640, 16), "elements"),
    ],
    ids=["tpu-criteo", "cpu-criteo", "tpu-rcv1-width", "tpu-one-block",
         "tpu-one-block-narrow", "tpu-under-a-block", "tpu-small",
         "tpu-bf16-model",
         "tpu-2d-model", "tpu-flat-sample",
         "tpu-kdd2012", "cpu-kdd2012", "tpu-one-lane-block",
         "tpu-under-a-lane-block", "tpu-over-vmem-multiple-of-8",
         "tpu-vmem-to-the-byte", "tpu-eight-columns-over-vmem",
         "tpu-bf16-wide-model"],
)
def test_the_chooser_answers_from_backend_and_shapes(
        monkeypatch, on_tpu, w, c_sel, want):
    monkeypatch.setattr(gradients, "_on_tpu", lambda: on_tpu)
    got = gradients.sparse_gather_path(_spec(*w), _spec(c_sel, jnp.int32))
    assert got == want


@pytest.mark.parametrize(
    "on_tpu,d,slots,walk,dtype,want",
    [
        # criteo under ASGD: 145,472 packed rows x 39 live slots, 23,000 a
        # tile of 4,096 of its 1,000,000 columns; under ASAGA 29,656 rows
        (True, 1_000_000, 145_472 * 39, None, jnp.float32, "segments"),
        (True, 1_000_000, 29_656 * 39, None, jnp.float32, "segments"),
        (False, 1_000_000, 145_472 * 39, None, jnp.float32, "scatter"),
        (False, 1_000_000, 29_656 * 39, None, jnp.float32, "scatter"),
        # the end-of-run check: every slot of an ASAGA shard
        (True, 1_000_000, 1_432_520 * 39, None, jnp.float32, "segments"),
        # kdd2012: 236,640 x 11 slots over 54,686,452 columns, 195 a tile
        (True, 54_686_452, 236_640 * 11, None, jnp.float32, "scatter"),
        (False, 54_686_452, 236_640 * 11, None, jnp.float32, "scatter"),
        # webspam: a WALKED sample's pairs in one list (ISSUE 54), held to
        # the same constant: 4,008 slots a tile of its 4,055 on the widest
        # shard, 407 on the narrowest, over the constant too since ISSUE 57
        (True, 16_609_143, 992 * 16_384, (64, 256), jnp.float32, "segments"),
        (True, 16_609_143, 992 * 1_664, (128, 512), jnp.float32, "segments"),
        (False, 16_609_143, 992 * 16_384, (64, 256), jnp.float32, "scatter"),
        # the constant to the slot: 245 tiles of 256 slots
        (True, 1_000_000, 245 * 256, None, jnp.float32, "segments"),
        (True, 1_000_000, 245 * 256 - 1, None, jnp.float32, "scatter"),
        (True, 1_000_000, 145_472 * 39, None, jnp.bfloat16, "scatter"),
        (True, 1_000_000, 145_472 * 39, None, jnp.float64, "scatter"),
    ],
    ids=["tpu-criteo-asgd", "tpu-criteo-asaga", "cpu-criteo-asgd",
         "cpu-criteo-asaga", "tpu-criteo-check", "tpu-kdd2012",
         "cpu-kdd2012", "tpu-webspam-widest", "tpu-webspam-narrowest",
         "cpu-webspam", "tpu-one-constant-a-tile", "tpu-a-slot-under",
         "tpu-bf16", "tpu-f64"],
)
def test_the_scatter_chooser_answers_from_backend_and_shapes(
        monkeypatch, on_tpu, d, slots, walk, dtype, want):
    """``gradients.sparse_scatter_path`` at the four sparse cells' shapes
    (ISSUE 52): one number every caller holds, the mean run of slots a
    tile of ``g``, against ONE constant; since ISSUE 54 a walked sample
    (``walk``: the block it is walked in) is asked the same question
    (``tests/test_sparse_ragged.py`` holds webspam's eight widths)."""
    del walk  # what the sample's margins are walked in: no longer asked
    monkeypatch.setattr(gradients, "_on_tpu", lambda: on_tpu)
    assert gradients.sparse_scatter_path(d, slots, dtype) == want


def test_a_width_with_no_eight_row_view_keeps_todays_expression():
    """``d`` 47,236 through the helper: the element-wise gather's margins,
    to the bit, on whichever backend."""
    c, v, w = _sample(47_236, 300, 9, "padding")
    assert gradients.sparse_gather_path(w, c) == "elements"
    m = gradients.sparse_margins(c, v, w)
    np.testing.assert_array_equal(
        _bits(m), _bits(jnp.sum(v * w[c], axis=1)))


def test_where_the_chooser_says_rows8_the_helper_runs_it(
        monkeypatch, segments_interpreted):
    """The CPU can run the blocked form (it is plain ``jax.numpy``): with
    the chooser steered, the helper's jaxpr holds the blocked loop, and
    the step's gradient is the element-wise step's within the order of a
    margin's sum (and of a column's: 7,000 slots over one tile of 4,096
    columns are summed by sorted segments)."""
    _small_blocks(monkeypatch, 8)
    d, n = 4_096, 1_500
    rs = np.random.default_rng(4)
    cols = jnp.asarray(rs.integers(0, d, (n, 8)), jnp.int32)
    vals = jnp.asarray(rs.standard_normal((n, 8)), jnp.float32)
    y = jnp.asarray(rs.integers(0, 2, n), jnp.float32)
    w = jnp.asarray(0.1 * rs.standard_normal(d), jnp.float32)
    key = jax.random.PRNGKey(1)
    g1, _ = steps.make_sparse_asgd_worker_step(0.5, d, "logistic")(
        cols, vals, y, w, key)
    monkeypatch.setattr(gradients, "_on_tpu", lambda: True)
    step8 = steps.make_sparse_asgd_worker_step(0.5, d, "logistic")
    text = str(jax.make_jaxpr(step8)(cols, vals, y, w, key))
    assert "scan" in text and f"f32[8,8,{BLOCK}]" in text
    g8, _ = step8(cols, vals, y, w, key)
    assert np.max(np.abs(np.asarray(g8) - np.asarray(g1))) <= (
        1e-6 * np.max(np.abs(np.asarray(g1))))


def test_where_the_chooser_says_lanes128_the_helper_runs_it(
        monkeypatch, segments_interpreted):
    """A model over ``SPARSE_VMEM_BYTES`` (patched down to this test's
    size) on a TPU: the helper's jaxpr holds the lane-row loop, and the
    step's gradient is the element-wise step's within the order of a
    margin's sum."""
    monkeypatch.setattr(gradients, "SPARSE_LANES_BLOCK_SLOTS", BLOCK * 16)
    d, n = 40_004, 1_500
    rs = np.random.default_rng(4)
    cols = jnp.asarray(rs.integers(0, d, (n, 16)), jnp.int32)
    vals = jnp.asarray(rs.standard_normal((n, 16)), jnp.float32)
    y = jnp.asarray(rs.integers(0, 2, n), jnp.float32)
    w = jnp.asarray(0.1 * rs.standard_normal(d), jnp.float32)
    key = jax.random.PRNGKey(1)
    g1, _ = steps.make_sparse_asgd_worker_step(0.5, d, "logistic")(
        cols, vals, y, w, key)
    monkeypatch.setattr(gradients, "_on_tpu", lambda: True)
    # under VMEM and no multiple of 8: the element-wise gather, as ever
    assert steps.make_sparse_asgd_worker_step(0.5, d).gather_path(
        n, 16) == "elements"
    monkeypatch.setattr(gradients, "SPARSE_VMEM_BYTES", 4 * d - 1)
    step = steps.make_sparse_asgd_worker_step(0.5, d, "logistic")
    assert step.gather_path(n, 16) == "lanes128"
    text = str(jax.make_jaxpr(step)(cols, vals, y, w, key))
    assert "scan" in text and f"f32[{BLOCK},16,128]" in text
    gl, _ = step(cols, vals, y, w, key)
    assert np.max(np.abs(np.asarray(gl) - np.asarray(g1))) <= (
        1e-6 * np.max(np.abs(np.asarray(g1))))


# ------------------------------------------- every caller, the one helper

D, K = 48, 8


def _ell_shard(n):
    rs = np.random.default_rng(n)
    return (jnp.asarray(rs.integers(0, D, (n, K)), jnp.int32),
            jnp.asarray(rs.standard_normal((n, K)), jnp.float32),
            jnp.asarray(rs.standard_normal(n), jnp.float32))


@pytest.fixture()
def margins_spy(monkeypatch):
    calls = []
    real = gradients.sparse_margins

    def spy(c_sel, v_sel, w, walk=None):
        calls.append((tuple(c_sel.shape), tuple(w.shape)))
        return real(c_sel, v_sel, w, walk)

    # the steps call the name they imported; the residual its module's
    monkeypatch.setattr(steps, "sparse_margins", spy)
    monkeypatch.setattr(gradients, "sparse_margins", spy)
    return calls


def _lower(program, n):
    """Trace ``program`` over a shard of ``n`` rows (a shape no other
    test traces); returns the ``(c_sel, w)`` shapes it should hand on."""
    cols, vals, y = _ell_shard(n)
    w, alpha = jnp.zeros(D, jnp.float32), jnp.zeros(n, jnp.float32)
    key = jax.random.PRNGKey(0)
    cap = steps.sparse_step_capacity(0.25, n)
    shards, keys = [(cols, vals, y)], jnp.stack([key])
    if program == "asgd-step":
        steps.make_sparse_asgd_worker_step(0.25, D).lower(
            cols, vals, y, w, key)
    elif program == "asgd-step-logistic":
        steps.make_sparse_asgd_worker_step(0.25, D, "logistic").lower(
            cols, vals, y, w, key)
    elif program == "saga-step":
        steps.make_sparse_saga_worker_step(0.25, D).lower(
            cols, vals, y, w, alpha, key)
    elif program == "fused-asgd-rounds":
        steps.make_fused_asgd_rounds(
            0.1, 0.25, n, shards, rounds_per_call=2, sparse_d=D
        ).lower(w, jnp.float32(0.0), keys)
    elif program == "fused-saga-rounds":
        steps.make_fused_saga_rounds(
            0.1, 0.25, n, shards, rounds_per_call=2, sparse_d=D
        ).lower(w, w, (alpha,), keys)
    elif program == "dcn-step":
        idx = jnp.zeros(cap, jnp.int32)
        steps.make_saga_dcn_sparse_worker_step(D).lower(
            cols, vals, y, w, idx, jnp.zeros(cap, jnp.float32), jnp.int32(3))
    elif program == "residual":
        jax.clear_caches()  # jitted at module level: trace it anew
        gradients.sparse_residual.lower(cols, vals, y, w)
        return (n, K), (D,)
    return (cap, K), (D,)


@pytest.mark.parametrize(
    "program,n",
    [("asgd-step", 341), ("asgd-step-logistic", 342), ("saga-step", 343),
     ("fused-asgd-rounds", 344), ("fused-saga-rounds", 345),
     ("dcn-step", 346), ("residual", 347)])
def test_every_sparse_program_takes_its_margins_from_the_one_helper(
        monkeypatch, margins_spy, program, n):
    # no program indexes the model itself: ``w[...]`` on a tracer of the
    # model's shape is the helper's, inside the real ``sparse_margins``
    want = _lower(program, n)
    assert margins_spy == [want], (program, margins_spy)


# ------------------------------------------------------ the engine's record

def _cfg(**kw):
    base = dict(
        num_workers=4, num_iterations=12, gamma=0.5, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.7, printer_freq=4, seed=5,
        calibration_iters=4, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


@pytest.mark.parametrize("solver", [ASGD, ASAGA], ids=["asgd", "asaga"])
def test_an_engine_run_says_which_gather_it_ran(solver):
    ds = SparseShardedDataset.generate_on_device(
        4_099, 512, 12, 4, jax.devices()[:1], seed=7, noise=0.01)
    engine = solver(ds, None, _cfg(), devices=jax.devices()[:1])
    res = engine.run()
    assert res.accepted == 12
    assert res.extras["sparse_gather_path"] == "elements"  # the CPU's
    assert "dense_step_path" not in res.extras


def test_the_record_is_the_choosers_answer_for_the_steps_own_shapes(
        monkeypatch):
    """What the solver records is what the chooser says of the arrays the
    step will hand it: the packed capacity x the LIVE width (12 of the 16
    slots a row is stored in, since ISSUE 38), the model's ``(d,)``
    float32."""
    asked = []
    real = gradients.sparse_gather_path

    def chooser(w, c_sel):
        asked.append((tuple(w.shape), w.dtype, tuple(c_sel.shape)))
        return "rows8" if len(asked) == 1 else real(w, c_sel)

    monkeypatch.setattr(steps, "sparse_gather_path", chooser)
    ds = SparseShardedDataset.generate_on_device(
        4_099, 512, 12, 4, jax.devices()[:1], seed=7, noise=0.01)
    engine = ASGD(ds, None, _cfg(), devices=jax.devices()[:1])
    cap = steps.sparse_step_capacity(0.3, 1025)
    assert asked == [((512,), jnp.float32, (cap, 12))]
    assert engine._programs.extras["sparse_gather_path"] == "rows8"


def test_a_dense_run_records_no_sparse_gather():
    from asyncframework_tpu.data import make_regression

    X, y, _ = make_regression(512, 16, seed=3)
    res = ASGD(X, y, _cfg(gamma=0.3, batch_rate=0.2),
               devices=jax.devices()[:1]).run()
    assert "sparse_gather_path" not in res.extras
    assert res.extras["dense_step_path"] == "two_products"
