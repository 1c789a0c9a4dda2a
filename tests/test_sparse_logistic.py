"""The sparse deployment's program side (ISSUE 32): the click-log
generator, the sparse worker step with the logistic link, the blocked
trajectory evaluation, and an engine run on them, each held to
``benchmark/reference.py`` (float32 ``jax.numpy`` at precision "highest",
no program code) on seeded data.

Tolerances.  ``STEP_TOL``: the step's gradient off the reference's, over
the reference's largest entry.  Both are float32 sums of the same products,
scatter-added in the order the slots are stored (the step sorted them by
column first until PR 33: a stable sort, so the same order of sums a
column): 0.0 seen here, 1e-7 a term to be expected where the order
differs.  A margin from a bf16 model reads 5e-5 to 1e-4 under the logistic
link (its slope of at most 1/4 damps the margin's error) and 5e-4 to 9e-4
under least squares, and a missing sigmoid 0.3: 1e-5 fails all of them
(tested below).  ``EVAL_TOL``:
the evaluation's objective off the reference's, relative: float32 sums of
at most 2,001 rows, 2e-7 seen; a bf16 model reads 2e-5 or more."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.data.sparse import SparseShardedDataset
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.ops import steps
from asyncframework_tpu.solvers import ASGD, SolverConfig, engine_loop
from benchmark import reference
from test_asgd_fold import Dispatches

STEP_TOL = 1e-5
EVAL_TOL = 2e-6
LOSSES = ["least_squares", "logistic"]
#: sha256 over every shard's cols, vals and y of ``generate_on_device(4099,
#: 512, 12, 4, seed=2_147_483_659, noise=0.01)`` at the parent commit
#: (f4c5eb7): the generator's defaults keep those bytes
PARENT_BYTES = "852cb2ed1060101ef8ddc273c712e5d0e9f1703b95b2a2c983dab4f32b763a2d"
CLICKS = dict(column_skew=1.0, unit_values=True,
              bernoulli_labels={"scale": 3.0, "positive_share": 0.256})
#: the two deployments' shapes at a test's size: criteo's (12 non-zeros in
#: 16 slots, a model of 512) and kdd2012's (ISSUE 37: 11 in 16, rare clicks,
#: a width that is no multiple of 8 and over 16 times the slots a step of
#: a 2,001-row shard samples)
SHAPES = {
    "criteo": dict(d=512, nnz=12),
    "kdd2012": dict(d=200_004, nnz=11, bernoulli_labels={
        "scale": 3.0, "positive_share": 0.045}),
}


def _digest(ds):
    h = hashlib.sha256()
    for w in range(ds.num_workers):
        sh = ds.shard(w)
        for a in (sh.cols, sh.vals, sh.y):
            h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def _click_log(n=8003, d=512, nnz=12, workers=4, seed=5, **kw):
    # 8003 rows: shards of 2001 and 2000 rows, neither a multiple of a block
    return SparseShardedDataset.generate_on_device(
        n, d, nnz, workers, jax.devices()[:1], seed=seed, noise=0.0,
        **{**CLICKS, **kw}
    )


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _to_bf16(a):
    return jax.lax.reduce_precision(jnp.asarray(a), 8, 7)


# ------------------------------------------------------------ the generator

def test_the_generators_defaults_give_the_parents_bytes():
    ds = SparseShardedDataset.generate_on_device(
        4099, 512, 12, 4, jax.devices()[:1], seed=2_147_483_659, noise=0.01)
    assert _digest(ds) == PARENT_BYTES
    spelled = SparseShardedDataset.generate_on_device(
        4099, 512, 12, 4, jax.devices()[:1], seed=2_147_483_659, noise=0.01,
        column_skew=0.0, unit_values=False, bernoulli_labels=None)
    assert _digest(spelled) == PARENT_BYTES


def test_a_click_log_has_unit_rows_zero_one_labels_and_the_stated_share():
    ds = _click_log(n=40_000)
    assert _digest(ds) == _digest(_click_log(n=40_000))  # same seed
    assert _digest(ds) != _digest(_click_log(n=40_000, seed=6))
    ys = np.concatenate([np.asarray(ds.shard(w).y) for w in range(4)])
    assert set(np.unique(ys)) == {0.0, 1.0}
    # 40,000 Bernoulli draws: sd 0.0022; the bias is fitted on shard 0
    assert abs(ys.mean() - 0.256) < 0.012
    for w in range(4):
        sh = ds.shard(w)
        cols, vals = np.asarray(sh.cols), np.asarray(sh.vals)
        assert cols.shape[1] == 16 and cols.dtype == np.int32
        assert np.all(vals[:, :12] == np.float32(12 ** -0.5))
        assert not vals[:, 12:].any() and not cols[:, 12:].any()
        assert cols.min() >= 0 and cols.max() < 512
    # a row's stored values have unit length: 12 x 1/12
    pins, f0 = reference.data_pins([ds.shard(w) for w in range(4)],
                                   "logistic")
    assert abs(pins["row_second_moment"] - 1.0) < 1e-6
    assert pins["nnz_per_row"] == 12
    assert abs(pins["label_second_moment"] - ys.mean()) < 1e-6  # y^2 = y
    assert abs(f0 - np.log(2.0)) < 1e-6


def test_columns_follow_the_closed_form_zipf_over_a_seeded_bijection():
    d, nnz, n = 100_000, 39, 20_000
    ds = SparseShardedDataset.generate_on_device(
        n, d, nnz, 2, jax.devices()[:1], seed=9, noise=0.0, **CLICKS)
    live = np.concatenate(
        [np.asarray(ds.shard(w).cols)[:, :nnz].ravel() for w in range(2)])
    counts = np.sort(np.bincount(live, minlength=d))[::-1]
    got = counts[:8] / live.size
    r = np.arange(8)
    law = np.log((2 * r + 3) / (2 * r + 1)) / np.log(2 * d + 1)
    # 780,000 slots: the hottest rank's share has sd 0.0003
    np.testing.assert_allclose(got, law, rtol=0.05)
    # Zipf's own 1 / ((r + 1) H_d): 9% off at the hottest rank, 1.5% from
    # the second on, as ``_zipf_ranks`` says
    zipf = 1.0 / ((r + 1) * np.sum(1.0 / np.arange(1, d + 1)))
    np.testing.assert_allclose(law[1:], zipf[1:], rtol=0.03)
    assert 1.05 < law[0] / zipf[0] < 1.12
    # the hot columns are scattered, not the first few
    hot = np.argsort(np.bincount(live, minlength=d))[::-1][:4]
    assert np.ptp(hot) > 1000
    # another seed, another bijection
    other = SparseShardedDataset.generate_on_device(
        n, d, nnz, 2, jax.devices()[:1], seed=10, noise=0.0, **CLICKS)
    other_hot = np.argmax(np.bincount(
        np.asarray(other.shard(0).cols)[:, :nnz].ravel(), minlength=d))
    assert other_hot != hot[0]
    # a row may hold its hot column more than once: padded ELL adds them up
    rows = np.asarray(ds.shard(0).cols)[:200, :nnz]
    assert any(len(set(row)) < nnz for row in rows)


@pytest.mark.parametrize("d", [512, 47_236, 1_000_000, 3_000_017])
def test_the_column_bijection_is_one_and_fits_32_bits(d):
    from asyncframework_tpu.data.sparse import _column_bijection
    import math

    mult, shift = _column_bijection(d, seed=2_147_483_659)
    assert math.gcd(mult, d) == 1 and 0 <= shift < d
    assert mult * (d - 1) + shift < 2**32
    if d <= 47_236:
        image = (np.arange(d, dtype=np.uint64) * mult + shift) % d
        assert len(np.unique(image)) == d


# ------------------------------------------------------ the step's gradient

def _sampled(key, batch_rate, rows):
    """The rows a step samples: its own draw, made again."""
    _next, sub = jax.random.split(key)
    return jax.random.bernoulli(sub, batch_rate, (rows,)).astype(jnp.float32)


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("batch_rate", [0.3, 1.0])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_sparse_step_matches_the_reference_on_its_own_sampled_rows(
        loss, batch_rate, shape):
    d = SHAPES[shape]["d"]
    # ragged shards, a hot column, duplicates in a row
    ds = _click_log(**SHAPES[shape])
    w = np.random.default_rng(2).standard_normal(d).astype(np.float32)
    step = steps.make_sparse_asgd_worker_step(batch_rate, d, loss)
    for wid in (0, 3):
        sh = ds.shard(wid)
        key = jax.random.PRNGKey(wid + 7)
        g, _key = step(sh.cols, sh.vals, sh.y, jnp.asarray(w), key)
        mask = _sampled(key, batch_rate, sh.size)
        assert batch_rate == 1.0 or 0.2 * sh.size < mask.sum() < 0.4 * sh.size
        want = reference.full_gradient(sh, w, d, loss, weights=mask)
        assert _rel(g, want) < STEP_TOL
        # what the tolerance is for: a bf16 model, and the other loss
        g16, _ = step(sh.cols, sh.vals, sh.y, _to_bf16(w), key)
        assert _rel(g16, want) > 3 * STEP_TOL
        other = [x for x in LOSSES if x != loss][0]
        assert _rel(g, reference.full_gradient(
            sh, w, d, other, weights=mask)) > 0.1


def test_least_squares_sparse_step_is_the_program_it_was():
    """The link is a trace-time choice: the default and the spelled-out
    least-squares step lower to the same text, which holds no sigmoid; the
    logistic one differs by that op (and its multiply by ``valid``).  Against
    the parent commit itself (f4c5eb7) the lowered text of this step at
    three shapes, and the compiled text less its source-line metadata, were
    the same (by hand, PR 32)."""
    args = (jnp.zeros((64, 8), jnp.int32), jnp.zeros((64, 8), jnp.float32),
            jnp.zeros(64, jnp.float32), jnp.zeros(32, jnp.float32),
            jax.random.PRNGKey(0))
    text = {
        name: make().lower(*args).as_text()
        for name, make in {
            "default": lambda: steps.make_sparse_asgd_worker_step(0.25, 32),
            "ls": lambda: steps.make_sparse_asgd_worker_step(
                0.25, 32, "least_squares"),
            "logistic": lambda: steps.make_sparse_asgd_worker_step(
                0.25, 32, "logistic"),
        }.items()
    }
    assert text["default"] == text["ls"]
    # the sigmoid lowers to an exponential; least squares has none
    assert "stablehlo.exponential" not in text["ls"]
    assert "stablehlo.exponential" in text["logistic"]
    with pytest.raises(ValueError, match="hinge"):
        steps.make_sparse_asgd_worker_step(0.25, 32, "hinge").lower(*args)


def test_fused_sparse_rounds_still_refuse_the_logistic_loss():
    # its parity test is least squares only: the raise stays (ISSUE 32)
    ds = _click_log(n=512, workers=2)
    shards = [(s.cols, s.vals, s.y) for s in ds.shards.values()]
    with pytest.raises(ValueError, match="least_squares only"):
        steps.make_fused_asgd_rounds(1.0, 0.1, 512, shards, loss="logistic",
                                     sparse_d=512)


# ------------------------------------------------- the blocked evaluation

@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("snapshots", [1, 8, 11])
@pytest.mark.parametrize("block_rows", [256, 2001, 4096])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_blocked_evaluation_matches_the_reference(
        monkeypatch, loss, snapshots, block_rows, shape):
    """Blocks that do not divide the shard (2,001 rows in blocks of 256:
    the last is clamped and masked), one block exactly, a block larger than
    the shard; 1 snapshot, one tile of 8, and 11 (a tile and a ragged
    one); each at both deployments' shapes."""
    monkeypatch.setattr(steps, "SPARSE_EVAL_BLOCK_ROWS", block_rows)
    d = SHAPES[shape]["d"]
    sh = _click_log(**SHAPES[shape]).shard(0)
    assert sh.size == 2001
    ev = steps.make_sparse_trajectory_loss_eval(loss)
    assert ev.blocks(sh.size) == -(-2001 // min(block_rows, 2001))
    assert ev.snapshots_per_call == 8
    rs = np.random.default_rng(snapshots)
    W = (rs.standard_normal((snapshots, d)) * 0.5).astype(np.float32)
    W[0] = 0.0
    got = np.asarray(ev(sh.cols, sh.vals, sh.y, jnp.asarray(W)),
                     np.float64) / sh.size
    want = np.array([reference.objective([sh], w, loss) for w in W])
    assert np.max(np.abs(got - want) / want) < EVAL_TOL
    if snapshots > 1:
        got16 = np.asarray(ev(sh.cols, sh.vals, sh.y, _to_bf16(W)),
                           np.float64) / sh.size
        assert np.max(np.abs(got16 - want) / want) > 10 * EVAL_TOL


def test_blocked_evaluation_makes_no_copy_of_the_shard(monkeypatch):
    """Per block one gather of ``(snapshots, K, rows)`` and nothing as
    large as the shard: the jaxpr's only whole-shard values are its
    arguments."""
    monkeypatch.setattr(steps, "SPARSE_EVAL_BLOCK_ROWS", 256)
    ev = steps.make_sparse_trajectory_loss_eval("logistic")
    jaxpr = jax.make_jaxpr(ev)(
        jnp.zeros((2001, 16), jnp.int32), jnp.zeros((2001, 16), jnp.float32),
        jnp.zeros(2001, jnp.float32), jnp.zeros((8, 512), jnp.float32))
    text = str(jaxpr)
    assert text.count("gather") >= 1
    made = [ln for ln in text.splitlines()
            if "2001,16]" in ln.split("=")[0] and "lambda" not in ln]
    assert not made, made


# --------------------------------------------------------- an engine run

def _cfg(**kw):
    base = dict(
        num_workers=4, num_iterations=40, gamma=1.0, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.7, printer_freq=3, seed=5,
        loss="logistic", calibration_iters=4, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


def test_an_asgd_run_on_a_click_log_is_the_serial_replay_of_its_updates(
        monkeypatch):
    """Every gradient the updater applied is the reference's gradient of
    the rows that task sampled at the model that task read, and the final
    model and every snapshot are the serial path's over those gradients."""
    d = 512
    ds = _click_log(d=d)
    cfg = _cfg()
    engine = ASGD(ds, None, cfg, devices=jax.devices()[:1])
    tasks = []  # (shard, w read, key, g) of every step of the run
    real_step = engine._step

    def step(cols, vals, y, w, key):
        g, new_key = real_step(cols, vals, y, w, key)
        tasks.append((int(y.shape[0]), cols, np.asarray(w),
                      np.asarray(key), np.asarray(g)))
        return g, new_key

    step.task_rows = real_step.task_rows
    engine._step = step
    spy = Dispatches(engine, monkeypatch)
    res = engine.run()
    assert res.accepted == 40 and spy.updates == 40
    by_bytes = {t[4].tobytes(): t for t in tasks}
    shard_of = {id(ds.shard(w).cols): ds.shard(w) for w in range(4)}
    par_recs = cfg.batch_rate * ds.n / cfg.num_workers
    w = np.zeros(d, np.float32)
    k = 0
    replay = [w.copy()]
    for _kind, gs, _k0, _w2, _k2 in spy.records:
        for g in gs:
            rows, cols, w_read, key, _g = by_bytes[g.tobytes()]
            sh = shard_of[id(cols)]
            mask = _sampled(jnp.asarray(key), cfg.batch_rate, rows)
            want = reference.full_gradient(sh, w_read, d, "logistic",
                                           weights=mask)
            assert _rel(g, want) < STEP_TOL
            lr = np.float32(cfg.gamma / np.sqrt(k / cfg.num_workers + 1.0))
            w = (w - np.float32(lr / par_recs) * want).astype(np.float32)
            k += 1
            replay.append(w.copy())
    # 40 float32 updates against float64 gradients rounded once each
    np.testing.assert_allclose(res.final_w, replay[-1], rtol=0, atol=2e-5)
    # the trajectory: the reference's objective of the replayed models
    shards = [ds.shard(i) for i in range(4)]
    at = [0] + [j * cfg.printer_freq + 1 for j in range(14)] + [40]
    assert len(res.trajectory) == len(at)
    for (_t, f), u in zip(res.trajectory, at):
        want = reference.objective(shards, replay[u], "logistic")
        assert abs(f - want) < 2e-5 * want
    assert res.trajectory[0][1] == pytest.approx(np.log(2.0), rel=1e-6)
    assert res.trajectory[-1][1] < 0.9 * np.log(2.0)
    # what the run says of itself (the benchmark's per-layer metrics)
    ex = res.extras
    cap = steps.sparse_step_capacity(0.3, 2001)
    assert ex["sparse_step_capacity"] == cap
    assert ex["sampled_slots_per_step"] == cap * 16
    assert ex["model_bytes"] == 4 * d
    assert ex["eval_snapshots"] == len(at) == 16
    assert ex["eval_blocks"] == 4 * 2  # 4 shards, one block, two calls of 8
    assert ex["eval_slots"] == 2 * 8003 * 16  # every row, twice
    assert 0 < ex["trajectory_eval_s"] < 30


def test_a_dense_run_counts_its_evaluation_too():
    from asyncframework_tpu.data import make_regression

    X, y, _ = make_regression(1024, 16, seed=3)
    res = ASGD(X, y, _cfg(loss="least_squares", gamma=0.3, batch_rate=0.2,
                          num_iterations=12),
               devices=jax.devices()[:1]).run()
    ex = res.extras
    assert ex["eval_snapshots"] == len(res.trajectory)
    assert ex["eval_blocks"] == 4 and "eval_slots" not in ex
    assert ex["model_bytes"] == 64 and "sparse_step_capacity" not in ex
    assert ex["trajectory_eval_s"] > 0


def test_trajectory_eval_is_a_span_of_a_traced_run_and_a_work_stage():
    assert trace.TRAJECTORY_EVAL == "trajectory.eval"
    assert trace.TRAJECTORY_EVAL in trace.WORK_STAGES
    trace.reset_aggregator()
    ds = _click_log(n=2048)
    res = ASGD(ds, None, _cfg(num_iterations=12, trace_sample=0.5),
               devices=jax.devices()[:1]).run()
    stages = trace.aggregator().snapshot()["stages_ms"]
    assert stages[trace.TRAJECTORY_EVAL]["count"] == 1
    # one span a run, as long as the counter says (the counter also holds
    # the stack of the snapshots and the division)
    assert stages[trace.TRAJECTORY_EVAL]["p50"] <= (
        res.extras["trajectory_eval_s"] * 1e3 + 1.0)
    # untraced: the span call gets no handle and records nothing
    trace.reset_aggregator()
    ASGD(ds, None, _cfg(num_iterations=12), devices=jax.devices()[:1]).run()
    assert trace.TRAJECTORY_EVAL not in (
        trace.aggregator().snapshot()["stages_ms"])


# ------------------------------------- the trajectory's memory (ISSUE 37)

def _evaluated_all_at_once(engine, handles):
    """The evaluation as it was until PR 37: EVERY call's stack built
    first, then shard by shard over all of them."""
    per_call = engine._eval.snapshots_per_call
    stacks = []
    for lo in range(0, len(handles), per_call):
        group = handles[lo:lo + per_call]
        group += handles[:1] * (per_call - len(group))
        stacks.append(jnp.stack(group))
    totals = np.zeros(len(stacks) * per_call, np.float64)
    for wid in range(engine.cfg.num_workers):
        sh = engine.ds.shard(wid)
        totals += np.concatenate([
            np.asarray(engine._eval(sh.cols, sh.vals, sh.y, W), np.float64)
            for W in stacks])
    return totals[:len(handles)] / engine.ds.n


@pytest.mark.parametrize("shape", list(SHAPES))
def test_seventeen_snapshots_are_evaluated_a_stack_at_a_time_to_the_bit(
        monkeypatch, shape):
    """Three calls of eight (the last padded with the first snapshot): the
    trajectory is what the all-at-once evaluation gave, bit for bit, and
    when a call's stack is built the one before it is gone."""
    import gc
    import weakref

    d = SHAPES[shape]["d"]
    ds = _click_log(**SHAPES[shape])
    engine = ASGD(ds, None, _cfg(), devices=jax.devices()[:1])
    rs = np.random.default_rng(17)
    handles = [jnp.asarray((0.1 * j * rs.standard_normal(d)).astype(
        np.float32)) for j in range(17)]
    want = _evaluated_all_at_once(engine, list(handles))
    stacks, alive_at_build = [], []
    real_stack = jnp.stack

    def stack(arrays, *a, **kw):
        gc.collect()
        alive_at_build.append(sum(r() is not None for r in stacks))
        out = real_stack(arrays, *a, **kw)
        stacks.append(weakref.ref(out))
        return out

    monkeypatch.setattr(engine_loop.jnp, "stack", stack)
    counters = {}
    traj = engine._evaluate_trajectory(
        [(float(j), h) for j, h in enumerate(handles)], None, counters)
    monkeypatch.undo()
    assert [f for _t, f in traj] == [float(f) for f in want]
    assert [t for t, _f in traj] == [float(j) for j in range(17)]
    assert alive_at_build == [0, 0, 0]  # never two stacks
    gc.collect()
    assert not any(r() is not None for r in stacks)
    assert counters["eval_calls"] == 3 and counters["eval_stack_rows"] == 8
    assert counters["eval_snapshots"] == 17
    assert counters["eval_blocks"] == 3 * 4  # one block a shard a call
    assert counters["eval_slots"] == 3 * 8003 * 16


def test_the_trajectory_eval_span_carries_its_calls(monkeypatch):
    spans = []
    real = trace.UpdateTrace.add

    def add(self, stage, *args, **kw):
        span = real(self, stage, *args, **kw)
        if stage == trace.TRAJECTORY_EVAL:
            spans.append(span)
        return span

    monkeypatch.setattr(trace.UpdateTrace, "add", add)
    ds = _click_log(n=2048)
    res = ASGD(ds, None, _cfg(num_iterations=30, trace_sample=0.5),
               devices=jax.devices()[:1]).run()
    (ev,) = spans
    # w = 0, after updates 1, 4, ..., 28, the final model: 12 snapshots
    assert ev.batch == len(res.trajectory) == 12
    assert ev.calls == res.extras["eval_calls"] == 2
    assert trace.Span.from_wire(ev.to_wire()).calls == 2
