"""ASAGA's accept path takes the step's own ``g`` for the table delta where
the worker's history slice is the one the step read, and pays the exact
delta (a second read of the shard) where it is not (``ASAGA.run``).

The schedules here are made deterministic: the submitter is held until
every result it submitted has been merged (no natural overlap:
``serialised``, ``conftest.py``), and an
overlap is then put where the test wants it, by a ``_make_task`` on the
instance that hands a task the slice its worker's PREVIOUS task captured
(what a task made before its worker's last commit holds).  The guarantee
under test is the one every ASAGA run gives: ``alpha_bar`` is the mean
history gradient of the table, here against float64 on the host.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.data import (
    SparseShardedDataset,
    densify,
    make_sparse_regression,
)
from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.solvers import ASAGA, SolverConfig

N, D, NW, B, SEED = 2048, 32, 4, 0.2, 13
KINDS = pytest.mark.parametrize("kind", ["dense", "padded-ell"])
#: ``alpha_bar`` off the table's mean over ``max |X^T y / n|``: f32 sums on
#: the program's side, 1e-7 to 3e-7 measured here; ``delta = g`` under one
#: overlapped task a worker reads 1e-2 and more (the control below)
DRIFT_TOL = 5e-6


def _cfg(**kw):
    base = dict(num_workers=NW, num_iterations=80, gamma=0.3, taw=2**31 - 1,
                batch_rate=B, bucket_ratio=0.7, printer_freq=1000, coeff=0.0,
                seed=SEED, run_timeout_s=120.0)
    base.update(kw)
    return SolverConfig(**base)


def _solver(kind, devices, **cfg):
    """``(solver, rows)``: an ASAGA solver over seeded data on ``devices``
    and each shard's rows on the host, float64."""
    if kind == "dense":
        ds = ShardedDataset.generate_on_device(
            N, D, NW, devices, seed=SEED, noise=0.01)
        rows = [(np.asarray(ds.shard(w).X, np.float64),
                 np.asarray(ds.shard(w).y, np.float64)) for w in range(NW)]
    else:
        parts = make_sparse_regression(N, D, 0.2, SEED)
        ds = SparseShardedDataset(*parts, D, NW, devices)
        X, y = densify(ds)
        X, y = X.astype(np.float64), y.astype(np.float64)
        bounds = [(ds.shard(w).start, ds.shard(w).start + ds.shard(w).size)
                  for w in range(NW)]
        rows = [(X[a:b], y[a:b]) for a, b in bounds]
    return ASAGA(ds, None, _cfg(**cfg), devices=devices), rows


def _drift(res, rows):
    """``max |alpha_bar - X^T alpha / n|`` over ``max |X^T y / n|``, float64."""
    mean = sum(X.T @ np.asarray(res.extras["alpha"][w], np.float64)
               for w, (X, _y) in enumerate(rows)) / N
    unit = np.max(np.abs(sum(X.T @ y for X, y in rows) / N))
    ab = np.asarray(res.extras["alpha_bar"], np.float64)
    return float(np.max(np.abs(ab - mean)) / unit)


def _count_deltas(solver):
    """Calls of the table delta on the updater's thread (the warm-up and
    ``history_drift`` call the same executable from the caller's)."""
    calls = []
    real = solver._table_delta

    def counting(*args):
        if threading.current_thread().name == "saga-updater":
            calls.append(1)
        return real(*args)

    solver._table_delta = counting
    return calls


def _overlap(solver, honest=True):
    """Every second task of a worker gets the slice (and its commit count)
    that the worker's task before it captured: a task made before its
    worker's last commit.  Not ``honest``: the stale slice under the
    CURRENT count, which makes the updater take ``delta = g`` blindly, the
    reference drivers' rule.  Returns the list of stale tasks made."""
    real = solver._make_task
    last, made, stale = {}, {}, []

    def make_task(wid, w_pub, key, alpha_slice, slice_commits, *rest):
        made[wid] = made.get(wid, 0) + 1
        read, count = alpha_slice, slice_commits
        if made[wid] % 2 == 0:
            read, count = last[wid]
            stale.append(wid)
        last[wid] = (alpha_slice, slice_commits)
        return real(wid, w_pub, key, read,
                    count if honest else slice_commits, *rest)

    solver._make_task = make_task
    return stale


def test_the_sparse_steps_g_is_its_table_delta_on_an_unchanged_slice():
    """What lets the padded-ELL accept path take the same shortcut as the
    dense one (whose case is ``test_asaga_reference``'s): ``g`` and the
    exact delta against the slice the step read are one product, equal bit
    for bit and to float64 within f32 rounding; against a slice that moved
    on the delta is another vector."""
    solver, rows = _solver("padded-ell", jax.devices()[:1])
    shard, (X, y) = solver.ds.shard(0), rows[0]
    rs = np.random.default_rng(3)
    w = rs.standard_normal(D).astype(np.float32)
    alpha = rs.standard_normal(len(y)).astype(np.float32)
    g, diff, idx, valid, c_sel, v_sel, _key = solver._step(
        shard.cols, shard.vals, shard.y, w, alpha, jax.random.PRNGKey(5))
    delta = solver._table_delta(c_sel, v_sel, diff, jnp.asarray(alpha), idx)
    assert np.array_equal(np.asarray(g), np.asarray(delta))
    mask = np.zeros(len(y))
    mask[np.asarray(idx)[np.asarray(valid) > 0]] = 1.0
    assert mask.sum() > 0.1 * len(y)
    want = X.T @ (mask * (X @ w.astype(np.float64) - y - alpha))
    assert np.max(np.abs(np.asarray(g) - want)) <= 5e-6 * np.max(np.abs(want))
    moved = solver._table_delta(
        c_sel, v_sel, diff, jnp.asarray(alpha + 1.0), idx)
    assert not np.array_equal(np.asarray(g), np.asarray(moved))


@KINDS
def test_without_overlap_every_accept_reuses_g(kind, serialised):
    solver, rows = _solver(kind, jax.devices()[:1])
    deltas = _count_deltas(solver)
    res = solver.run()
    assert res.accepted == 80
    assert res.extras["history_reused"] == 80
    assert res.extras["history_recomputed"] == 0
    assert deltas == []  # the shard was never read a second time
    assert _drift(res, rows) <= DRIFT_TOL
    assert 0.0 <= res.extras["history_drift"] <= DRIFT_TOL


@KINDS
def test_the_standing_sample_pays_the_exact_delta_on_a_slice_that_stands(
        kind, serialised, monkeypatch):
    """Every ``EXACT_DELTA_EVERY``-th accept (over padded ELL, whose steps
    are longer and whose windows hold fewer accepts, every
    ``EXACT_SPARSE_DELTA_EVERY``-th) takes the exact side with no overlap
    at all (what keeps the executable in every profiler window on the
    chip), and the table's mean is kept as on the other side.  Each
    storage reads its own period and not the other's."""
    from asyncframework_tpu.solvers import asaga

    # more than a run of this file makes
    assert asaga.EXACT_DELTA_EVERY == 256
    assert asaga.EXACT_SPARSE_DELTA_EVERY == 128
    mine, other = ("EXACT_DELTA_EVERY", "EXACT_SPARSE_DELTA_EVERY")[
        ::1 if kind == "dense" else -1]
    monkeypatch.setattr(asaga, mine, 16)
    monkeypatch.setattr(asaga, other, 7)
    solver, rows = _solver(kind, jax.devices()[:1])
    deltas = _count_deltas(solver)
    res = solver.run()
    assert res.accepted == 80
    assert res.extras["history_recomputed"] == 5 == len(deltas)
    assert res.extras["history_reused"] == 75
    assert _drift(res, rows) <= DRIFT_TOL


@KINDS
def test_an_overlapped_task_pays_the_exact_delta(kind, serialised):
    solver, rows = _solver(kind, jax.devices()[:1])
    deltas = _count_deltas(solver)
    stale = _overlap(solver)
    res = solver.run()
    assert res.accepted == 80
    # every submitted task was merged and accepted: the stale ones, and
    # only they, were recomputed
    assert 30 <= len(stale) <= 40
    assert res.extras["history_recomputed"] == len(stale) == len(deltas)
    assert res.extras["history_reused"] == 80 - len(stale)
    assert _drift(res, rows) <= DRIFT_TOL
    assert res.extras["history_drift"] <= DRIFT_TOL


@KINDS
def test_an_overlapped_task_pays_the_exact_delta_in_a_folded_drain(
        kind, serialised, held_updater):
    """The same schedule under a backlog (ISSUE 60): the updater wakes to
    a whole cohort and applies it in one dispatch, and the stale tasks,
    and only they, are recomputed, each against the slice at ITS commit."""
    solver, rows = _solver(kind, jax.devices()[:1])
    deltas = _count_deltas(solver)
    stale = _overlap(solver)
    held_updater(NW)
    res = solver.run()
    assert res.accepted == 80
    assert 30 <= len(stale) <= 40
    assert res.extras["history_recomputed"] == len(stale) == len(deltas)
    assert res.extras["history_reused"] == 80 - len(stale)
    assert 0 < res.extras["apply_dispatches"] < 40  # drains were folded
    assert _drift(res, rows) <= DRIFT_TOL
    assert res.extras["history_drift"] <= DRIFT_TOL


@KINDS
def test_the_same_schedule_with_g_for_every_delta_loses_the_table(
        kind, serialised):
    """The control: the overlap above is one the invariant can see."""
    solver, rows = _solver(kind, jax.devices()[:1])
    stale = _overlap(solver, honest=False)
    res = solver.run()
    assert res.accepted == 80 and len(stale) >= 30
    assert res.extras["history_recomputed"] == 0
    assert _drift(res, rows) > 100 * DRIFT_TOL
    assert res.extras["history_drift"] > 100 * DRIFT_TOL


@KINDS
def test_a_shard_rehomed_under_a_result_in_flight_takes_the_exact_delta(
        kind, serialised, devices8):
    """Worker 1's shard moves to the other device after its third step ran
    and before its result is handled: the slot's count moved on with the
    slice, the payload is brought to the slice's new home, and that one
    accept recomputes."""
    solver, rows = _solver(kind, devices8[:2])
    deltas = _count_deltas(solver)
    hooks, made = [], []
    real_follows, real_make = solver._history_follows, solver._make_task

    def follows(*a):
        hooks.append(real_follows(*a))
        return hooks[-1]

    def make_task(wid, *a):
        fn = real_make(wid, *a)
        made.append(wid)
        if wid != 1 or made.count(1) != 3:
            return fn

        def moved_under():
            out = fn()
            shard = solver._recovery.move_shard(1, 0)
            hooks[-1](1, shard)
            return out

        return moved_under

    solver._history_follows, solver._make_task = follows, make_task
    home = solver._recovery.shard(1).device
    res = solver.run()
    assert solver._recovery.shard(1).device != home
    assert res.accepted == 80
    assert res.extras["history_recomputed"] == 1 == len(deltas)
    assert res.extras["history_reused"] == 79
    assert _drift(res, rows) <= DRIFT_TOL


def _sides_by_worker(solver):
    """For every result the updater merges, in order and by worker:
    whether its accept paid the exact delta.  (The updater counts a
    drain's flops, a result at a time by worker, in front of the drain's
    accept paths, and calls the table delta inside a result's own: both on
    the instance.  Whose path a delta belongs to is read from the slice it
    is taken against, the fourth operand in either payload: the table's
    slot of that worker, which ``_history_follows`` is handed.)"""
    sides, tables = {}, []
    real_flops, real_delta = solver._task_flops, solver._table_delta
    real_follows = solver._history_follows

    def task_flops(wid):
        sides.setdefault(wid, []).append(False)
        return real_flops(wid)

    def table_delta(*args):
        if threading.current_thread().name == "saga-updater":
            (wid,) = [w for w, a in tables[-1].items() if a is args[3]]
            # (one result of a worker is out at a time: its newest entry)
            sides[wid][-1] = True
        return real_delta(*args)

    def follows(run, alpha, commits):
        tables.append(alpha)
        return real_follows(run, alpha, commits)

    solver._task_flops, solver._table_delta = task_flops, table_delta
    solver._history_follows = follows
    return sides


@KINDS
def test_a_resumed_run_tells_the_sides_apart_from_the_restored_table(
        kind, serialised, tmp_path):
    """The counts start anew over the restored slices (nothing is in
    flight across a restart): every worker's first accept of the resumed
    run reuses its ``g``, and its overlapped tasks, every second one, are
    recomputed against the restored table.  How many tasks the submitter
    makes beyond the budget is a race of two threads (a cohort may be
    submitted between the hundredth accept and the loop's next look at
    ``k``), so ``len(stale)`` bounds the recomputed side and does not
    equal it."""
    ck = dict(checkpoint_dir=str(tmp_path / "ck"), checkpoint_freq=40)
    first, _rows = _solver(kind, jax.devices()[:1], num_iterations=40, **ck)
    assert first.run().extras["history_reused"] == 40
    solver, rows = _solver(kind, jax.devices()[:1], num_iterations=100, **ck)
    sides = _sides_by_worker(solver)
    stale = _overlap(solver)
    res = solver.run()
    assert res.accepted == 60  # the run's own accepts, from k = 40 on
    ex = res.extras
    assert ex["history_reused"] + ex["history_recomputed"] == res.accepted
    # a worker's accepts come in the order its tasks were made (one is out
    # at a time): fresh, stale, fresh, ... from the restored count 0 on
    assert sorted(sides) == list(range(NW))
    for paid in sides.values():
        assert len(paid) >= 2
        assert paid == [i % 2 == 1 for i in range(len(paid))]
    assert sum(map(len, sides.values())) == res.accepted
    assert sum(map(sum, sides.values())) == ex["history_recomputed"]
    assert 20 <= ex["history_recomputed"] <= len(stale)
    assert any(np.any(a != 0) for a in res.extras["alpha"].values())
    assert _drift(res, rows) <= DRIFT_TOL


@KINDS
def test_the_two_counters_sum_to_the_accepted_when_results_are_dropped(kind):
    """The engine as it runs (tasks overlap commits where they do), with a
    finite ``taw``: ASAGA's filter drops everything once ``k`` passes it,
    and a dropped result is counted on neither side."""
    solver, rows = _solver(kind, jax.devices()[:1], taw=30,
                           num_iterations=10_000, run_timeout_s=1.5)
    res = solver.run()
    assert res.dropped > 0 and res.accepted > 0
    ex = res.extras
    assert ex["history_reused"] + ex["history_recomputed"] == res.accepted
    assert _drift(res, rows) <= DRIFT_TOL


@KINDS
def test_a_warmed_solver_compiles_nothing_on_either_side(kind, serialised):
    solver, _rows = _solver(kind, jax.devices()[:1])
    _overlap(solver)
    solver.run()
    res = solver.run()
    assert res.extras["history_reused"] > 0
    assert res.extras["history_recomputed"] > 0
    assert res.extras["compiles_in_run"] == 0


def test_the_reader_gives_the_reused_share_of_the_accepts():
    from benchmark import manifest as manifest_mod

    reader = manifest_mod.Manifest().metric_reader("history_reuse")

    def run(**extras):
        return {"result": {"elapsed_s": 20.0, "extras": extras}}

    assert reader.read(run(history_reused=750, history_recomputed=250),
                       None) == 75.0
    assert reader.read(run(history_reused=0, history_recomputed=4),
                       None) == 0.0
    # the parent, an ASGD cell, a run that accepted nothing: left out
    assert reader.read(run(updater_history_s=1.0), None) is None
    assert reader.read(run(), {"modules": {}}) is None
    assert reader.read(run(history_reused=0, history_recomputed=0),
                       None) is None
    assert (reader.NAME, reader.UNIT, reader.MOVES) == (
        "history_reuse", "%", "updates_per_s")


def test_the_manifest_gives_the_reader_to_the_asaga_cell_alone():
    from benchmark import manifest as manifest_mod

    man = manifest_mod.Manifest()
    (entry,) = [m for m in man.metric_entries(
        "per_layer", "mnist8m-asaga.steady") if m["name"] == "history_reuse"]
    assert entry == {
        "name": "history_reuse", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "updates_per_s", "workloads": ["mnist8m-asaga.steady"]}
    for cell in ("mnist8m-asgd.steady", "mnist8m-asgd.steady-w32",
                 "mnist8m-f32-asgd.steady"):
        assert "history_reuse" not in {
            m["name"] for m in man.metric_entries("per_layer", cell)}
