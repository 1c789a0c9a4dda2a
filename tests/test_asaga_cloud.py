"""ASAGA under the cloud tail (ISSUE 58): the history table keeps its mean
whatever was late, and the engine counts how old a worker's slice is when
it is replaced, by class of worker (``engine/straggler.py: DelayModel.
book_history_age``, booked in ASAGA's updater; ``TrainResult.extras``'s
four ``history_age_*`` integers) against ``benchmark/
reference_history_age.py``, the same count restated from a run's accept
order with no program code.  Counts and identities, never a rate."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_history_age, reference_delay  # noqa: E402
from benchmark import reference_history_age, reference_saga  # noqa: E402

from asyncframework_tpu.data.sharded import ShardedDataset  # noqa: E402
from asyncframework_tpu.data.sparse import SparseShardedDataset  # noqa: E402
from asyncframework_tpu.engine.straggler import DelayModel  # noqa: E402
from asyncframework_tpu.metrics import trace  # noqa: E402
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig  # noqa: E402
from asyncframework_tpu.solvers import engine_loop  # noqa: E402

N, D, NW, SEED = 6144, 32, 12, 29
ELL, NNZ = "ell", 6
BOTH = pytest.mark.parametrize("storage", [jnp.float32, ELL],
                               ids=["dense", "ell"])
AGES = ("history_age_late_sum", "history_age_late_n",
        "history_age_healthy_sum", "history_age_healthy_n")
#: twelve workers: 0 of the long tail, 4 and 8 of the normal class
LATE = {0: "long_tail", 4: "normal", 8: "normal"}
DRIFT_TOL = 2e-6


def _cfg(**kw):
    base = dict(num_workers=NW, num_iterations=420, gamma=0.05,
                taw=2**31 - 1, batch_rate=0.2, bucket_ratio=0.7,
                printer_freq=1000, coeff=-1.0, seed=SEED,
                calibration_iters=60, run_timeout_s=120.0)
    base.update(kw)
    return SolverConfig(**base)


def _solve(storage, **cfg):
    devices = jax.devices()[:1]
    if storage is ELL:
        ds = SparseShardedDataset.generate_on_device(
            N, D, NNZ, NW, devices, seed=SEED, noise=0.01)
    else:
        ds = ShardedDataset.generate_on_device(
            N, D, NW, devices, seed=SEED, noise=0.01, dtype=storage)
    return ds, ASAGA(ds, None, _cfg(**cfg), devices=devices)


@pytest.fixture()
def heard(monkeypatch):
    """One listener a run made in the test, on the run's own event bus:
    ``benchmark/check_history_age.py``'s patch, undone after the test; the
    newest run's is the list's last, and ``.order`` its accept order."""
    monkeypatch.setattr(engine_loop, "RunInstruments",
                        engine_loop.RunInstruments)
    monkeypatch.setattr(engine_loop, "DelayModel", engine_loop.DelayModel)
    return check_history_age._hear_the_runs(drop_class=False)


# --------------------------------------------- the model and the reference
def test_the_reference_counts_an_age_behind_the_calibration_alone():
    order = [0, 1, 2, 1, 0, 2, 1, 1, 0]
    #        -  -  -  2  4  3  3  1  4    (the updates since its last)
    assert reference_history_age.ages(order, 4) == [
        (0, 4), (2, 3), (1, 3), (1, 1), (0, 4)]
    assert reference_history_age.ages(order, 1) == [
        (1, 2), (0, 4), (2, 3), (1, 3), (1, 1), (0, 4)]
    # a previous commit may lie in front of the calibration's end; a
    # worker's first accept has no age wherever it falls
    assert reference_history_age.ages([0, 1, 2, 3, 0], 3) == [(0, 4)]
    for never in (0, None):
        assert reference_history_age.ages(order, never) == []
    got = reference_history_age.account(order, {0}, 4)
    assert got == {"history_age_late_sum": 8, "history_age_late_n": 2,
                   "history_age_healthy_sum": 7, "history_age_healthy_n": 3}
    assert reference_history_age.account(order, {0: "normal"}, 4) == got
    assert set(reference_history_age.account(order, {0}, None).values()) == {0}
    spread = reference_history_age.by_class(order, {0: "long_tail"}, 4)
    assert spread["long_tail"] == {"count": 2, "mean": 4.0, "p50": 4,
                                   "p95": 4, "max": 4}
    assert spread["healthy"] == {"count": 3, "mean": 7 / 3, "p50": 3,
                                 "p95": 3, "max": 3}
    assert "normal" not in spread


@pytest.mark.parametrize("n", [4, 12, 32])
def test_the_model_books_an_age_to_the_class_of_its_worker(n):
    late = reference_delay.late_workers(n)
    model = DelayModel(-1.0, n, seed=3)
    for wid in range(n):
        assert model.worker_class(wid) == late.get(wid, "healthy"), wid
    # before the calibration's end nothing is booked
    assert model.book_history_age(0, 7) is None
    assert model.book_history_age(1, 7) is None
    got = model.account([0] * n)
    assert {k: got[k] for k in AGES} == dict.fromkeys(AGES, 0)
    model.calibrate(5.0, at_update=100 * n, at_s=1.0)
    booked = [(w, 3 * w + 1) for w in range(n)] + [(0, 40), (1, 2)]
    for wid, age in booked:
        assert model.book_history_age(wid, age) == late.get(wid, "healthy")
    got = model.account([0] * n)
    assert got["history_age_late_sum"] == sum(
        a for w, a in booked if w in late)
    assert got["history_age_late_n"] == sum(1 for w, _ in booked if w in late)
    assert got["history_age_healthy_sum"] == sum(
        a for w, a in booked if w not in late)
    assert got["history_age_healthy_n"] == sum(
        1 for w, _ in booked if w not in late)
    assert all(isinstance(got[k], int) for k in AGES)


def test_a_model_that_is_off_books_nothing_and_the_controlled_delay_marks_one():
    off = DelayModel(0.0, 8, seed=1)
    off.calibrate(13.0, at_update=801, at_s=1.9)  # the engine always does
    assert off.book_history_age(0, 9) is None
    assert {off.worker_class(w) for w in range(8)} == {"healthy"}
    assert {k: off.account([1] * 8)[k] for k in AGES} == dict.fromkeys(AGES, 0)
    one = DelayModel(2.0, 8, seed=1)
    one.calibrate(13.0, at_update=801, at_s=1.9)
    assert one.book_history_age(0, 9) == "normal"
    assert one.book_history_age(5, 4) == "healthy"
    got = one.account([1] * 8)
    assert [got[k] for k in AGES] == [9, 1, 4, 1]


# ------------------------------------------------ an engine run under the tail
@BOTH
def test_the_table_keeps_its_mean_and_the_ages_are_the_replays(
        storage, heard):
    ds, solver = _solve(storage)
    res = solver.run()
    extras, order = res.extras, heard[-1].order
    assert res.accepted == 420 == len(order)
    assert reference_delay.late_workers(NW) == LATE
    # the invariant, under the tail: alpha_bar is the mean of the table
    shards = [ds.shard(w) for w in range(NW)]
    alphas = [extras["alpha"][w] for w in range(NW)]
    drift = reference_saga.history_drift(
        shards, alphas, extras["alpha_bar"], N, block_rows=512,
        **({"d": D} if storage is ELL else {}))
    assert 0.0 <= drift <= DRIFT_TOL
    assert 0.0 <= extras["history_drift"] <= DRIFT_TOL
    # the delay account adds up over the accept order
    at = extras["delay_calibrated_at_update"]
    assert 60 <= at < 420 and extras["delayed_tasks"] > 0
    from_late = sum(1 for w in order if w in LATE)
    assert extras["accepted_from_stragglers"] == from_late > 0
    assert from_late + sum(1 for w in order if w not in LATE) == res.accepted
    assert extras["accepted_after_calibration"] == 420 - at
    # the four integers are the replay of the run's own accept order
    want = reference_history_age.account(order, LATE, at)
    assert {k: extras[k] for k in AGES} == want
    assert want["history_age_late_n"] > 0 < want["history_age_healthy_n"]
    assert all(isinstance(extras[k], int) for k in AGES)
    # every accept behind the calibration's end of a worker seen before
    counted = want["history_age_late_n"] + want["history_age_healthy_n"]
    assert 420 - at - NW <= counted <= 420 - at
    # a late worker's slice is the older one, on the mean
    assert (want["history_age_late_sum"] * want["history_age_healthy_n"]
            > want["history_age_healthy_sum"] * want["history_age_late_n"])


@BOTH
def test_under_a_backlog_the_ages_are_still_the_replays(
        storage, heard, held_updater):
    """The updater folds its drains (ISSUE 60): an age is reckoned from the
    update's OWN index, not from its drain's first, so the four integers
    are the replay of the accept order whatever the drains' sizes were."""
    ds, solver = _solve(storage)
    held_updater(NW // 2)
    res = solver.run()
    extras, order = res.extras, heard[-1].order
    assert res.accepted == 420 == len(order)
    assert extras["apply_dispatches"] < 420 // 2  # drains were folded
    at = extras["delay_calibrated_at_update"]
    assert 60 <= at < 420
    want = reference_history_age.account(order, LATE, at)
    assert {k: extras[k] for k in AGES} == want
    assert want["history_age_late_n"] > 0 < want["history_age_healthy_n"]
    shards = [ds.shard(w) for w in range(NW)]
    alphas = [extras["alpha"][w] for w in range(NW)]
    drift = reference_saga.history_drift(
        shards, alphas, extras["alpha_bar"], N, block_rows=512,
        **({"d": D} if storage is ELL else {}))
    assert 0.0 <= drift <= DRIFT_TOL


@BOTH
def test_a_run_at_coeff_zero_reports_zeros_and_no_other_extra_moves(storage):
    _ds, late = _solve(storage, num_iterations=200)
    _ds, steady = _solve(storage, num_iterations=200, coeff=0.0)
    under, quiet = late.run().extras, steady.run().extras
    assert {k: quiet[k] for k in AGES} == dict.fromkeys(AGES, 0)
    assert quiet["straggler_workers"] == 0 and quiet["delayed_tasks"] == 0
    # the same keys either way: the four are the only ones this adds, and
    # a run with nobody late has them all the same
    # (but for the non-zero pairs of who waited behind whom at a lock)
    def named(extras):
        return {k for k in extras if "_behind_" not in k}

    assert named(quiet) == named(under) and set(AGES) <= set(quiet)
    assert quiet["history_reused"] + quiet["history_recomputed"] == 200


@BOTH
def test_a_run_that_ends_inside_its_calibration_reports_zeros(storage):
    _ds, solver = _solve(storage, num_iterations=48, calibration_iters=400)
    res = solver.run()
    assert res.accepted == 48
    assert {k: res.extras[k] for k in AGES} == dict.fromkeys(AGES, 0)
    assert res.extras["straggler_workers"] == 3
    assert res.extras["delay_calibrated_at_update"] == 0


@BOTH
def test_run_sync_books_no_age(storage):
    _ds, solver = _solve(storage, num_iterations=12, calibration_iters=3)
    res = solver.run_sync()
    assert res.extras["delayed_tasks"] > 0  # the tail was on
    assert {k: res.extras[k] for k in AGES} == dict.fromkeys(AGES, 0)


def test_asgd_keeps_no_history_and_reports_zeros():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2048, 16)).astype(np.float32)
    y = X @ rng.normal(size=16).astype(np.float32)
    res = ASGD(X, y, _cfg(gamma=0.4, num_iterations=300)).run()
    assert res.extras["delayed_tasks"] > 0
    assert {k: res.extras[k] for k in AGES} == dict.fromkeys(AGES, 0)


# ------------------------------------------------------------------ the span
@BOTH
def test_the_history_span_carries_the_class_and_the_age(storage, tmp_path):
    """Every update sampled: the ``merge.history`` spans in start order ARE
    the accept order, and each span of a counted accept carries its
    worker's class and the age the replay gives; the others carry none."""
    log = tmp_path / "cloud.jsonl"
    _ds, solver = _solve(storage, trace_sample=1.0, event_log=str(log))
    res = solver.run()
    spans = sorted((sp for sp in trace.load_trace_events(log)[0]
                    if sp.stage == trace.MERGE_HISTORY),
                   key=lambda sp: sp.start_ms)
    assert len(spans) == res.accepted == 420
    order = [sp.worker_id for sp in spans]
    at = res.extras["delay_calibrated_at_update"]
    assert {k: res.extras[k] for k in AGES} == (
        reference_history_age.account(order, LATE, at))
    seen, counted = set(), []
    for i, sp in enumerate(spans):
        if i >= at and sp.worker_id in seen:
            counted.append((sp.worker_id, sp.history_age))
            assert sp.delay_class == LATE.get(sp.worker_id, "healthy")
        else:
            assert sp.history_age is None and sp.delay_class is None
        seen.add(sp.worker_id)
    assert counted == reference_history_age.ages(order, at)
    assert {"healthy", "normal", "long_tail"} == {
        sp.delay_class for sp in spans if sp.delay_class}
    # the event a span rides to the log keeps both
    ev = trace.span_event(trace.Span(
        stage=trace.MERGE_HISTORY, trace_id="t", span_id="s", parent_id="p",
        worker_id=4, model_version=9, start_ms=1.0, dur_ms=0.2,
        delay_class="normal", history_age=81), 2.0)
    assert (ev.delay_class, ev.history_age) == ("normal", 81)
    wire = trace.Span.from_wire(trace.Span(
        stage=trace.MERGE_HISTORY, trace_id="t", span_id="s", parent_id="p",
        worker_id=4, model_version=9, start_ms=1.0, dur_ms=0.2,
        history_age=81).to_wire())
    assert wire.history_age == 81 and wire.delay_class is None


@BOTH
def test_a_steady_traced_run_puts_nothing_on_the_span(storage, tmp_path):
    log = tmp_path / "steady.jsonl"
    _ds, solver = _solve(storage, num_iterations=96, coeff=0.0,
                         trace_sample=1.0, event_log=str(log))
    solver.run()
    spans = [sp for sp in trace.load_trace_events(log)[0]
             if sp.stage == trace.MERGE_HISTORY]
    assert len(spans) == 96
    assert {(sp.delay_class, sp.history_age) for sp in spans} == {(None, None)}
