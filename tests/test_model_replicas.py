"""The model lives on every chip (ISSUE 47): where a run's shards lie on
several devices ASGD's updater applies every drain to a replica a device, a
task's step reads the replica on its shard's device, and nothing is copied
in front of a step; on ONE device the run makes the calls it always made.

CPU, four of the eight host devices, eight workers (two shards a device, as
the four-chip cell), ``ASGD.run``."""

import jax
import numpy as np
import pytest

from test_asgd_fold import _close, _serial_replay

from asyncframework_tpu.data import make_regression
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.solvers import ASGD, SolverConfig, engine_loop
from asyncframework_tpu.solvers.engine_loop import ModelReplicas


@pytest.fixture(scope="module")
def problem():
    X, y, _ = make_regression(2048, 16, seed=11)
    return X, y


@pytest.fixture(scope="module")
def four():
    return jax.devices()[:4]


def _cfg(**kw):
    base = dict(
        num_workers=8, num_iterations=96, gamma=0.5, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=10, seed=5,
        calibration_iters=4, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


@pytest.fixture()
def timed(monkeypatch):
    """``timed.on`` is true from ``EngineRun.start_clock`` to the end of
    the submitter loop (``EngineRun.shutdown``); ``timed.runs`` are the
    runs built."""

    class Timed:
        on = False        # inside the submitter loop
        started = False   # the clock has started (steps still out at the
                          # loop's end are called behind it)

    state = Timed()
    state.runs = []
    real_init = engine_loop.EngineRun.__init__
    real_clock = engine_loop.EngineRun.start_clock
    real_shutdown = engine_loop.EngineRun.shutdown

    def init(run, *a, **kw):
        real_init(run, *a, **kw)
        state.runs.append(run)

    def start_clock(run):
        out = real_clock(run)
        state.on = state.started = True
        return out

    def shutdown(run, run_ok):
        state.on = False
        return real_shutdown(run, run_ok)

    monkeypatch.setattr(engine_loop.EngineRun, "__init__", init)
    monkeypatch.setattr(engine_loop.EngineRun, "start_clock", start_clock)
    monkeypatch.setattr(engine_loop.EngineRun, "shutdown", shutdown)
    return state


def _spy_steps(solver, timed):
    """Every call of the worker step since the clock started: the devices
    of its shard, of the model and of the key it was called with.  (A task
    built in the loop's last turn may never be called: the tasks built are
    these calls and at most a fleet more.)"""
    calls = []
    real = solver._step

    def step(*args):
        *operands, w, key = args
        if timed.started:
            calls.append((operands[0].device, w.device, key.device))
        return real(*args)

    solver._step = step
    return calls


class Applies:
    """Every apply dispatch of the timed run, on whatever device: ``(device,
    kind, gradients that counted, k before, w after, k after)``, host
    values (``tests/test_asgd_fold.py: Dispatches`` keeps one device's)."""

    def __init__(self, solver, timed):
        self.records = []
        real_apply, real_fold = solver._apply, solver._apply_fold

        def apply(w, g, k):
            if not timed.on:
                return real_apply(w, g, k)
            assert w.device == g.device == k.device
            g_host, k0 = np.array(g), float(k)  # g and k are donated
            w2, k2 = real_apply(w, g, k)
            self.records.append(
                (w.device, "apply", [g_host], k0, np.asarray(w2), float(k2)))
            return w2, k2

        def fold(w, gs, m, k):
            if not timed.on:
                return real_fold(w, gs, m, k)
            assert {w.device, m.device, k.device} == {g.device for g in gs}
            live, k0 = [np.array(g) for g in gs[:int(m)]], float(k)
            w2, k2 = real_fold(w, gs, m, k)
            self.records.append(
                (w.device, "fold", live, k0, np.asarray(w2), float(k2)))
            return w2, k2

        solver._apply, solver._apply_fold = apply, fold

    def on(self, device):
        return [r[1:] for r in self.records if r[0] == device]


# --------------------------------------- (a) a step reads its own chip's model
def test_every_step_is_called_with_the_model_on_its_shards_device(
        problem, four, timed, tmp_path):
    X, y = problem
    log = tmp_path / "run.jsonl"
    solver = ASGD(X, y, _cfg(trace_sample=1.0, event_log=str(log)),
                  devices=four)
    calls = _spy_steps(solver, timed)
    res = solver.run()
    assert res.accepted == 96
    assert calls and {shard for shard, _w, _k in calls} == set(four)
    for shard, w, key in calls:
        assert shard == w == key
    # nothing was copied in front of a step, and nothing compiled
    assert res.extras["model_reads_copied"] == 0
    assert 0 <= res.extras["model_reads_local"] - len(calls) <= 8
    assert res.extras["compiles_in_run"] == 0
    spans, _ = trace.load_trace_events(log)
    stages = {sp.stage for sp in spans}
    assert trace.TASK_MODEL_COPY not in stages
    assert {trace.TASK_TURN, trace.TASK_ENQUEUE, trace.TASK_DISPATCH} <= stages
    (run,) = timed.runs
    assert run.chips == list(four)
    # a version is ONE handle with a buffer a device, and the account
    # counts handles: live + eight results + eight pinned + the snapshots
    assert all(type(w) is ModelReplicas and len(w) == 4
               for _t, w in run.snapshots)
    assert res.extras["versions_pinned_max"] <= 8
    assert res.extras["model_copies_peak"] <= (
        1 + 8 + 8 + 2 * len(run.snapshots))


# ------------------------- (b) replicas of a version are the same to the bit
@pytest.mark.parametrize("freq,taw", [(10, 2**31 - 1), (3, 2**31 - 1),
                                      (10, 5)])
def test_every_replica_is_the_serial_replay_of_the_accepted_gradients(
        freq, taw, problem, four, timed, held_updater):
    """Folded drains, snapshot splits and dropped results included: every
    device runs the same dispatches on the same operands, so replicas are
    equal to the bit, and each is the serial path over the accepted
    gradients in their recorded order."""
    X, y = problem
    cfg = _cfg(printer_freq=freq, taw=taw)
    solver = ASGD(X, y, cfg, devices=four)
    applies = Applies(solver, timed)
    held_updater(8)
    res = solver.run()
    assert res.accepted == 96
    first = applies.on(four[0])
    assert sum(len(r[1]) for r in first) == 96
    assert "fold" in {r[0] for r in first}          # drains were folded
    for dev in four[1:]:
        mine = applies.on(dev)
        assert len(mine) == len(first)
        for (kind, gs, k0, w2, k2), (kind0, gs0, k00, w20, k20) in zip(
                mine, first):
            assert (kind, k0, k2) == (kind0, k00, k20)
            assert all(np.array_equal(a, b) for a, b in zip(gs, gs0))
            assert np.array_equal(w2, w20)           # to the bit
    assert res.extras["apply_dispatches"] == len(first)
    models, _ks = _serial_replay(
        cfg, X.shape[0], X.shape[1], [("", *r[1:]) for r in first])
    _close(res.final_w, models[-1])
    # at every snapshot: the model after its update, on all four devices
    (run,) = timed.runs
    want = [0] + [j * freq + 1 for j in range((96 - 1) // freq + 1)] + [96]
    assert res.snapshot_updates == want
    for updates, (_t_ms, w) in zip(want, run.snapshots):
        assert [b.device for b in w] == list(four)
        bufs = [np.asarray(b) for b in w]
        assert all(np.array_equal(bufs[0], b) for b in bufs[1:])
        _close(bufs[0], models[updates])


# ------------------------------------ (c) ONE device: the calls it always made
def test_on_one_device_nothing_is_put_and_every_apply_is_one_dispatch(
        problem, timed, monkeypatch):
    X, y = problem
    puts = []
    real_put = jax.device_put

    def device_put(x, device=None, **kw):
        if timed.on:
            puts.append(device)
        return real_put(x, device, **kw)

    solver = ASGD(X, y, _cfg(), devices=jax.devices()[:1])
    calls = _spy_steps(solver, timed)
    applies = Applies(solver, timed)
    monkeypatch.setattr(jax, "device_put", device_put)
    res = solver.run()
    assert res.accepted == 96
    assert puts == []
    (run,) = timed.runs
    assert run.chips is None and solver._spread == {}
    # the model is one single-device buffer, a snapshot its handle, a
    # result its step's own output, and every drain ONE dispatch
    assert all(isinstance(w, jax.Array) and len(w.devices()) == 1
               for _t, w in run.snapshots)
    assert len(applies.records) == res.extras["apply_dispatches"]
    assert {r[0] for r in applies.records} == {jax.devices()[0]}
    assert res.extras["model_reads_copied"] == 0
    assert 0 <= res.extras["model_reads_local"] - len(calls) <= 8


# ------------------------------- (d) a version of the store is still copied
def test_a_stale_version_is_copied_to_the_tasks_device_and_counted(
        problem, four, timed, tmp_path):
    """``stale_read_offset``: the ``VersionedModelStore`` publishes a
    version on the driver's device; a task on another device copies it
    through ``on_device`` as ever (``task.model_copy``), and the run
    counts it."""
    X, y = problem
    log = tmp_path / "run.jsonl"
    solver = ASGD(X, y, _cfg(stale_read_offset=2, trace_sample=1.0,
                             event_log=str(log)), devices=four)
    calls = _spy_steps(solver, timed)
    res = solver.run()
    assert res.accepted == 96
    for shard, w, key in calls:
        assert shard == w == key     # copied in front of the step
    off_driver = sum(1 for shard, _w, _k in calls if shard != four[0])
    assert off_driver > 0
    # three tasks in four lie off the driver's device, and each copied
    assert 0 <= res.extras["model_reads_copied"] - off_driver <= 6
    tasks = (res.extras["model_reads_copied"]
             + res.extras["model_reads_local"])
    assert 0 <= tasks - len(calls) <= 8
    assert res.extras["model_reads_copied"] >= tasks // 2
    spans, _ = trace.load_trace_events(log)
    copies = [sp for sp in spans if sp.stage == trace.TASK_MODEL_COPY]
    assert len(copies) >= off_driver - 8   # but for those still in flight


# ------------------------- (e) a re-homed shard reads its new device's replica
def test_a_rehomed_shard_reads_the_replica_on_its_new_device(
        problem, four, timed):
    X, y = problem
    solver = ASGD(X, y, _cfg(), devices=four)
    calls = _spy_steps(solver, timed)
    built = []
    real = solver._make_task

    def make_task(wid, w_pub, *a, **kw):
        built.append((wid, w_pub.device))
        if len(built) == 24:
            # worker 5's shard (device 1) goes to worker 2's device
            solver._recovery.move_shard(5, 2)
        return real(wid, w_pub, *a, **kw)

    solver._make_task = make_task
    res = solver.run()
    assert res.accepted == 96
    before = {dev for wid, dev in built[:24] if wid == 5}
    after = {dev for wid, dev in built[24:] if wid == 5}
    assert before == {four[1]} and after == {four[2]}
    for shard, w, key in calls:
        assert shard == w == key
    assert res.extras["model_reads_copied"] == 0
