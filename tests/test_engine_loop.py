"""The one engine loop (``solvers/engine_loop.py``): every solver's run
goes through it, and a run stops whatever it started.

What the loop DOES is guarded where it always was (``test_solvers.py``,
``test_engine_spans.py``, ``test_checkpoint.py``, ``test_wiring.py``, the
fault-tolerance files); these cases guard that it is ONE loop.
"""

import ast
import os
import threading

import pytest

from asyncframework_tpu.data import make_regression
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig, engine_loop

SOLVERS = pytest.mark.parametrize("solver", [ASGD, ASAGA],
                                  ids=["asgd", "asaga"])
ENGINE_THREADS = {"ps-updater", "saga-updater", "heartbeat-monitor",
                  "speculation-monitor"}


@pytest.fixture(scope="module")
def problem():
    X, y, _ = make_regression(1024, 16, seed=5)
    return X, y


def _cfg(**kw):
    defaults = dict(
        num_workers=4, num_iterations=40, gamma=0.5, batch_rate=0.3,
        bucket_ratio=0.5, printer_freq=10, seed=7, calibration_iters=4,
        run_timeout_s=60.0, heartbeat=True, speculation=True,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


@pytest.fixture()
def runs(monkeypatch):
    """Every ``EngineRun`` built during the test, with the calls of its
    monitors' start and stop."""
    seen = []
    real_init = engine_loop.EngineRun.__init__
    real_start = engine_loop.EngineRun.start_monitors
    real_shutdown = engine_loop.EngineRun.shutdown

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        self.calls = []
        seen.append(self)

    def start_monitors(self, *a, **kw):
        self.calls.append("start_monitors")
        return real_start(self, *a, **kw)

    def shutdown(self, run_ok):
        self.calls.append(("shutdown", run_ok))
        return real_shutdown(self, run_ok)

    monkeypatch.setattr(engine_loop.EngineRun, "__init__", init)
    monkeypatch.setattr(engine_loop.EngineRun, "start_monitors",
                        start_monitors)
    monkeypatch.setattr(engine_loop.EngineRun, "shutdown", shutdown)
    return seen


def _nothing_left_running(run, before):
    """The run's scheduler is shut down, its monitors are stopped, and no
    engine thread born since ``before`` is alive."""
    assert run.sched.pool.closed
    assert not run._ft.monitor._thread.is_alive()
    assert run._spec._thread is None  # stop() joins, then forgets it
    left = [t.name for t in threading.enumerate()
            if t not in before and t.name in ENGINE_THREADS]
    assert left == []


@SOLVERS
def test_both_solvers_submit_through_the_one_loop(
        solver, devices8, problem, monkeypatch, runs):
    """A spy on the shared submitter's cohort choice sees every cohort of
    a run: one non-empty cohort a round, every merged result from one."""
    cohorts = []
    real = engine_loop.partial_barrier

    def spy(*a, **kw):
        cohort = real(*a, **kw)
        if cohort:
            cohorts.append(list(cohort))
        return cohort

    monkeypatch.setattr(engine_loop, "partial_barrier", spy)
    res = solver(*problem, _cfg(), devices=devices8[:2]).run()
    assert res.accepted == 40
    assert len(runs) == 1 and not runs[0].sync
    assert len(cohorts) == res.rounds
    assert sum(map(len, cohorts)) >= res.accepted + res.dropped


@SOLVERS
@pytest.mark.parametrize("ending", ["clean end", "a task that raises"])
def test_a_run_stops_everything_it_started(
        solver, ending, devices8, problem, runs):
    engine = solver(*problem, _cfg(num_iterations=400), devices=devices8[:2])
    before = set(threading.enumerate())
    if ending == "clean end":
        assert engine.run().accepted == 400
    else:
        real_step, calls = engine._step, []

        def failing_step(*args):
            calls.append(1)
            if len(calls) > 12:
                raise RuntimeError("injected device failure")
            return real_step(*args)

        engine._step = failing_step
        with pytest.raises(RuntimeError):
            engine.run()
    (run,) = runs
    assert run.calls == ["start_monitors",
                         ("shutdown", ending == "clean end")]
    assert run.stop.is_set()
    _nothing_left_running(run, before)


@SOLVERS
def test_the_monitors_are_built_once_for_run_and_run_sync(
        solver, devices8, problem, runs):
    """``run_sync`` starts and stops its monitors through the same two
    methods as ``run``, and reports what they saw under the same keys."""
    engine = solver(*problem, _cfg(), devices=devices8[:2])
    before = set(threading.enumerate())
    results = [engine.run(), engine.run_sync()]
    assert [r.sync for r in runs] == [False, True]
    for run, res in zip(runs, results):
        assert run.calls == ["start_monitors", ("shutdown", True)]
        _nothing_left_running(run, before)
        assert {"speculated", "speculation_wins", "host_stalls",
                "host_stall_max_ms"} <= set(res.extras)
    assert results[1].rounds == 40
    assert engine.scheduler is runs[1].sched


# ------------------------------------------- ONE frame of a drain, ONE round
@pytest.mark.parametrize("solver,taw", [
    (ASGD, 2**31 - 1), (ASGD, 1), (ASAGA, 2**31 - 1),
], ids=["asgd", "asgd-taw-1", "asaga"])
def test_both_solvers_merge_through_the_one_frame(
        solver, taw, devices8, problem, monkeypatch, runs):
    """A spy on the shared frame (``EngineRun.updater``) sees every drain
    of a run of either solver: what ``drive`` starts is the frame's
    callable, every accepted update is in one segment the frame handed the
    solver's ``dispatch``, the segments are the run's updates in order,
    none goes past a snapshot's update, and the drains' accepted and
    dropped results are the result's."""
    frames, started, drains, segments, merged = [], [], [], [], []
    real_updater = engine_loop.EngineRun.updater
    real_drive = engine_loop.EngineRun.drive

    def updater(run, accepts, dispatch, *a, **kw):
        def spied(live, at_k, alone):
            made = dispatch(live, at_k, alone)
            segments.append((at_k, len(live), alone, set(made)))
            return made

        real_drained = run.inst.on_drained
        real_merged = run.inst.on_gradient_merged

        def on_drained(results):
            drains.append(len(results))
            return real_drained(results)

        def on_gradient_merged(res, accepted, at_k, task_ms):
            merged.append((len(drains), accepted))
            return real_merged(res, accepted, at_k, task_ms)

        run.inst.on_drained = on_drained
        run.inst.on_gradient_merged = on_gradient_merged
        frames.append(real_updater(run, accepts, spied, *a, **kw))
        return frames[-1]

    def drive(run, updater, thread_name, make_tasks):
        started.append((updater, thread_name))
        return real_drive(run, updater, thread_name, make_tasks)

    monkeypatch.setattr(engine_loop.EngineRun, "updater", updater)
    monkeypatch.setattr(engine_loop.EngineRun, "drive", drive)
    cfg = _cfg(taw=taw, printer_freq=7, heartbeat=False, speculation=False)
    res = solver(*problem, cfg, devices=devices8[:2]).run()
    assert res.accepted == 40
    (frame,) = frames
    assert started == [
        (frame, "ps-updater" if solver is ASGD else "saga-updater")]
    # every result of every drain went through the frame's filter
    assert sum(acc for _, acc in merged) == res.accepted
    assert sum(not acc for _, acc in merged) == res.dropped
    assert res.accepted + res.dropped <= sum(drains)
    assert {at for at, _ in merged} <= set(range(1, len(drains) + 1))
    if taw == 1:
        assert res.dropped > 0
    # the segments are the run's updates, in order, split at a snapshot
    at = 0
    for at_k, n, alone, fields in segments:
        assert at_k == at and n >= 1 and "w" in fields
        assert not any(u % 7 == 0 for u in range(at_k, at_k + n - 1))
        at += n
    assert at == res.accepted
    assert any(alone for _, _, alone, _ in segments)
    # (the first is the model at the clock's start, the last the final one)
    assert res.snapshot_updates[1:-1] == [u + 1 for u in range(0, 40, 7)]


@SOLVERS
def test_both_solvers_synchronous_rounds_are_the_one_round(
        solver, devices8, problem, monkeypatch, runs):
    """A spy on the shared round (``EngineRun.drive_sync``) sees every
    round of either ``run_sync``: a merge a worker a round, one apply a
    round."""
    merges, applies = [], []
    real = engine_loop.EngineRun.drive_sync

    def drive_sync(run, make_tasks, merge, apply_round):
        def spied_merge(res):
            merges.append(res.worker_id)
            return merge(res)

        def spied_apply(w, acc):
            applies.append(len(merges))
            return apply_round(w, acc)

        return real(run, make_tasks, spied_merge, spied_apply)

    monkeypatch.setattr(engine_loop.EngineRun, "drive_sync", drive_sync)
    cfg = _cfg(num_iterations=12, heartbeat=False, speculation=False)
    res = solver(*problem, cfg, devices=devices8[:2]).run_sync()
    (run,) = runs
    assert run.sync and res.rounds == 12 and res.accepted == 48
    assert applies == [4 * (r + 1) for r in range(12)]
    assert all(sorted(merges[i:i + 4]) == [0, 1, 2, 3]
               for i in range(0, 48, 4))


def _schedule_sites(source):
    """``(what, line)`` of everything in ``source`` that belongs to the
    drain's frame or the synchronous round: a call of ``collect_all``,
    ``_collect_checked`` or ``drain``, an assignment to ``state["k"]`` or
    ``state["accepted"]`` (on any name: ``run.state`` too), an append to
    ``snapshots``."""
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            owner = node.func.value
            name = getattr(owner, "id", getattr(owner, "attr", None))
            if node.func.attr in ("collect_all", "_collect_checked", "drain"):
                found.append((node.func.attr, node.lineno))
            elif node.func.attr == "append" and name == "snapshots":
                found.append(("snapshots.append", node.lineno))
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target] if isinstance(node, ast.AugAssign)
                   else [])
        for target in targets:
            for t in ast.walk(target):
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.slice, ast.Constant)
                        and t.slice.value in ("k", "accepted")):
                    found.append((f'[{t.slice.value!r}] =', t.lineno))
    return sorted(found, key=lambda site: site[1])


@pytest.mark.parametrize("module", ["asgd", "asaga"])
def test_a_solver_holds_no_part_of_the_schedule(module):
    """The frame and the round each stand once, in ``engine_loop.py``: a
    walk of a solver's source finds no take from the queue, no publication
    of the count and no snapshot."""
    root = os.path.dirname(os.path.abspath(engine_loop.__file__))
    with open(os.path.join(root, module + ".py")) as f:
        assert _schedule_sites(f.read()) == []
    with open(os.path.join(root, "engine_loop.py")) as f:
        engine = {what for what, _ in _schedule_sites(f.read())}
    assert {"collect_all", "drain", "_collect_checked", "snapshots.append",
            "['k'] =", "['accepted'] ="} <= engine


def test_the_walk_of_the_schedule_sees_what_it_is_meant_to():
    found = _schedule_sites(
        "def updater():\n"
        "    results = [ctx.collect_all(timeout=1)]\n"
        "    results.extend(islice(run.ctx.drain(), 3))\n"
        "    state['k'] = k + 1\n"
        "    run.state['accepted'] += 1\n"
        "    state['w'], state['k'] = w, 2\n"
        "    state['flops'] += 1.0\n"
        "    run.snapshots.append((0.0, w))\n"
        "    rows.append(1)\n")
    assert [what for what, _ in found] == [
        "collect_all", "drain", "['k'] =", "['accepted'] =", "['k'] =",
        "snapshots.append"]


@SOLVERS
def test_a_rehomed_shards_key_follows_it(solver, devices8, problem,
                                         monkeypatch):
    """The hook the heartbeat monitor is handed moves the shard's PRNG
    chain to its new device under either solver (ASAGA's own adds the
    slice and moves its count on)."""
    hooks = []

    class Monitor:  # in ``instrumentation.FaultTolerantRun``'s place
        def __init__(self, *a, on_moved=None, **kw):
            hooks.append(on_moved)

        def start(self):
            pass

        stop = start

    monkeypatch.setattr(engine_loop, "FaultTolerantRun", Monitor)
    engine = solver(*problem, _cfg(speculation=False), devices=devices8[:2])
    run = engine_loop.EngineRun(engine)
    run.cold_start()
    alpha = commits = None
    if solver is ASAGA:
        _, alpha = engine._zero_history()
        commits = dict.fromkeys(alpha, 0)
        run.start_monitors(engine._history_follows(run, alpha, commits))
    else:
        run.start_monitors()
    try:
        home = engine._recovery.shard(1).device
        moved = engine._recovery.move_shard(1, 0)
        assert moved.device != home
        assert run.worker_keys[1].device == home
        (hook,) = hooks
        hook(1, moved)
        assert run.worker_keys[1].device == moved.device
        assert run.worker_keys[3].device == home  # the others stay
        if alpha is not None:
            assert alpha[1].device == moved.device
            assert commits == {0: 0, 1: 1, 2: 0, 3: 0}
    finally:
        run.shutdown(True)


def test_asaga_adds_exactly_its_seven_history_extras(devices8, problem):
    """The shared result assembly: an ASAGA run's ``extras`` are an ASGD
    run's plus the history table's seven (``history_check_s`` since PR 46:
    what reading the table back and holding ``alpha_bar`` to it took)."""
    # (a cell of the lock waits' table, ``lock_wait_<lock>_<waiter>_behind_
    # <holder>_s``, is there only where that pair waited: by run, not by
    # solver; tests/test_lock_clock.py holds the keys every run carries)
    keys = {
        solver: {k for k in solver(*problem, _cfg(), devices=devices8[:2])
                 .run().extras if "_behind_" not in k}
        for solver in (ASGD, ASAGA)
    }
    assert keys[ASAGA] - keys[ASGD] == {
        "alpha", "alpha_bar", "updater_history_s", "history_drift",
        "history_reused", "history_recomputed", "history_check_s"}
    assert keys[ASGD] <= keys[ASAGA]
    assert "dense_step_path" in keys[ASGD]
