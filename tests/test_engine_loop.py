"""The one engine loop (``solvers/engine_loop.py``): every solver's run
goes through it, and a run stops whatever it started.

What the loop DOES is guarded where it always was (``test_solvers.py``,
``test_engine_spans.py``, ``test_checkpoint.py``, ``test_wiring.py``, the
fault-tolerance files); these cases guard that it is ONE loop.
"""

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
