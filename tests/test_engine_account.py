"""The engine's account of what it did NOT give the device, and of its own
last seconds (ISSUE 34): why the submitter sent nothing, what each chip was
given (``solvers/instrumentation.py: Occupancy``, a traced run's), the
``worker.idle`` span, and the split of a run's end.  Each case is a count
or an identity, never a rate."""

import dataclasses
import glob
import itertools
import os
import time

import numpy as np
import pytest

from asyncframework_tpu.metrics import trace
from asyncframework_tpu.metrics.bus import GradientMerged, RoundSubmitted
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig
from asyncframework_tpu.solvers import instrumentation
from asyncframework_tpu.solvers.instrumentation import (
    Occupancy,
    RunInstruments,
)

SOLVERS = pytest.mark.parametrize("solver_cls", [ASGD, ASAGA],
                                  ids=["asgd", "asaga"])
EPS_MS = 0.05  # float noise of epoch milliseconds, not a tolerance of order
HOLDS = ("submit_hold_backlog_s", "submit_hold_barrier_s",
         "submit_wait_workers_s")
TAIL = ("run_tail_s", "run_tail_join_s", "run_tail_shutdown_s",
        "run_tail_fence_s", "inflight_at_stop", "results_unmerged")
AFTER = ("trajectory_eval_s", "checkpoint_s", "close_s")


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(2048, 16)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    w = rng.normal(size=16).astype(np.float32)
    return X, X @ w


def _solver(solver_cls, problem, devices=None, **kw):
    base = dict(
        num_workers=4, num_iterations=48, taw=2**31 - 1, batch_rate=0.3,
        gamma=0.4 if solver_cls is ASGD else 0.05, bucket_ratio=0.5,
        printer_freq=10, seed=3, calibration_iters=8, run_timeout_s=60.0,
    )
    base.update(kw)
    return solver_cls(*problem, SolverConfig(**base), devices=devices)


def _slow_step(solver, seconds):
    """Every step of ``solver`` takes one to four times ``seconds`` longer
    on its executor, in turn: a cohort's results come back apart."""
    real = solver._step
    turn = itertools.count()

    def step(*args):
        time.sleep(seconds * (1 + next(turn) % 4))
        return real(*args)

    solver._step = step


# ------------------------------------------------- the occupancy account alone
class _Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_enters_and_leaves_over_two_chips_to_the_digit():
    clock = _Clock()
    # workers 0 and 2 on chip 0, 1 on chip 1, 3 on chip 7 (never entered)
    occ = Occupancy({0: 0, 1: 1, 2: 0, 3: 7}, clock=clock)
    clock.now = 101.0
    assert occ.enter([0, 1]) == {}      # a worker's first task: no idle
    clock.now = 103.0
    assert occ.enter([2]) == {}
    clock.now = 104.0
    occ.leave(0)                        # chip 0 still holds worker 2
    clock.now = 106.0
    occ.leave(2)                        # chip 0 is empty from here
    occ.leave(2)                        # a second result of the same task
    occ.leave(3)                        # a worker that never entered
    clock.now = 109.0
    assert occ.enter([0]) == {0: 5.0}   # left at 104
    assert occ.enter([0]) == {}         # still in flight: nothing
    clock.now = 110.0
    occ.leave(1)
    clock.now = 112.0
    out = occ.close()
    # in flight: 2 over [101,103), 3 over [103,104), 2 over [104,106),
    # 1 over [106,109), 2 over [109,110), 1 over [110,112)
    assert out["inflight_task_s"] == 4.0 + 3.0 + 4.0 + 3.0 + 2.0 + 2.0
    assert out["chip_empty_s"] == {
        0: 1.0 + 3.0,                   # [100,101) and [106,109)
        1: 1.0 + 2.0,                   # [100,101) and [110,112)
        7: 12.0,                        # the whole run
    }
    assert out["chip_empty_max_s"] == 12.0
    assert out["chip_empty_mean_s"] == pytest.approx(19.0 / 3.0)
    assert occ.worker_idle_s == {0: 5.0, 1: 0.0, 2: 0.0}
    # the clock has stopped: later events change nothing
    clock.now = 150.0
    occ.leave(0)
    assert occ.enter([1]) == {}
    assert occ.close() == out


def test_start_begins_the_account_again():
    clock = _Clock()
    occ = Occupancy({0: 0}, clock=clock)
    occ.enter([0])
    clock.now = 105.0
    occ.start()                         # the run's clock starts here
    clock.now = 107.0
    out = occ.close()
    assert out["inflight_task_s"] == 0.0
    assert out["chip_empty_s"] == {0: 2.0}
    assert occ.worker_idle_s == {}


# -------------------------------------------------------------- a traced run
@SOLVERS
def test_a_traced_runs_account_adds_up(solver_cls, problem, devices8,
                                       tmp_path):
    log = tmp_path / "run.jsonl"
    nw = 4
    solver = _solver(solver_cls, problem, devices=devices8[:2],
                     trace_sample=1.0, event_log=str(log))
    res = solver.run()
    ex = res.extras
    assert res.accepted == 48
    for key in HOLDS:
        assert ex[key] >= 0.0, key
    # the three are the submitter's polling wait; its blocked first job
    # (``JobScheduler.blocked_ns``) is the rest of ``submitter_wait_s``
    assert sum(ex[k] for k in HOLDS) <= ex["submitter_wait_s"] + 1e-9
    assert 0.0 <= ex["chip_empty_max_s"] <= res.elapsed_s
    assert 0.0 <= ex["chip_empty_mean_s"] <= ex["chip_empty_max_s"]
    assert 0.0 < ex["inflight_task_s"] <= nw * res.elapsed_s
    # one entry a chip that holds a shard, by the device's id
    assert set(ex["chip_empty_s"]) == {d.id for d in devices8[:2]}
    assert set(res.waiting_time_ms) == set(range(nw))
    assert all(ms >= 0.0 for ms in res.waiting_time_ms.values())

    spans, _ = trace.load_trace_events(log)
    firsts = 0
    idle_ms = dict.fromkeys(range(nw), 0.0)
    for tid, group in trace.build_traces(spans).items():
        by_stage = {}
        for sp in group:
            by_stage.setdefault(sp.stage, []).append(sp)
        if trace.SUBMIT not in by_stage:
            continue  # the run's own trace (trajectory.eval)
        (submit,) = by_stage[trace.SUBMIT]
        idles = by_stage.get(trace.WORKER_IDLE, [])
        if not idles:
            firsts += 1
            continue
        (idle,) = idles
        assert idle.trace_id == submit.trace_id == tid
        assert idle.parent_id is None
        assert idle.worker_id == submit.worker_id
        assert abs(idle.start_ms + idle.dur_ms - submit.start_ms) <= EPS_MS
        idle_ms[idle.worker_id] += idle.dur_ms
        # canonical order: it precedes the update's submit
        assert group.index(idle) < group.index(submit)
    assert firsts == nw  # a worker's first task has none
    # sampled 1 in 1: the spans are the whole of the account
    for wid, ms in res.waiting_time_ms.items():
        assert idle_ms[wid] == pytest.approx(ms, abs=1e-3)


@SOLVERS
def test_a_traced_sync_run_keeps_the_occupancy_and_no_tail(
        solver_cls, problem, devices8):
    solver = _solver(solver_cls, problem, devices=devices8[:2],
                     trace_sample=0.5, num_iterations=6)
    res = solver.run_sync()
    ex = res.extras
    assert 0.0 <= ex["chip_empty_max_s"] <= res.elapsed_s
    assert 0.0 < ex["inflight_task_s"] <= 4 * res.elapsed_s
    assert set(res.waiting_time_ms) == set(range(4))
    # one driver thread: no submitter, no loop exit; the three after the
    # fence all the same
    assert not set(HOLDS + TAIL) & set(ex)
    assert all(ex[k] >= 0.0 for k in AFTER)


@pytest.mark.parametrize("ratio", [1.0, 0.0])
def test_the_barriers_hold_is_the_bucket_ratios(ratio, problem):
    """Ratio 1.0: no cohort goes out until the whole fleet is back, and
    with a step of 3 ms and more the submitter sees the first three back
    and waits.  Ratio 0: whoever is back goes out; the bucket holds
    nobody, so not one sleep is put down to it."""
    solver = _solver(ASGD, problem, bucket_ratio=ratio, num_iterations=32)
    _slow_step(solver, 0.003)
    ex = solver.run().extras
    if ratio:
        assert ex["submit_hold_barrier_s"] > 0.0
    else:
        assert ex["submit_hold_barrier_s"] == 0.0
        assert ex["submit_wait_workers_s"] > 0.0
    assert ex["submit_empty_polls"] > 0


def test_a_fleet_of_unmerged_results_is_the_backlogs_hold(
        problem, monkeypatch):
    """An updater that sleeps until a whole fleet is queued, and 5 ms
    more: the submitter's polls find it a fleet behind."""
    from asyncframework_tpu.context import AsyncContext

    real = AsyncContext.collect_all

    def collect_all(self, timeout=None):
        if timeout:  # the blocking take; ctx.drain() never comes here
            deadline = time.monotonic() + 2.0
            while self.size() < 4 and time.monotonic() < deadline:
                time.sleep(0.0005)
            time.sleep(0.005)
        return real(self, timeout=timeout)

    monkeypatch.setattr(AsyncContext, "collect_all", collect_all)
    solver = _solver(ASGD, problem, bucket_ratio=0.0, num_iterations=32)
    ex = solver.run().extras
    assert ex["submit_hold_backlog_s"] > 0.0
    assert sum(ex[k] for k in HOLDS) <= ex["submitter_wait_s"] + 1e-9


# ------------------------------------------------------------ an untraced run
def test_an_untraced_run_keeps_no_occupancy_and_reads_no_clock_for_it(
        problem, monkeypatch):
    built = []
    real_init = Occupancy.__init__

    def init(self, *a, **kw):
        built.append(self)
        real_init(self, *a, **kw)

    monkeypatch.setattr(Occupancy, "__init__", init)
    insts = []
    real_close = RunInstruments.close

    def close(self, *a, **kw):
        insts.append(self)
        return real_close(self, *a, **kw)

    monkeypatch.setattr(RunInstruments, "close", close)
    res = _solver(ASGD, problem, trace_sample=None).run()
    assert res.accepted == 48
    assert built == []
    assert insts and all(i.occupancy is None for i in insts)
    assert res.waiting_time_ms == {}
    ex = res.extras
    for key in HOLDS + TAIL + AFTER + ("run_lead_s",):
        assert isinstance(ex[key], (int, float)), key
    for key in ("inflight_task_s", "chip_empty_s", "chip_empty_max_s",
                "chip_empty_mean_s"):
        assert key not in ex, key
    # outside a profiler session the holds cost what a wait stage costs:
    # the one shared no-op, no allocation, no clock
    for stage in (trace.HOLD_BARRIER, trace.HOLD_BACKLOG,
                  trace.WAIT_WORKERS):
        assert trace.span(stage) is trace._NO_SPAN


def test_an_instrument_without_a_tracer_builds_no_occupancy():
    inst = RunInstruments(SolverConfig(num_workers=2), 2,
                          chip_of=lambda wid: wid)
    try:
        assert inst.tracer is None and inst.occupancy is None
    finally:
        inst.close()
    inst = RunInstruments(SolverConfig(num_workers=2, trace_sample=0.5), 2,
                          chip_of=lambda wid: 5)
    try:
        assert inst.occupancy.close()["chip_empty_s"].keys() == {5}
    finally:
        inst.close()


# ------------------------------------------------------------- the run's end
@SOLVERS
def test_the_tail_lies_inside_elapsed_and_holds_its_parts(
        solver_cls, problem):
    solver = _solver(solver_cls, problem, num_iterations=10**9,
                     run_timeout_s=0.4)
    _slow_step(solver, 0.002)
    res = solver.run()
    ex = res.extras
    parts = [ex["run_tail_join_s"], ex["run_tail_shutdown_s"],
             ex["run_tail_fence_s"]]
    assert all(p >= 0.0 for p in parts)
    assert sum(parts) <= ex["run_tail_s"] <= res.elapsed_s
    # the deadline cut the run with tasks out, at most a fleet in flight
    # and a fleet's results queued (the backlog bound); of those, the ones
    # that came back came back to nobody
    assert 0 <= ex["results_unmerged"] <= ex["inflight_at_stop"] <= 2 * 4


def test_a_run_that_spends_its_budget_leaves_what_it_ignored_unmerged(
        problem):
    res = _solver(ASGD, problem).run()
    ex = res.extras
    assert res.accepted == 48 and res.dropped == 0
    # the loop leaves when the budget is spent: what was out then is
    # ignored past the budget, or never drained
    assert 0 <= ex["results_unmerged"] <= ex["inflight_at_stop"] <= 2 * 4


def test_what_follows_the_fence_is_accounted_for(problem, tmp_path):
    """``run()``'s return less ``elapsed_s`` is the lead (the run built,
    the hot path warmed again), the trajectory's evaluation, the final
    checkpoint and the close of the instruments, to 50 ms.  The best of
    three: a loaded test host can hold any one run's thread that long."""
    solver = _solver(ASGD, problem, event_log=str(tmp_path / "ev"))
    solver.run()  # compiles
    rest = []
    for i in range(3):
        # a directory of its own: the run starts cold and saves at its end
        solver.cfg = dataclasses.replace(
            solver.cfg, checkpoint_dir=str(tmp_path / f"ck{i}")
        )
        t0 = time.monotonic()
        res = solver.run()
        whole = time.monotonic() - t0 - res.elapsed_s
        ex = res.extras
        assert ex["checkpoint_s"] > 0.0 and ex["close_s"] > 0.0
        named = ex["run_lead_s"] + sum(ex[k] for k in AFTER)
        assert named <= whole + 1e-6
        rest.append(whole - named)
    assert min(rest) <= 0.05, rest


# ------------------------------------------------------------------ the bus
def test_an_unheard_bus_is_posted_nothing_and_a_late_listener_hears_all(
        monkeypatch):
    built = []
    for cls in (GradientMerged, RoundSubmitted):
        def spy(*a, _cls=cls, **kw):
            built.append(_cls.__name__)
            return _cls(*a, **kw)

        monkeypatch.setattr(instrumentation, cls.__name__, spy)

    class Result:
        worker_id, staleness, batch_size = 1, 2, 64

    class Listener:
        def __init__(self):
            self.heard = []

        def on_event(self, ev):
            self.heard.append(type(ev).__name__)

    inst = RunInstruments(SolverConfig(num_workers=2), 2)
    try:
        assert not inst.bus.heard
        inst.on_round_submitted(1, [0, 1], 0)
        inst.on_gradient_merged(Result, True, 0)
        assert built == [] and inst.bus.posted_events == 0
        # the histogram is fed all the same
        assert inst.staleness_hist == {2: 1}
        listener = Listener()
        inst.bus.add_listener(listener)  # after construction
        assert inst.bus.heard
        inst.on_round_submitted(2, [0], 1)
        inst.on_gradient_merged(Result, False, 1)
        assert listener.heard == built == ["RoundSubmitted", "GradientMerged"]
        assert inst.staleness_hist == {2: 2}
    finally:
        inst.close()


# ------------------------------------------------ on the profiler's clock
def test_the_barriers_hold_and_the_dispatchs_chip_are_in_the_host_plane(
        problem, devices8, tmp_path):
    import jax
    from jax.profiler import ProfileData

    solver = _solver(ASGD, problem, devices=devices8[:2], bucket_ratio=1.0,
                     num_iterations=24, trace_sample=None)
    _slow_step(solver, 0.003)
    trace_dir = str(tmp_path / "xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        res = solver.run()
    finally:
        jax.profiler.stop_trace()
    assert res.accepted == 24 and res.extras["submit_hold_barrier_s"] > 0.0
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    names, dispatched = set(), set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == trace.ANNOTATION_PREFIX + trace.TASK_DISPATCH:
                    stats = dict(ev.stats)
                    dispatched.add((stats["worker"], stats["chip"]))
    assert trace.ANNOTATION_PREFIX + trace.HOLD_BARRIER in names
    assert trace.ANNOTATION_PREFIX + trace.WAIT_WORKERS not in names
    ids = [d.id for d in devices8[:2]]
    assert dispatched == {(wid, ids[wid % 2]) for wid in range(4)}


# ------------------------------ the model-sized state (ISSUE 37), hand-counted
COPIES = ("results_held_max", "versions_pinned_max", "model_copies_peak",
          "snapshots_held")


def _runs_built(monkeypatch):
    """Every ``EngineRun`` built from now on, in order."""
    from asyncframework_tpu.solvers import engine_loop

    runs = []
    real_init = engine_loop.EngineRun.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        runs.append(self)

    monkeypatch.setattr(engine_loop.EngineRun, "__init__", init)
    return runs


def _in_lockstep(monkeypatch):
    """A cohort goes out only once every result of the cohorts before it
    is APPLIED: with ``bucket_ratio`` 1.0 and an updater held until a whole
    fleet is queued, a run is rounds of four in a fixed order."""
    from asyncframework_tpu.solvers import engine_loop

    runs = _runs_built(monkeypatch)
    real_barrier = engine_loop.partial_barrier

    def barrier(ctx, nw, bucket):
        run = runs[-1]
        with run.state_lock:
            merged = run.state["accepted"] + run.state["dropped"]
            if merged != nw * run.state["rounds"]:
                return []
        return real_barrier(ctx, nw, bucket)

    monkeypatch.setattr(engine_loop, "partial_barrier", barrier)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "ell"])
def test_the_count_of_model_copies_is_the_hand_count_of_a_lockstep_run(
        sparse, problem, monkeypatch, held_updater):
    """Two rounds of four, ``printer_freq`` 4.  Round 1 is handed ``w0``
    (one version pinned; it is the live model and snapshot 0).  Drain 1
    finds 4 results, nothing pinned, one snapshot: 1 + 4 = 5 buffers; its
    two dispatches leave ``w1`` (a snapshot) and ``w4``.  Round 2 is handed
    ``w4``.  Drain 2 finds 4 results, snapshots ``w0`` and ``w1`` and the
    live ``w4``: 3 + 4 = 7.  The end: four snapshots (``w0``, ``w1``,
    ``w5``, the final ``w8``, which is the live model) and one evaluation
    stack beside them: all four rows over a dense shard, eight over padded
    ELL."""
    _in_lockstep(monkeypatch)
    held_updater(4)
    kw = dict(num_iterations=8, printer_freq=4, bucket_ratio=1.0)
    if sparse:
        import jax

        from asyncframework_tpu.data.sparse import SparseShardedDataset

        ds = SparseShardedDataset.generate_on_device(
            2048, 40_004, 11, 4, jax.devices()[:1], seed=5)
        solver = ASGD(ds, None, SolverConfig(
            num_workers=4, taw=2**31 - 1, batch_rate=0.3, gamma=0.4, seed=3,
            calibration_iters=8, run_timeout_s=60.0, **kw),
            devices=jax.devices()[:1])
    else:
        solver = _solver(ASGD, problem, **kw)
    res = solver.run()
    assert res.accepted == 8 and res.rounds == 2
    stack = 8 if sparse else 4
    assert {k: res.extras[k] for k in COPIES} == {
        "results_held_max": 4, "versions_pinned_max": 1,
        "model_copies_peak": 4 + stack, "snapshots_held": 4,
    }
    assert res.extras["eval_calls"] == 1
    assert res.extras["eval_stack_rows"] == stack


def test_the_account_counts_a_buffer_once_whatever_holds_it(problem):
    """The account itself, fed by hand: versions are distinct handles, a
    buffer that is the live model, a snapshot and a pinned version at once
    is one copy, and a finished task unpins."""
    import jax.numpy as jnp

    from asyncframework_tpu.solvers import engine_loop

    solver = _solver(ASGD, problem)
    run = engine_loop.EngineRun(solver)
    try:
        run.cold_start()
        run.start_clock()  # snapshot 0 is the live w0
        w0 = run.state["w"]
        w1, w2 = jnp.ones(16), jnp.full(16, 2.0)
        run.pin([0, 1], w0)
        run.count_copies(0)
        assert run.copies == {"results_held_max": 0, "versions_pinned_max": 1,
                              "model_copies_peak": 1}
        run.pin([2], w1)
        run.pin([3], w2)
        run.count_copies(3)  # w0 (live, snapshot, pinned), w1, w2, 3 results
        assert run.copies == {"results_held_max": 3, "versions_pinned_max": 3,
                              "model_copies_peak": 6}
        # worker 2's task is back (what the handler does under the key
        # lock); the model moves on to w2, which worker 3 still pins
        with run.key_lock:
            run.pinned.pop(2)
        run.state["w"] = w2
        run.snapshots.append((1.0, w2))
        run.count_copies(1, stack_rows=8)  # w0, w2, 1 result, 8 rows
        assert run.copies == {"results_held_max": 3, "versions_pinned_max": 3,
                              "model_copies_peak": 11}
    finally:
        run.shutdown(run_ok=True)


@SOLVERS
def test_every_engine_run_reports_the_four_counts(solver_cls, problem):
    res = _solver(solver_cls, problem).run()
    ex = res.extras
    assert ex["snapshots_held"] == len(res.trajectory)
    assert 1 <= ex["results_held_max"] <= 3 * 4 - 1
    assert 1 <= ex["versions_pinned_max"] <= 4
    # at the end: every snapshot and the dense stack of all of them
    assert ex["model_copies_peak"] >= 2 * ex["snapshots_held"]
    sync = _solver(solver_cls, problem, num_iterations=6).run_sync().extras
    # a sync run pins and holds nothing the account sees: its last reading
    assert sync["results_held_max"] == 0 and sync["versions_pinned_max"] == 0
    assert sync["model_copies_peak"] == 2 * sync["snapshots_held"]


def test_the_pins_survive_sixteen_workers_on_a_short_switch_interval(
        problem, monkeypatch):
    """More workers than cores and a thread switch every 10 us: the
    submitter pins and sixteen handlers unpin under one lock, so when the
    run is over nothing is pinned but what was still out, and no reading
    went over what the engine can hold."""
    import sys

    runs = _runs_built(monkeypatch)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        res = _solver(ASGD, problem, num_workers=16, num_iterations=400,
                      bucket_ratio=0.3, printer_freq=50,
                      run_timeout_s=30.0).run()
    finally:
        sys.setswitchinterval(old)
    ex = res.extras
    assert res.accepted == 400
    with runs[-1].key_lock:
        assert len(runs[-1].pinned) <= ex["inflight_at_stop"]
    assert 1 <= ex["versions_pinned_max"] <= 16
    # a fleet drained and not yet applied, a fleet less one queued when
    # the submitter last looked, and the fleet it then sent out
    assert 1 <= ex["results_held_max"] <= 3 * 16 - 1
    assert ex["snapshots_held"] == len(res.trajectory) == 10
    # ten snapshots and the dense stack of them, at least; every reading
    # under all the engine can hold at once
    assert 20 <= ex["model_copies_peak"] <= 10 + 10 + 16 + 47
