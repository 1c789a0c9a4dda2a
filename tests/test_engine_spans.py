"""The engine's spans and counters (ISSUE 23): one span call
(``metrics.trace.span``), the sampling decision at submit, every stage
recorded where it happens, work stages on the profiler's clock and wait
stages not, the always-on counters and the two exact records on
``TrainResult``."""

import glob
import os
import time

import numpy as np
import pytest

from asyncframework_tpu.engine.executor import ExecutorPool
from asyncframework_tpu.engine.heartbeat import HeartbeatMonitor
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig
from asyncframework_tpu.solvers.instrumentation import RunInstruments
from asyncframework_tpu.utils.clock import SystemClock

EPS_MS = 0.05  # float noise of epoch milliseconds, not a tolerance of order


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2048, 16)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    w = rng.normal(size=16).astype(np.float32)
    return X, X @ w


def _cfg(**kw):
    base = dict(
        num_workers=4, num_iterations=48, gamma=0.4, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=10, seed=3,
        calibration_iters=8, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


def _run(solver_cls, mode, problem, **kw):
    X, y = problem
    solver = solver_cls(X, y, _cfg(**kw))
    return getattr(solver, mode)()


def _traces(log):
    spans, _ = trace.load_trace_events(log)
    return trace.build_traces(spans)


# ------------------------------------------------------------ the span tree
@pytest.mark.parametrize("solver_cls,mode", [
    (ASGD, "run"), (ASAGA, "run"), (ASGD, "run_sync"), (ASAGA, "run_sync"),
])
def test_every_update_is_one_trace_whose_compute_has_four_children(
        solver_cls, mode, problem, tmp_path):
    log = tmp_path / "run.jsonl"
    gamma = 0.4 if solver_cls is ASGD else 0.05
    res = _run(solver_cls, mode, problem, trace_sample=1.0, gamma=gamma,
               num_iterations=48 if mode == "run" else 12,
               event_log=str(log))
    traces = _traces(log)
    complete = 0
    for spans in traces.values():
        by_stage = {}
        for sp in spans:
            by_stage.setdefault(sp.stage, []).append(sp)
        if trace.MERGE_APPLY not in by_stage:
            continue  # still in flight when the run stopped
        complete += 1
        (submit,) = by_stage[trace.SUBMIT]
        (compute,) = by_stage[trace.COMPUTE]
        assert submit.parent_id is None
        assert compute.parent_id == submit.span_id
        assert submit.batch >= 1
        children = []
        for st in trace.COMPUTE_CHILDREN:
            (child,) = by_stage[st]
            assert child.parent_id == compute.span_id, st
            # inside the parent
            assert child.start_ms >= compute.start_ms - EPS_MS, st
            assert (child.start_ms + child.dur_ms
                    <= compute.start_ms + compute.dur_ms + EPS_MS), st
            children.append(child)
        starts = [c.start_ms for c in children]
        assert starts == sorted(starts)
        # each child ends before the next begins: they cover, not overlap
        for a, b in zip(children, children[1:]):
            assert a.start_ms + a.dur_ms <= b.start_ms + EPS_MS
        (apply_span,) = by_stage[trace.MERGE_APPLY]
        assert apply_span.parent_id == compute.span_id
        assert apply_span.staleness is not None
        assert apply_span.accepted is not None
        assert apply_span.batch is not None
        # age of the model basis at merge: at least the task's own time
        assert apply_span.staleness_ms >= compute.dur_ms - EPS_MS
        if mode == "run":
            (queue_span,) = by_stage[trace.MERGE_QUEUE]
            assert queue_span.parent_id == compute.span_id
            # merge.queue starts where compute ends
            assert queue_span.start_ms >= (
                compute.start_ms + compute.dur_ms - EPS_MS
            )
    assert complete >= res.accepted - 8  # all but the last in flight


def test_a_drain_records_one_apply_span_per_update_with_the_batch(
        problem, tmp_path, held_updater):
    """Under a backlog the updater applies several results in one
    dispatch: every sampled update of that dispatch gets ITS
    ``merge.apply`` (same start and duration, ``batch`` = the slots of
    that dispatch), never its duration divided by the batch; and every
    sampled update appears in exactly one ``merge.apply``, also where a
    snapshot split its drain in two."""
    log = tmp_path / "drain.jsonl"
    held_updater(8)
    res = _run(ASGD, "run", problem, trace_sample=1.0,
               num_workers=8, num_iterations=96, event_log=str(log))
    spans, _ = trace.load_trace_events(log)
    applies = [s for s in spans if s.stage == trace.MERGE_APPLY]
    assert applies and all(1 <= s.batch <= 8 for s in applies if s.accepted)
    assert 1 < res.extras["drain_items_max"] <= 8
    assert len({s.trace_id for s in applies}) == len(applies)
    assert len([s for s in applies if s.accepted]) == res.accepted
    by_start = {}
    for s in applies:
        by_start.setdefault((s.start_ms, s.dur_ms), []).append(s)
    # one interval a dispatch
    assert len(by_start) == res.extras["apply_dispatches"]
    shared = [g for g in by_start.values() if len(g) > 1]
    assert shared  # a dispatch of several results shares one interval
    for group in by_start.values():
        assert len({s.batch for s in group}) == 1
        assert group[0].batch == len(group)  # sampled 1 in 1: all of them
    # a drain split at a snapshot's update: the first dispatch ends ON it
    sizes = [g[0].batch for _k, g in sorted(by_start.items())]
    ends = set(np.cumsum(sizes))
    assert all(j * 10 + 1 in ends for j in range(10))


# ------------------------------------ inside task.inbox and task.dispatch
def _inside(child, parent):
    return (child.start_ms >= parent.start_ms - EPS_MS
            and child.start_ms + child.dur_ms
            <= parent.start_ms + parent.dur_ms + EPS_MS)


@pytest.mark.parametrize("solver_cls", [ASGD, ASAGA])
def test_the_inbox_and_the_dispatch_have_their_own_children(
        solver_cls, problem, tmp_path):
    """ISSUE 41: on a sampled update ``task.wake`` lies inside
    ``task.inbox``; ``task.turn``, every ``task.model_copy`` and
    ``task.enqueue`` lie inside ``task.dispatch``, in that order, and do
    not overlap.  Four workers on four of the CPU's devices.  ASAGA's
    model is one buffer on the driver's device: worker 0's shard lies
    there and copies nothing, the others copy the model.  ASGD's model
    lives on every device its shards lie on (ISSUE 47): no task copies
    anything, and ``task.turn`` and ``task.enqueue`` are still inside
    ``task.dispatch``, in order."""
    log = tmp_path / "run.jsonl"
    gamma = 0.4 if solver_cls is ASGD else 0.05
    res = _run(solver_cls, "run", problem, trace_sample=1.0, gamma=gamma,
               event_log=str(log))
    complete = copies = alone = 0
    for spans in _traces(log).values():
        by_stage = {}
        for sp in spans:
            by_stage.setdefault(sp.stage, []).append(sp)
        if trace.MERGE_APPLY not in by_stage:
            continue  # still in flight when the run stopped
        complete += 1
        (inbox,) = by_stage[trace.TASK_INBOX]
        (wake,) = by_stage[trace.TASK_WAKE]
        assert wake.parent_id == inbox.span_id and _inside(wake, inbox)
        (dispatch,) = by_stage[trace.TASK_DISPATCH]
        (turn,) = by_stage[trace.TASK_TURN]
        (enqueue,) = by_stage[trace.TASK_ENQUEUE]
        moved = by_stage.get(trace.TASK_MODEL_COPY, [])
        assert bool(moved) == (solver_cls is ASAGA and inbox.worker_id != 0)
        copies += len(moved)
        inner = [turn, *sorted(moved, key=lambda sp: sp.start_ms), enqueue]
        for sp in inner:
            assert sp.parent_id == dispatch.span_id, sp.stage
            assert _inside(sp, dispatch), sp.stage
        for a, b in zip(inner, inner[1:]):
            assert a.start_ms + a.dur_ms <= b.start_ms + EPS_MS
        assert turn.dur_ms < 5.0  # dense shards: the chip keeps no turns
        (wait,) = by_stage[trace.TASK_DEVICE_WAIT]
        for sp in by_stage.get(trace.TASK_DEVICE_WAIT_ALONE, []):
            alone += 1
            (compute,) = by_stage[trace.COMPUTE]
            assert sp.parent_id == compute.span_id
            assert _inside(sp, wait) and wait.dur_ms - sp.dur_ms < 1.0
    assert complete >= res.accepted - 8
    assert copies >= complete // 2 if solver_cls is ASAGA else copies == 0
    tasks = res.extras["model_reads_local"] + res.extras["model_reads_copied"]
    assert tasks >= res.accepted
    if solver_cls is ASGD:
        assert res.extras["model_reads_copied"] == 0
    else:  # three workers in four lie off the driver's device
        assert res.extras["model_reads_copied"] >= tasks // 2
    assert alone >= 1  # one worker a device here: most tasks are alone


@pytest.mark.parametrize("solver_cls", [ASGD, ASAGA])
def test_a_retried_copy_records_none_of_the_inner_stages(
        solver_cls, problem, tmp_path):
    """The first copy of worker 2's first task runs the closure to its end
    and then raises: the retry is launched through the same inbox and
    enters the same closure, and records nothing a second time."""
    X, y = problem
    log = tmp_path / "retry.jsonl"
    gamma = 0.4 if solver_cls is ASGD else 0.05
    solver = solver_cls(X, y, _cfg(heartbeat=False, trace_sample=1.0,
                                   gamma=gamma, event_log=str(log)))
    real = solver._make_task
    failed = []

    def flaky(wid, *a, **kw):
        fn = real(wid, *a, **kw)
        if wid != 2 or failed:
            return fn
        failed.append(wid)

        def once():
            out = fn()
            if len(failed) == 1:
                failed.append("raised")
                raise RuntimeError("injected task failure")
            return out

        once.on_launch = fn.on_launch
        return once

    solver._make_task = flaky
    res = solver.run()
    assert res.accepted == 48 and res.extras["task_retries"] == 1
    seen = set()
    for spans in _traces(log).values():
        stages = [sp.stage for sp in spans]
        seen.update(stages)
        for st in trace.TASK_STAGES:
            assert stages.count(st) <= 1, (st, stages)
    # (ASGD's tasks copy no model: it lives on every device, ISSUE 47)
    copied = {trace.TASK_MODEL_COPY} if solver_cls is ASGD else set()
    assert set(trace.TASK_STAGES) - copied <= seen
    assert not copied & seen


class _FakeStep:
    """A step's first output on a fake chip: complete when the test says."""

    def __init__(self):
        import threading

        self.waited_for = threading.Event()
        self.complete = threading.Event()

    def block_until_ready(self):
        self.waited_for.set()
        assert self.complete.wait(timeout=10)


def _sampled(inst, workers):
    uts = inst.start_updates(workers)
    with trace.span(trace.SUBMIT, uts.values(), batch=len(workers)):
        inst.begin_compute(uts, 0)
    return uts


def test_a_task_is_alone_where_no_other_step_is_out_on_its_chip(tmp_path):
    """Two workers on one fake chip: the first to enqueue is alone, the
    second, enqueued while the first's step is out, is not; a task
    enqueued after both completed is alone again."""
    import threading

    from asyncframework_tpu.solvers.instrumentation import (
        StepsOut,
        worker_task,
    )

    log = tmp_path / "alone.jsonl"
    inst = RunInstruments(_cfg(trace_sample=1.0, event_log=str(log)), 3)
    uts = _sampled(inst, [0, 1, 2])
    chip = StepsOut()
    steps = [_FakeStep() for _ in range(3)]
    tasks = [worker_task(lambda mine, st=st: (st,), 0.0, uts[wid],
                         worker=wid, steps_out=chip)
             for wid, st in enumerate(steps)]
    threads = [threading.Thread(target=t) for t in tasks]
    threads[0].start()
    assert steps[0].waited_for.wait(timeout=10)
    threads[1].start()
    assert steps[1].waited_for.wait(timeout=10)
    time.sleep(0.005)
    steps[0].complete.set()
    steps[1].complete.set()
    for th in threads[:2]:
        th.join(timeout=10)
        assert not th.is_alive()
    steps[2].complete.set()
    tasks[2]()
    inst.close()
    spans, _ = trace.load_trace_events(log)
    alone = {sp.worker_id: sp for sp in spans
             if sp.stage == trace.TASK_DEVICE_WAIT_ALONE}
    waits = {sp.worker_id: sp for sp in spans
             if sp.stage == trace.TASK_DEVICE_WAIT}
    assert sorted(alone) == [0, 2] and sorted(waits) == [0, 1, 2]
    assert alone[0].dur_ms >= 5.0
    for wid, sp in alone.items():
        assert _inside(sp, waits[wid])
        assert waits[wid].dur_ms - sp.dur_ms < 1.0
        assert sp.parent_id == waits[wid].parent_id  # both under compute


def test_a_task_posts_no_span_in_front_of_its_enqueue(tmp_path):
    """The stages a task records on its way to the chip (the wake-up, the
    inbox, the turn, the copies) go to the sink behind the enqueue: a
    span's post wakes the bus's thread, and on the chip that made a
    sampled dispatch 0.35 ms longer than an unsampled one (PERF.md section
    6, PR 41).  By the device's wait they have all been handed over."""
    from asyncframework_tpu.solvers.instrumentation import worker_task

    log = tmp_path / "held.jsonl"
    inst = RunInstruments(_cfg(trace_sample=1.0, event_log=str(log)), 1)
    (ut,) = _sampled(inst, [0]).values()
    posted = []
    real_sink = ut._sink
    ut._sink = lambda sp: (posted.append(sp.stage), real_sink(sp))
    seen_at = {}

    class _Step(_FakeStep):
        def block_until_ready(self):
            seen_at["wait"] = list(posted)

    def dispatch(mine):
        with trace.span(trace.TASK_ENQUEUE, mine):
            seen_at["enqueue"] = list(posted)
        return (_Step(),)

    task = worker_task(dispatch, 0.0, ut)
    task.on_launch()
    task()
    inst.close()
    assert seen_at["enqueue"] == []
    assert seen_at["wait"] == [
        trace.TASK_WAKE, trace.TASK_INBOX, trace.TASK_TURN,
        trace.TASK_ENQUEUE, trace.TASK_DISPATCH]
    assert posted[5:] == [trace.TASK_DEVICE_WAIT]


def test_a_held_turn_shows_in_task_turn_and_not_in_task_enqueue(tmp_path):
    import threading

    from asyncframework_tpu.solvers.engine_loop import DispatchTurns
    from asyncframework_tpu.solvers.instrumentation import worker_task

    log = tmp_path / "turn.jsonl"
    inst = RunInstruments(_cfg(trace_sample=1.0, event_log=str(log)), 1)
    (ut,) = _sampled(inst, [0]).values()
    turns = DispatchTurns(patience_s=10.0)
    ahead = turns.ticket()  # a task built first that has not dispatched

    def dispatch(mine):
        with trace.span(trace.TASK_ENQUEUE, mine):
            time.sleep(0.002)
        step = _FakeStep()
        step.complete.set()
        return (step,)

    th = threading.Thread(target=worker_task(dispatch, 0.0, ut, turns=turns))
    th.start()
    time.sleep(0.03)
    turns.served(ahead)
    th.join(timeout=10)
    assert not th.is_alive()
    inst.close()
    spans, _ = trace.load_trace_events(log)
    by_stage = {sp.stage: sp for sp in spans}
    turn, enqueue = by_stage[trace.TASK_TURN], by_stage[trace.TASK_ENQUEUE]
    dispatch_span = by_stage[trace.TASK_DISPATCH]
    assert turn.dur_ms >= 25.0 and 1.5 <= enqueue.dur_ms < 20.0
    assert turn.start_ms + turn.dur_ms <= enqueue.start_ms + EPS_MS
    assert _inside(turn, dispatch_span) and _inside(enqueue, dispatch_span)
    assert dispatch_span.dur_ms >= turn.dur_ms + enqueue.dur_ms - EPS_MS


# ------------------------------------------- the stages on the profiler's clock
def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            names.update(ev.name for ev in line.events
                         if ev.name.startswith(trace.ANNOTATION_PREFIX))
    return names


@pytest.mark.parametrize("solver_cls", [ASGD, ASAGA])
def test_work_stages_land_in_the_host_plane_and_wait_stages_do_not(
        solver_cls, problem, tmp_path):
    import jax

    trace_dir = str(tmp_path / "xplane")
    gamma = 0.4 if solver_cls is ASGD else 0.05
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        # no recorder: the annotations need no handle
        res = _run(solver_cls, "run", problem, trace_sample=None, gamma=gamma)
    finally:
        jax.profiler.stop_trace()
    assert res.accepted == 48
    names = _host_events(trace_dir)
    for stage in (trace.SUBMIT, trace.TASK_DISPATCH, trace.MERGE_QUEUE,
                  trace.MERGE_APPLY, trace.SNAPSHOT):
        assert trace.ANNOTATION_PREFIX + stage in names, (stage, sorted(names))
    for stage in (trace.TASK_INBOX, trace.TASK_DEVICE_WAIT,
                  trace.RESULT_QUEUE, trace.COMPUTE):
        assert trace.ANNOTATION_PREFIX + stage not in names, stage
    # nothing but the work stages and the submitter's two holds (ISSUE 34:
    # waits of one thread with a cause; tests/test_engine_account.py)
    assert names <= {
        trace.ANNOTATION_PREFIX + st
        for st in trace.WORK_STAGES | trace.HOLD_STAGES
    }
    assert trace.ANNOTATION_PREFIX + trace.WAIT_WORKERS not in names


def test_span_records_nothing_and_reads_no_clock_without_a_handle(
        monkeypatch):
    def boom():
        raise AssertionError("a span without a handle read the clock")

    monkeypatch.setattr(trace, "now_ms", boom)
    for stage in (trace.TASK_DISPATCH, trace.TASK_DEVICE_WAIT):
        with trace.span(stage, None, batch=2) as sp:
            pass
        assert sp.start_ms == 0.0
    with trace.span(trace.MERGE_APPLY, []):  # a drain with nothing sampled
        pass


# --------------------------------------------------------- tracing off is off
def test_trace_sample_none_builds_no_recorder_and_no_handle(problem):
    inst = RunInstruments(_cfg(trace_sample=None), 4)
    try:
        assert inst.tracer is None
        assert inst.start_updates([0, 1, 2, 3]) == {}
    finally:
        inst.close()
    handles = []
    real = RunInstruments.on_drained

    def spy(self, results):
        handles.extend(r.trace for r in results)
        return real(self, results)

    trace.reset_aggregator()
    RunInstruments.on_drained = spy
    try:
        res = _run(ASGD, "run", problem, trace_sample=None)
    finally:
        RunInstruments.on_drained = real
    assert res.accepted == 48 and len(handles) >= 48
    assert all(h is None for h in handles)
    assert trace.aggregator().snapshot()["spans"] == 0
    # the counters are there all the same
    for key in ("updater_busy_s", "updater_wait_s", "submitter_busy_s",
                "submitter_wait_s", "submit_empty_polls", "drains",
                "drain_items_max", "task_retries", "compiles_in_run",
                "host_stall_max_ms", "host_stalls"):
        assert isinstance(res.extras[key], (int, float)), key


def test_sampling_falls_at_submit_once_per_interval(problem):
    inst = RunInstruments(_cfg(trace_sample=0.25), 4)
    try:
        got = [sorted(inst.start_updates([0, 1])) for _ in range(8)]
    finally:
        inst.close()
    # counter-based per worker: the first of every four, first included
    assert got == [[0, 1], [], [], [], [0, 1], [], [], []]


# --------------------------------------------------------------- the counters
@pytest.mark.parametrize("solver_cls,mode", [
    (ASGD, "run"), (ASAGA, "run"), (ASGD, "run_sync"),
])
def test_counters_add_up(solver_cls, mode, problem):
    gamma = 0.4 if solver_cls is ASGD else 0.05
    t0 = time.monotonic()
    res = _run(solver_cls, mode, problem, gamma=gamma,
               num_iterations=48 if mode == "run" else 12)
    wall = time.monotonic() - t0
    ex = res.extras
    for thread in ("updater",) + (("submitter",) if mode == "run" else ()):
        busy, wait = ex[thread + "_busy_s"], ex[thread + "_wait_s"]
        assert busy >= 0 and wait >= 0
        # a thread's busy and waiting time lie inside the call
        assert busy + wait <= wall
        assert busy + wait >= 0.5 * res.elapsed_s
    if mode != "run":
        assert "submitter_busy_s" not in ex
    assert ex["drains"] >= res.accepted / max(1, ex["drain_items_max"])
    assert ex["task_retries"] == 0
    assert ex["compiles_in_run"] == 0  # the solver's warm-up came before
    assert ex["host_stalls"] >= 0 and ex["host_stall_max_ms"] >= 0.0


@pytest.mark.parametrize("ran_first", [False, True])
def test_a_raised_task_is_counted_as_a_retry(ran_first, problem, tmp_path):
    """``ran_first``: the first copy of the task runs the closure to its
    end and then raises, so the retry enters the same closure again.  Only
    the first copy to run records the task stages."""
    X, y = problem
    log = tmp_path / "retry.jsonl"
    solver = ASGD(X, y, _cfg(heartbeat=False, trace_sample=1.0,
                             event_log=str(log)))
    real = solver._make_task
    failed = []

    def flaky(wid, *a, **kw):
        fn = real(wid, *a, **kw)
        if wid == 2 and not failed:
            failed.append(wid)

            def once():
                if len(failed) == 1:
                    failed.append("raised")
                    if ran_first:
                        fn()
                    raise RuntimeError("injected task failure")
                return fn()

            return once
        return fn

    solver._make_task = flaky
    res = solver.run()
    assert res.accepted == 48
    assert res.extras["task_retries"] == 1
    for spans in _traces(log).values():
        stages = [sp.stage for sp in spans]
        for st in trace.COMPUTE_CHILDREN:
            assert stages.count(st) <= 1, stages


@pytest.mark.parametrize("solver_cls,taw", [(ASGD, 1), (ASAGA, 20)])
def test_staleness_hist_counts_every_merged_result(solver_cls, taw, problem):
    gamma = 0.4 if solver_cls is ASGD else 0.05
    # ASAGA's filter (k - staleness <= taw) stops accepting for good once
    # k passes taw: that run ends at its deadline, all the rest dropped
    res = _run(solver_cls, "run", problem, taw=taw, gamma=gamma,
               run_timeout_s=2.0)
    assert res.dropped > 0  # the filter fired: both kinds are counted
    assert sum(res.staleness_hist.values()) == res.accepted + res.dropped
    assert list(res.staleness_hist) == sorted(res.staleness_hist)
    if solver_cls is ASGD:
        assert sum(n for s, n in res.staleness_hist.items()
                   if s > taw) == res.dropped


@pytest.mark.parametrize("backlog", [False, True])
def test_snapshot_updates_are_the_accepted_counts_behind_the_trajectory(
        backlog, problem, held_updater):
    if backlog:
        held_updater(8)
    res = _run(ASGD, "run", problem, num_workers=8,
               num_iterations=64, printer_freq=5)
    ups = res.snapshot_updates
    assert len(ups) == len(res.trajectory)
    assert ups[0] == 0 and ups[-1] == res.accepted == 64
    assert ups == sorted(ups)
    # folded or not: the model after update j * printer_freq + 1
    assert ups[1:-1] == [j * 5 + 1 for j in range(len(ups) - 2)]
    assert (res.extras["drain_items_max"] > 1) or not backlog


# ------------------------------------------------------------- the host gauge
class _JumpingClock(SystemClock):
    """A host that stands still: every clock read after ``jump()`` is 3 s
    later than it would have been."""

    def __init__(self):
        self.offset_ms = 0.0

    def now_ms(self):
        return super().now_ms() + self.offset_ms


def _monitor(clock, **kw):
    pool = ExecutorPool(2, lambda *a: None, clock=clock)
    mon = HeartbeatMonitor(
        pool, lambda wid: None, timeout_ms=120_000.0,
        check_interval_s=0.05, clock=clock, **kw,
    )
    return pool, mon


def test_a_clock_that_jumps_three_seconds_is_a_host_stall():
    clock = _JumpingClock()
    pool, mon = _monitor(clock)
    mon.start()
    try:
        time.sleep(0.2)
        quiet = mon.stalls  # a loaded test host may already have stalled
        clock.offset_ms = 3000.0
        time.sleep(0.2)
    finally:
        mon.stop()
        pool.shutdown()
    assert 2900.0 <= mon.stall_max_ms <= 3000.0 + 150.0
    assert mon.stalls >= quiet + 1


def test_the_stall_dump_is_armed_only_with_a_recorder_and_cancelled_at_stop(
        monkeypatch, problem):
    import faulthandler

    calls = []
    monkeypatch.setattr(faulthandler, "dump_traceback_later",
                        lambda *a, **kw: calls.append(("arm", a)))
    monkeypatch.setattr(faulthandler, "cancel_dump_traceback_later",
                        lambda: calls.append(("cancel",)))
    res = _run(ASGD, "run", problem, trace_sample=None)
    assert res.accepted == 48 and calls == []
    res = _run(ASGD, "run", problem, trace_sample=0.5)
    assert res.accepted == 48
    assert calls[0] == ("arm", (1.0,)) and calls[-1] == ("cancel",)
    assert calls.count(("cancel",)) == 1


def test_the_stall_dump_has_one_owner_in_a_process(monkeypatch):
    """``faulthandler``'s watchdog is process-wide: of two monitors that
    ask for it, the first to arm it owns it, and only the owner cancels."""
    import faulthandler

    from asyncframework_tpu.engine import heartbeat

    calls = []
    monkeypatch.setattr(faulthandler, "dump_traceback_later",
                        lambda *a, **kw: calls.append("arm"))
    monkeypatch.setattr(faulthandler, "cancel_dump_traceback_later",
                        lambda: calls.append("cancel"))
    clock = SystemClock()
    pool_a, first = _monitor(clock, dump_on_stall=True)
    pool_b, second = _monitor(clock, dump_on_stall=True)
    first.start()
    try:
        time.sleep(0.12)
        assert heartbeat._dump_owner is first
        second.start()
        time.sleep(0.12)
        second.stop()
        assert "cancel" not in calls and heartbeat._dump_owner is first
    finally:
        first.stop()
        second.stop()
        pool_a.shutdown()
        pool_b.shutdown()
    assert calls.count("cancel") == 1 and heartbeat._dump_owner is None


def test_the_monitors_own_scan_is_not_a_host_stall():
    """How late a scan woke counts from the end of the last scan: a scan
    that itself takes long (an executor replaced) is no stall."""
    clock = SystemClock()
    pool, mon = _monitor(clock)
    real = mon.check_once

    def slow_scan():
        time.sleep(0.3)
        return real()

    mon.check_once = slow_scan
    mon.start()
    try:
        time.sleep(0.9)
    finally:
        mon.stop()
        pool.shutdown()
    assert mon.stall_max_ms < 250.0
