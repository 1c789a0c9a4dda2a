"""The injected straggler delay, its stage and its account (ISSUE 51):
``engine/straggler.py: DelayModel`` against ``benchmark/reference_delay.py``
(the reference's model restated with no program code); an engine run's
``TrainResult.extras`` against a replay of the run's own log; and the span
tree: ``task.delay`` is a child of ``compute``, once a delayed sampled task,
never on a retry or a speculative copy.  Counts and identities, never a
rate."""

import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_delay, reference_delay  # noqa: E402

from asyncframework_tpu.engine.straggler import DelayModel  # noqa: E402
from asyncframework_tpu.metrics import trace  # noqa: E402
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig  # noqa: E402
from asyncframework_tpu.solvers import engine_loop  # noqa: E402
from asyncframework_tpu.solvers.instrumentation import (  # noqa: E402
    RunInstruments,
    worker_task,
)

ACCOUNT = ("avg_delay_ms", "delay_calibrated_at_update",
           "delay_calibrated_at_s", "straggler_workers", "delayed_tasks",
           "delay_sleep_s", "delay_sleep_long_tail_s",
           "accepted_from_stragglers", "accepted_after_calibration",
           # ISSUE 58: the age of a worker's history at its commit, by
           # class (tests/test_asaga_cloud.py)
           "history_age_late_sum", "history_age_late_n",
           "history_age_healthy_sum", "history_age_healthy_n")
EPS_MS = 0.05  # float noise of epoch milliseconds, not a tolerance of order


# ---------------------------------------------- the model and the reference
@pytest.mark.parametrize("seed", [1, 42, 3_000_000_019])
@pytest.mark.parametrize("n", [4, 8, 32, 33])
def test_the_model_is_late_where_and_as_long_as_the_reference(n, seed):
    model = DelayModel(-1.0, n, seed)
    late = reference_delay.late_workers(n)
    assert sorted(model.stragglers) == sorted(late)
    for wid in range(n):
        assert model.long_tail(wid) == (
            late.get(wid) == reference_delay.LONG_TAIL), wid
    # nobody sleeps before the scale is known
    assert [model.delay_ms(w) for w in range(n)] == [0.0] * n
    assert model.account([0] * n)["delayed_tasks"] == 0
    model.calibrate(13.37, at_update=3 * n, at_s=2.0)
    # tasks are built a cohort at a time, in worker order, and a cohort
    # holds whoever is back: three rounds of differing membership
    order = [w for r in range(3) for w in range(n) if (w + r) % 3]
    log = [(w, model.delay_ms(w)) for w in order]
    workers, slept = reference_delay.split(log)
    assert set(workers) <= set(late)
    assert [w for w in order if w in late] == workers
    assert slept == reference_delay.sleeps(seed, 13.37, workers, n)
    # every multiplier inside its class's range
    for w, ms in zip(workers, slept):
        lo, hi = reference_delay.RANGE[late[w]]
        assert round(lo * 13.37) <= ms <= round(hi * 13.37), (w, ms)
    got = model.account([7] * n)
    tail = [ms for w, ms in zip(workers, slept)
            if late[w] == reference_delay.LONG_TAIL]
    assert got["delayed_tasks"] == len(slept)
    assert got["delay_sleep_s"] == pytest.approx(sum(slept) / 1e3, rel=1e-9)
    assert got["delay_sleep_long_tail_s"] == pytest.approx(
        sum(tail) / 1e3, rel=1e-9)
    assert got["straggler_workers"] == len(late)
    assert got["accepted_from_stragglers"] == 7 * len(late)
    assert got["accepted_after_calibration"] == 7 * n - 3 * n
    assert (got["avg_delay_ms"], got["delay_calibrated_at_update"],
            got["delay_calibrated_at_s"]) == (13.37, 3 * n, 2.0)


def test_the_issues_fleet_of_32_and_the_controlled_delay():
    assert reference_delay.late_workers(32) == {
        0: "long_tail", 4: "long_tail", 8: "normal", 12: "normal",
        16: "normal", 20: "normal", 24: "normal", 28: "normal"}
    assert reference_delay.late_workers(8, 0.0) == {}
    assert reference_delay.late_workers(8, 1.0) == {0: "normal"}
    # coeff 1.0: worker 0 alone, the scale itself, no draw
    model = DelayModel(1.0, 8, seed=5)
    model.calibrate(9.6)
    log = [(w, model.delay_ms(w)) for w in (0, 1, 2, 0, 7, 0)]
    workers, slept = reference_delay.split(log)
    assert workers == [0, 0, 0] and slept == [10.0] * 3
    assert slept == reference_delay.sleeps(5, 9.6, workers, 8, 1.0)
    with pytest.raises(ValueError):
        reference_delay.sleeps(5, 9.6, [1], 8, 1.0)
    assert model.stragglers == [0] and not model.long_tail(0)
    assert model.account([3] * 8)["accepted_from_stragglers"] == 3


def test_a_model_that_is_off_or_not_yet_calibrated_reports_zeros():
    off = DelayModel(0.0, 32, seed=1)
    off.calibrate(13.0, at_update=3201, at_s=1.9)  # the engine always does
    assert [off.delay_ms(w) for w in range(32)] == [0.0] * 32
    assert off.account([10] * 32) == dict.fromkeys(ACCOUNT, 0)
    waiting = DelayModel(-1.0, 32, seed=1)
    got = waiting.account([10] * 32)
    assert got.pop("straggler_workers") == 8
    assert set(got.values()) == {0}


# ------------------------------------------- an engine run against its log
@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(2048, 16)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    w = rng.normal(size=16).astype(np.float32)
    return X, X @ w


def _cfg(**kw):
    base = dict(
        num_workers=4, num_iterations=64, gamma=0.4, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=10, seed=3,
        calibration_iters=8, run_timeout_s=60.0, coeff=-1.0,
    )
    base.update(kw)
    return SolverConfig(**base)


@pytest.fixture()
def run_log(monkeypatch):
    """One log of each engine run made in the test, in call order: every
    task built with the delay it was given, every merged result, the
    calibration's end.  The patch is ``benchmark/check_delay.py``'s own,
    undone after the test; the newest run's log is the list's last."""
    monkeypatch.setattr(engine_loop, "DelayModel", engine_loop.DelayModel)
    monkeypatch.setattr(RunInstruments, "on_gradient_merged",
                        RunInstruments.on_gradient_merged)
    return check_delay._log_the_runs(halve=False)


@pytest.mark.parametrize("solver_cls,mode", [
    (ASGD, "run"), (ASAGA, "run"), (ASGD, "run_sync"), (ASAGA, "run_sync"),
])
def test_the_account_is_a_replay_of_the_runs_own_log(
        solver_cls, mode, problem, run_log):
    X, y = problem
    sync = mode == "run_sync"
    cfg = _cfg(gamma=0.4 if solver_cls is ASGD else 0.05,
               # a sync run counts rounds, in both the budget and the
               # calibration
               num_iterations=16 if sync else 64,
               calibration_iters=3 if sync else 8)
    res = getattr(solver_cls(X, y, cfg), mode)()
    extras, run_log = res.extras, run_log[-1]
    (at,) = [i for i, e in enumerate(run_log) if e[0] == "calibrated"]
    _, scale, at_update, at_s = run_log[at]
    before = sum(1 for e in run_log[:at] if e[0] == "merged" and e[2])
    tasks = [(i, e[1], e[2]) for i, e in enumerate(run_log)
             if e[0] == "task"]
    workers, slept = reference_delay.split([(w, ms) for _, w, ms in tasks])
    # four workers: one is late, worker 0, of the normal class
    assert reference_delay.late_workers(4) == {0: "normal"}
    assert set(workers) == {0} and extras["straggler_workers"] == 1
    # nobody slept before the calibration's end, which the log places
    # where the account does
    assert not [i for i, _w, ms in tasks if ms > 0 and i < at]
    assert extras["delay_calibrated_at_update"] == at_update == before
    assert before > cfg.calibration_iters * (4 if sync else 1)
    assert extras["delay_calibrated_at_s"] == at_s > 0
    assert extras["avg_delay_ms"] == scale == res.avg_delay_ms > 0
    # the schedule is the reference's, from the seed, the scale and the
    # order the delayed tasks were built in
    assert slept == reference_delay.sleeps(cfg.seed, scale, workers, 4)
    assert extras["delayed_tasks"] == len(slept) > 0
    assert extras["delay_sleep_s"] == pytest.approx(
        sum(slept) / 1e3, rel=1e-9)
    assert extras["delay_sleep_long_tail_s"] == 0.0
    from_late = sum(1 for e in run_log
                    if e[0] == "merged" and e[2] and e[1] == 0)
    assert extras["accepted_from_stragglers"] == from_late > 0
    assert extras["accepted_after_calibration"] == res.accepted - before > 0
    assert all(isinstance(extras[k], (int, float)) for k in ACCOUNT)


@pytest.mark.parametrize("solver_cls", [ASGD, ASAGA], ids=["asgd", "asaga"])
def test_a_run_at_coeff_zero_reports_zeros_and_records_no_delay(
        solver_cls, problem, run_log, tmp_path):
    X, y = problem
    log = tmp_path / "steady.jsonl"
    cfg = _cfg(coeff=0.0, trace_sample=1.0, event_log=str(log),
               gamma=0.4 if solver_cls is ASGD else 0.05)
    res = solver_cls(X, y, cfg).run()
    run_log = run_log[-1]
    assert res.accepted == 64
    assert {k: res.extras[k] for k in ACCOUNT} == dict.fromkeys(ACCOUNT, 0)
    # the calibration ran (``TrainResult.avg_delay_ms`` is the
    # calibrator's, as ever) and every task was given 0.0
    assert res.avg_delay_ms > 0
    assert [e for e in run_log if e[0] == "calibrated"]
    assert {e[2] for e in run_log if e[0] == "task"} == {0.0}
    spans, _ = trace.load_trace_events(log)
    assert trace.TASK_DELAY not in {sp.stage for sp in spans}
    assert trace.TASK_DISPATCH in {sp.stage for sp in spans}


# ------------------------------------------------------------ the span tree
def _inside(child, parent):
    return (child.start_ms >= parent.start_ms - EPS_MS
            and child.start_ms + child.dur_ms
            <= parent.start_ms + parent.dur_ms + EPS_MS)


@pytest.mark.parametrize("solver_cls", [ASGD, ASAGA], ids=["asgd", "asaga"])
def test_the_delay_is_a_child_of_compute_once_a_delayed_sampled_task(
        solver_cls, problem, run_log, tmp_path):
    X, y = problem
    log = tmp_path / "cloud.jsonl"
    # twelve workers: 0 is of the long tail, 4 and 8 of the normal class
    cfg = _cfg(num_workers=12, num_iterations=240, calibration_iters=24,
               trace_sample=1.0, event_log=str(log),
               gamma=0.3 if solver_cls is ASGD else 0.05)
    res = solver_cls(X, y, cfg).run()
    run_log = run_log[-1]
    assert res.extras["straggler_workers"] == 3
    traces = trace.build_traces(trace.load_trace_events(log)[0])
    delayed = 0
    for spans in traces.values():
        by_stage = {}
        for sp in spans:
            by_stage.setdefault(sp.stage, []).append(sp)
        if trace.MERGE_APPLY not in by_stage:
            continue  # still in flight when the run stopped
        (compute,) = by_stage[trace.COMPUTE]
        delays = by_stage.get(trace.TASK_DELAY, [])
        assert len(delays) <= 1
        if not delays:
            continue
        (delay,) = delays
        delayed += 1
        assert delay.parent_id == compute.span_id and _inside(delay, compute)
        assert delay.worker_id in (0, 4, 8)
        assert delay.delay_class == (
            "long_tail" if delay.worker_id == 0 else "normal")
        # between the inbox and the dispatch: the five tile ``compute``
        (inbox,) = by_stage[trace.TASK_INBOX]
        (dispatch,) = by_stage[trace.TASK_DISPATCH]
        assert inbox.start_ms + inbox.dur_ms <= delay.start_ms + EPS_MS
        assert delay.start_ms + delay.dur_ms <= dispatch.start_ms + EPS_MS
        covered = sum(by_stage[st][0].dur_ms for st in trace.COMPUTE_CHILDREN)
        assert covered + delay.dur_ms <= compute.dur_ms + 5 * EPS_MS
    # every sampled delayed task that was merged recorded one; the sleep is
    # no shorter than what the model asked for
    asked = [ms for kind, _w, ms in (e[:3] for e in run_log if e[0] == "task")
             if ms > 0]
    assert 0 < delayed <= len(asked) == res.extras["delayed_tasks"]
    spans = [sp for sp in trace.load_trace_events(log)[0]
             if sp.stage == trace.TASK_DELAY]
    # one a delayed task, but for the three that can be asleep at the end
    assert len(asked) - 3 <= len(spans) <= len(asked)
    assert len({sp.trace_id for sp in spans}) == len(spans)
    assert min(sp.dur_ms for sp in spans) >= min(asked) - EPS_MS
    # the other workers' tasks record none
    assert {sp.worker_id for sp in spans} <= {0, 4, 8}


class _Step:
    def block_until_ready(self):
        pass


def _sampled(inst, workers):
    uts = inst.start_updates(workers)
    with trace.span(trace.SUBMIT, uts.values(), batch=len(workers)):
        inst.begin_compute(uts, 0)
    return uts


def test_a_retry_or_a_speculative_copy_neither_sleeps_nor_records(tmp_path):
    """The delay models a slow MACHINE: the closure's first body to run
    sleeps and records ``task.delay``; the same closure run again (a retry
    on a replacement executor, a speculative copy beside the first) goes
    straight to its dispatch."""
    log = tmp_path / "copies.jsonl"
    cfg = _cfg(trace_sample=1.0, event_log=str(log))
    inst = RunInstruments(cfg, 2)
    uts = _sampled(inst, [0, 1])
    late = worker_task(lambda mine: (_Step(),), 80.0, uts[0], worker=0,
                       long_tail=True)
    healthy = worker_task(lambda mine: (_Step(),), 0.0, uts[1], worker=1)
    took = []
    for fn in (late, late, healthy):
        t0 = time.perf_counter()
        fn()
        took.append((time.perf_counter() - t0) * 1e3)
    assert took[0] >= 80.0 and took[1] < 50.0 and took[2] < 50.0
    # a speculative copy that starts WHILE the first body sleeps
    (ut,) = _sampled(inst, [0]).values()
    again = worker_task(lambda mine: (_Step(),), 120.0, ut, worker=0)
    first = threading.Thread(target=again)
    first.start()
    time.sleep(0.005)
    t0 = time.perf_counter()
    again()
    copy_ms = (time.perf_counter() - t0) * 1e3
    first.join(timeout=10)
    assert not first.is_alive() and copy_ms < 80.0
    inst.close()
    spans, _ = trace.load_trace_events(log)
    delays = [sp for sp in spans if sp.stage == trace.TASK_DELAY]
    assert [(sp.worker_id, sp.delay_class) for sp in delays] == [
        (0, "long_tail"), (0, "normal")]
    assert delays[0].dur_ms >= 80.0 and delays[1].dur_ms >= 120.0
    # one dispatch a sampled update all the same: the first copy's
    assert len([sp for sp in spans
                if sp.stage == trace.TASK_DISPATCH]) == 3


def test_an_unsampled_delayed_task_sleeps_and_records_nothing(monkeypatch):
    def boom():
        raise AssertionError("a span without a handle read the clock")

    fn = worker_task(lambda mine: (_Step(),), 20.0, None, worker=0)
    monkeypatch.setattr(trace, "now_ms", boom)
    t0 = time.perf_counter()
    fn()
    assert (time.perf_counter() - t0) * 1e3 >= 20.0


def test_the_delay_is_annotated_in_a_profiler_session(problem, tmp_path):
    """``async.task.delay`` lands in the host plane of a device trace (a
    wait with a cause, like the submitter's two holds), with no recorder:
    ``trace_reduce`` can then name the gap a sleeper leaves."""
    import glob

    import jax

    X, y = problem
    trace_dir = str(tmp_path / "xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        res = ASGD(X, y, _cfg(trace_sample=None)).run()
    finally:
        jax.profiler.stop_trace()
    assert res.extras["delayed_tasks"] > 0
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    names = [ev.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith(trace.ANNOTATION_PREFIX)]
    want = trace.ANNOTATION_PREFIX + trace.TASK_DELAY
    assert want == "async.task.delay"
    assert 0 < names.count(want) <= res.extras["delayed_tasks"]
    # a steady run under the profiler annotates none
    assert trace.TASK_DELAY not in trace.WORK_STAGES | trace.HOLD_STAGES


def test_the_stage_is_in_the_vocabulary_and_rides_the_wire():
    assert trace.PARENT[trace.TASK_DELAY] == trace.COMPUTE
    assert trace.TASK_DELAY in trace.STAGES
    at = trace.STAGES.index
    assert at(trace.TASK_INBOX) < at(trace.TASK_DELAY) < at(
        trace.TASK_DISPATCH)
    sp = trace.Span(stage=trace.TASK_DELAY, trace_id="t", span_id="s",
                    parent_id="p", worker_id=4, model_version=3,
                    start_ms=1.0, dur_ms=27.0, delay_class="normal")
    back = trace.Span.from_wire(sp.to_wire())
    assert back == sp and sp.to_wire()["dc"] == "normal"
    ev = trace.span_event(sp, 5.0)
    assert ev.delay_class == "normal" and ev.stage == "task.delay"
