"""Who waits for whom on the host (ISSUE 53): the clock on the WAIT at the
engine's locks (``instrumentation.ClockedLock``: always on, no clock read
where nobody contends, a wait booked to the waiter's role and the
holder's), the run-wide count of PJRT calls in progress
(``instrumentation.CallsIn``) and the two fields a sampled task's
``task.enqueue`` span carries for it (``calls_in``, ``cpu_ms``), and what
of all that reaches a run's ``extras`` and the aggregator."""

import glob
import os
import threading
import time

import numpy as np
import pytest

from asyncframework_tpu.context import AsyncContext
from asyncframework_tpu.engine.executor import DeviceExecutor
from asyncframework_tpu.engine.job import TaskSpec
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.net import lockwatch
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig
from asyncframework_tpu.solvers import engine_loop, instrumentation
from asyncframework_tpu.solvers.instrumentation import (
    UNCOUNTED,
    CallsIn,
    ClockedLock,
    enqueue_step,
    lock_wait_counters,
    on_device,
)

HOLD_S = 0.06
#: a held lock is waited out to within this (a sleeping holder wakes late,
#: a waiter is scheduled late; never early by more than a timer's tick)
EARLY_S, LATE_S = 0.005, 0.25


@pytest.fixture(autouse=True)
def _main_role():
    """Every test starts and ends on a thread that said nothing."""
    trace.set_role(trace.MAIN)
    yield
    trace.set_role(trace.MAIN)


def _locks():
    return {
        "plain": ClockedLock("state"),
        "reentrant": ClockedLock("context", threading.RLock()),
        "alias": ClockedLock("key").alias("history"),
        "watched": ClockedLock("key", lockwatch.WatchedLock("engine.key")),
    }


def _from_another_thread(fn):
    """``fn()`` on a thread of its own (an ``RLock`` lets its owner in
    again: whether a lock is HELD is asked from elsewhere)."""
    got = []
    t = threading.Thread(target=lambda: got.append(fn()))
    t.start()
    t.join(5)
    (value,) = got
    return value


class _NoClock:
    """``time``, with every clock a ``ClockedLock`` could read broken."""

    def __getattr__(self, name):
        raise AssertionError(f"an uncontended lock read time.{name}")


# ------------------------------------------------------- the uncontended path
@pytest.mark.parametrize("kind", ["plain", "reentrant", "alias", "watched"])
def test_an_uncontended_enter_and_exit_read_no_clock_and_book_nothing(
        kind, monkeypatch):
    lock = _locks()[kind]
    monkeypatch.setattr(instrumentation, "time", _NoClock())
    trace.set_role(trace.UPDATER)
    for _ in range(3):
        with lock as got:
            assert got is lock
            # nothing was written for a waiter to read: the lock itself
            # says who holds it (a ``WatchedLock`` names no owner)
            assert lock.holder() == (
                trace.NOBODY if kind == "watched" else trace.UPDATER)
    assert lock.waits_ns == {} and lock.contended == 0
    assert (lock.max_ns, lock.max_at) == (0, None)
    assert lock.holder() == trace.NOBODY  # free again
    # exit is the inner lock's own, in C: no Python frame of this class
    assert type(lock).__exit__ == lock._inner.__exit__
    assert isinstance(lock, ClockedLock) and type(lock) is not ClockedLock


def test_a_lock_that_nobody_named_a_role_for_is_held_by_main():
    lock = ClockedLock("state")

    def body():
        with lock:
            return lock.holder()

    assert _from_another_thread(body) == trace.MAIN


def test_the_holder_is_read_from_the_locks_own_account_of_its_owner():
    """What ``holder`` rests on: CPython's ``RLock`` names its owner's
    thread id in its ``repr`` (this installation's; a format that moved
    would read ``nobody`` everywhere, and this says so first)."""
    inner = threading.RLock()
    assert "owner=0 " in repr(inner)
    with inner:
        assert f"owner={threading.get_ident()} " in repr(inner)
    lock = ClockedLock("key", inner)
    trace.set_role(trace.SUBMITTER)
    assert trace.role_of(threading.get_ident()) == trace.SUBMITTER
    assert trace.role_of(1) == trace.MAIN  # a thread that never said

    def says_and_ends():
        trace.set_role(trace.EXECUTOR)
        return threading.get_ident()

    # an id outlives its thread (the next thread may be handed it): the
    # role that was said under it does not
    gone = _from_another_thread(says_and_ends)
    assert trace.role_of(gone) == trace.MAIN
    with lock:
        assert _from_another_thread(lock.holder) == trace.SUBMITTER
        with lock:  # entered again: the same owner
            assert lock.holder() == trace.SUBMITTER
        assert lock.holder() == trace.SUBMITTER
    assert lock.holder() == trace.NOBODY


# --------------------------------------------------------- a wait, and whose
def _hold_and_wait(lock, holder_role, waiter_role, waiter_lock=None,
                   nested=False):
    """One thread takes ``lock`` as ``holder_role`` and keeps it
    ``HOLD_S``; a second, as ``waiter_role``, comes while it is held (at
    ``waiter_lock``, an alias of it, where given).  Returns the seconds
    the waiter stood, on the test's own clock."""
    held, stood = threading.Event(), []

    def holder():
        trace.set_role(holder_role)
        with lock:
            if nested:
                with lock:  # a re-entrant lock, entered again and left
                    pass
            held.set()
            time.sleep(HOLD_S)

    def waiter():
        trace.set_role(waiter_role)
        held.wait(5)
        t0 = time.perf_counter()
        with waiter_lock or lock:
            stood.append(time.perf_counter() - t0)

    threads = [threading.Thread(target=f) for f in (holder, waiter)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(5)
    (s,) = stood
    return s


@pytest.mark.parametrize("waiter,holder", [
    (trace.SUBMITTER, trace.UPDATER), (trace.UPDATER, trace.SUBMITTER),
    (trace.EXECUTOR, trace.UPDATER), (trace.EXECUTOR, trace.EXECUTOR),
    (trace.MAIN, trace.EXECUTOR),
])
def test_a_wait_is_booked_to_the_waiters_role_and_the_holders(
        waiter, holder):
    lock = ClockedLock("state")
    stood = _hold_and_wait(lock, holder, waiter)
    assert list(lock.waits_ns) == [(waiter, holder)]
    booked = lock.waits_ns[(waiter, holder)] * 1e-9
    assert HOLD_S - EARLY_S <= booked <= HOLD_S + LATE_S
    # the lock's own clock lies inside the test's
    assert booked <= stood + 1e-3
    assert lock.contended == 1
    assert lock.max_ns == lock.waits_ns[(waiter, holder)]
    assert lock.max_at == (waiter, holder)


def test_the_pairs_sum_to_the_locks_and_to_the_waiters_totals():
    state, key = ClockedLock("state"), ClockedLock("key")
    history = key.alias("history")
    context = ClockedLock("context", threading.RLock())
    _hold_and_wait(state, trace.UPDATER, trace.SUBMITTER)
    _hold_and_wait(state, trace.SUBMITTER, trace.UPDATER)
    _hold_and_wait(key, trace.UPDATER, trace.EXECUTOR)
    _hold_and_wait(key, trace.EXECUTOR, trace.UPDATER, waiter_lock=history)
    _hold_and_wait(context, trace.EXECUTOR, trace.SUBMITTER)
    out = lock_wait_counters((state, key, context, history))
    cells = {k: v for k, v in out.items() if "_behind_" in k}
    assert set(cells) == {
        "lock_wait_state_submitter_behind_updater_s",
        "lock_wait_state_updater_behind_submitter_s",
        "lock_wait_key_executor_behind_updater_s",
        "lock_wait_history_updater_behind_executor_s",
        "lock_wait_context_submitter_behind_executor_s",
    }
    for v in cells.values():
        assert HOLD_S - EARLY_S <= v <= HOLD_S + LATE_S
    for name in trace.LOCK_NAMES:
        mine = [v for k, v in cells.items()
                if k.startswith(f"lock_wait_{name}_")]
        assert out[f"lock_wait_{name}_s"] == pytest.approx(sum(mine))
        assert out[f"lock_contended_{name}"] == len(mine)
    for role in trace.ROLES:
        mine = [v for k, v in cells.items() if f"_{role}_behind_" in k]
        assert out[f"lock_wait_{role}_s"] == pytest.approx(sum(mine))
    assert out["lock_wait_main_s"] == 0.0
    worst = max(cells, key=cells.get)
    lock, waiter, _b, holder = worst[len("lock_wait_"):-2].split("_")
    assert out["lock_wait_max_at"] == f"{lock}:{waiter}:{holder}"
    assert out["lock_wait_max_ms"] == pytest.approx(1e3 * cells[worst])
    # every value a scalar a record keeps
    assert all(isinstance(v, (int, float, str)) for v in out.values())


def test_nothing_waited_reads_zeros_under_every_key():
    out = lock_wait_counters(_locks().values())
    want = {f"lock_wait_{n}_s" for n in trace.LOCK_NAMES + trace.ROLES}
    want |= {f"lock_contended_{n}" for n in trace.LOCK_NAMES}
    want |= {"lock_wait_max_ms", "lock_wait_max_at"}
    assert set(out) == want
    assert out.pop("lock_wait_max_at") == ""
    assert set(out.values()) == {0}
    assert lock_wait_counters(())["lock_wait_state_s"] == 0.0


def test_the_reentrant_lock_keeps_its_holder_through_a_nested_enter():
    lock = ClockedLock("context", threading.RLock())
    _hold_and_wait(lock, trace.UPDATER, trace.EXECUTOR, nested=True)
    assert list(lock.waits_ns) == [(trace.EXECUTOR, trace.UPDATER)]
    # and the context's own nesting goes through it (mark_busy enters
    # get_or_create_state under the lock it holds)
    ctx = AsyncContext(lock=lock)
    trace.set_role(trace.SUBMITTER)
    ctx.mark_busy([0, 1])
    assert ctx.available_workers() == 0
    assert lock.contended == 1
    assert lock.holder() == trace.NOBODY  # it was let go every time


def test_an_alias_is_the_same_lock_under_another_name():
    key = ClockedLock("key")
    history = key.alias("history")
    assert (history.name, key.name) == ("history", "key")
    assert history._inner is key._inner  # ONE inner lock
    with key:
        assert not _from_another_thread(lambda: history._acquire(False))
        assert history.holder() == key.holder() == trace.MAIN
    # a wait at the alias, behind a holder that took the lock by its
    # first name, is the alias's
    _hold_and_wait(key, trace.EXECUTOR, trace.UPDATER, waiter_lock=history)
    assert key.waits_ns == {}
    assert list(history.waits_ns) == [(trace.UPDATER, trace.EXECUTOR)]
    assert history._stage == "lock.history" and key._stage == "lock.key"


@pytest.mark.parametrize("kind", ["plain", "reentrant", "alias", "watched"])
def test_an_exception_inside_the_with_releases(kind):
    lock = _locks()[kind]
    with pytest.raises(KeyError):
        with lock:
            raise KeyError("inside")
    assert _from_another_thread(lambda: lock._acquire(False)) is True


@pytest.mark.parametrize("kind", ["plain", "reentrant", "alias"])
def test_many_threads_lose_no_update_and_no_wait(kind):
    """More threads than cores at a short switch interval: the lock still
    excludes (a lost update would show in the count), every wait is booked
    once, under the lock, and the pairs still sum to the total."""
    import sys

    lock = _locks()[kind]
    calls = CallsIn()
    shared = {"n": 0}
    threads_n, rounds = 24, 400
    deadline = time.monotonic() + 20

    def body(i):
        trace.set_role(trace.ROLES[i % 3])
        for _ in range(rounds):
            with lock, calls:
                n = shared["n"]
                if n % 50 == 0:
                    time.sleep(0)  # hand the interpreter over while held
                shared["n"] = n + 1
            if time.monotonic() > deadline:
                return

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=body, args=(i,))
                   for i in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert shared["n"] == threads_n * rounds
    waits, contended, max_ns, max_at = lock.read()
    assert contended > 0  # they did meet
    assert all(ns > 0 for ns in waits.values())
    assert set(w for w, _h in waits) <= set(trace.ROLES[:3])
    assert max_ns == 0 or max_at in waits
    assert max_ns <= sum(waits.values())
    out = lock_wait_counters([lock])
    assert out[f"lock_contended_{lock.name}"] == contended
    assert out[f"lock_wait_{lock.name}_s"] == pytest.approx(
        sum(waits.values()) * 1e-9)
    with calls as n:
        assert n == 0  # every call took its one off


def test_a_lock_on_its_way_to_a_waiter_is_booked_to_that_waiter():
    """A lock released to a blocked thread names no owner until that thread
    runs: a second waiter that comes in that instant stands behind the
    first, and learns its role from what the first left under the lock."""
    lock = ClockedLock("state")
    # as a waiter that asked while the lock was being handed over would
    # see it: free by the lock's own account
    assert lock.holder() == trace.NOBODY

    def first_waiter_books():
        trace.set_role(trace.UPDATER)
        lock._wait()  # it got the lock by waiting, and says who it is
        type(lock).__exit__(None, None, None)

    _from_another_thread(first_waiter_books)
    assert lock._handed_to == [trace.UPDATER]
    trace.set_role(trace.SUBMITTER)
    lock._wait()  # told "nobody" at the start, it reads the cell at the end
    type(lock).__exit__(None, None, None)
    assert (trace.SUBMITTER, trace.UPDATER) in lock.waits_ns
    assert lock.alias("history")._handed_to is lock._handed_to


def test_start_forgets_and_read_copies():
    lock = ClockedLock("state")
    _hold_and_wait(lock, trace.UPDATER, trace.SUBMITTER)
    waits, contended, max_ns, max_at = lock.read()
    assert contended == 1 and max_ns > 0
    assert max_at == (trace.SUBMITTER, trace.UPDATER)
    waits.clear()  # a copy
    assert lock.waits_ns
    lock.start()
    assert lock.read() == ({}, 0, 0, None)


def test_the_watchdog_sees_an_engine_lock_when_it_is_armed(monkeypatch):
    """The engine's plain locks take their inner lock from
    ``lockwatch.named_lock``: bare while the watchdog is off, watched
    (hold counts, the order graph) while it is on."""
    assert not lockwatch.enabled()
    bare = lockwatch.named_lock("engine.state")
    assert type(bare) is type(threading.Lock())
    monkeypatch.setattr(lockwatch, "_enabled", True)
    before = lockwatch.totals()["holds"]
    lock = ClockedLock("state", lockwatch.named_lock("engine.state"))
    with lock:
        assert lockwatch.held() == ["engine.state"]
    assert lockwatch.held() == []
    assert lockwatch.totals()["holds"] == before + 1


# ----------------------------------------------------------------- the roles
def test_an_executors_thread_says_its_role_once():
    seen = []
    done = threading.Event()
    ex = DeviceExecutor(0, lambda *a: done.set())
    try:
        ex.launch_task(TaskSpec(
            job_id=0, worker_id=0, fn=lambda: seen.append(trace.role())))
        assert done.wait(5)
    finally:
        ex.shutdown()
        ex.join(2)
    assert seen == [trace.EXECUTOR]
    assert trace.role() == trace.MAIN  # a thread-local: not this thread's


# -------------------------------------------------------- the calls in progress
def test_calls_in_hands_back_the_count_it_found():
    calls = CallsIn()
    with calls as a:
        with calls as b:
            with calls as c:
                pass
        with calls as d:
            pass
    with calls as e:
        pass
    assert (a, b, c, d, e) == (0, 1, 2, 1, 0)
    with pytest.raises(KeyError):
        with calls:
            raise KeyError("inside")
    with calls as f:
        assert f == 0  # an exception took its one off


def test_calls_made_at_once_find_each_other():
    calls, gate, found = CallsIn(), threading.Barrier(4), []

    def call():
        with calls as n:
            found.append(n)
            gate.wait(5)  # all four are inside before one leaves

    threads = [threading.Thread(target=call) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(5)
    # (the count is read an instant before it is raised: two that enter
    # in the same instant may find the same number, never a larger one)
    assert len(found) == 4 and min(found) == 0 and max(found) <= 3
    assert sum(found) >= 3
    with calls as n:
        assert n == 0


def test_an_unsampled_step_call_counts_itself_and_reads_no_clock(
        monkeypatch):
    calls = CallsIn()
    inside = []

    def step(a, b):
        with calls as others:
            inside.append(others)
        return a + b

    monkeypatch.setattr(instrumentation, "time", _NoClock())
    assert enqueue_step(step, (2, 3), None, calls) == 5
    assert inside == [1]  # the step's own call was in progress
    with calls as n:
        assert n == 0


def test_a_sampled_step_call_leaves_both_fields_on_its_span():
    calls, spans = CallsIn(), []
    ut = trace.UpdateTrace(trace.TraceContext("t" * 16, 3), spans.append)

    def step():
        t_end = time.perf_counter() + 0.02
        while time.perf_counter() < t_end:  # 20 ms ON the processor
            pass
        time.sleep(0.03)  # and 30 off it
        return "out"

    with calls:  # one other call is in progress
        assert enqueue_step(step, (), ut, calls) == "out"
    (sp,) = spans
    assert sp.stage == trace.TASK_ENQUEUE and sp.calls_in == 1
    assert 15.0 <= sp.cpu_ms <= sp.dur_ms - 20.0
    assert sp.dur_ms >= 49.0
    # a step that raises still closes its span, with both
    with pytest.raises(ZeroDivisionError):
        enqueue_step(lambda: 1 / 0, (), ut, calls)
    assert spans[1].calls_in == 0 and spans[1].cpu_ms >= 0.0
    with calls as n:
        assert n == 0


def test_a_copy_is_a_call_and_an_array_at_home_is_none(devices8):
    import jax
    import jax.numpy as jnp

    class Spy(CallsIn):
        entered = 0

        def __enter__(self):
            Spy.entered += 1
            return super().__enter__()

    calls = Spy()
    a = jax.device_put(jnp.ones(4), devices8[0])
    assert on_device(a, devices8[0], None, calls) is a
    assert Spy.entered == 0
    b = on_device(a, devices8[1], None, calls)
    assert b.device == devices8[1] and Spy.entered == 1
    # a caller with no run's count
    assert on_device(a, devices8[1]).device == devices8[1]
    with UNCOUNTED as n:
        assert n == 0
    spread = engine_loop._spreader(devices8[:3], devices8[1], calls)
    row = spread(b)
    assert [g.device for g in row] == devices8[:3] and row[1] is b
    assert Spy.entered == 2  # the two copies are ONE call


def test_the_two_fields_ride_the_wire_the_bus_and_the_aggregator():
    sp = trace.Span(stage=trace.TASK_ENQUEUE, trace_id="t", span_id="s",
                    parent_id=None, worker_id=1, model_version=2,
                    start_ms=1.0, dur_ms=4.0, calls_in=3, cpu_ms=0.5)
    wire = sp.to_wire()
    assert (wire["ci"], wire["cp"]) == (3, 0.5)
    assert trace.Span.from_wire(wire) == sp
    ev = trace.span_event(sp, 9.0)
    assert (ev.calls_in, ev.cpu_ms) == (3, 0.5)
    # a span without them says nothing of them
    bare = trace.Span(stage=trace.TASK_DISPATCH, trace_id="t", span_id="s",
                      parent_id=None, worker_id=1, model_version=2,
                      start_ms=1.0, dur_ms=4.0)
    assert "ci" not in bare.to_wire() and "cp" not in bare.to_wire()
    agg = trace.TraceAggregator()
    agg.add(bare)
    assert not {"stages_calls_in", "stages_cpu_ms", "enqueue_ms_by_calls_in",
                "enqueue_cpu_ms_by_calls_in"} & set(agg.snapshot())
    for calls_in, dur in ((0, 1.0), (0, 3.0), (1, 2.0), (2, 4.0), (3, 5.0),
                          (5, 7.0), (6, 9.0), (40, 11.0)):
        agg.add(trace.Span(
            stage=trace.TASK_ENQUEUE, trace_id="t", span_id="s",
            parent_id=None, worker_id=0, model_version=0, start_ms=0.0,
            dur_ms=dur, calls_in=calls_in, cpu_ms=dur / 10))
    snap = agg.snapshot()
    assert snap["stages_calls_in"][trace.TASK_ENQUEUE]["count"] == 8
    assert snap["stages_calls_in"][trace.TASK_ENQUEUE]["mean"] == 57 / 8
    assert snap["stages_cpu_ms"][trace.TASK_ENQUEUE]["max"] == 1.1
    assert snap["enqueue_ms_by_calls_in"] == {
        "0": {"count": 2, "p50": 1.0, "mean": 2.0},
        "1": {"count": 1, "p50": 2.0, "mean": 2.0},
        "2": {"count": 1, "p50": 4.0, "mean": 4.0},
        "3-5": {"count": 2, "p50": 5.0, "mean": 6.0},
        "6+": {"count": 2, "p50": 9.0, "mean": 10.0},
    }
    # the thread's CPU time by the same buckets: the mean (a ticking
    # clock's median is 0)
    assert snap["enqueue_cpu_ms_by_calls_in"] == {
        "0": {"count": 2, "mean": pytest.approx(0.2)},
        "1": {"count": 1, "mean": pytest.approx(0.2)},
        "2": {"count": 1, "mean": pytest.approx(0.4)},
        "3-5": {"count": 2, "mean": pytest.approx(0.6)},
        "6+": {"count": 2, "mean": pytest.approx(1.0)},
    }
    for key in ("enqueue_ms_by_calls_in", "enqueue_cpu_ms_by_calls_in"):
        assert list(snap[key]) == list(trace.CALLS_IN_BUCKETS)
    agg.reset()
    assert "stages_calls_in" not in agg.snapshot()


# ------------------------------------------------------------- an engine run
@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(2048, 16)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    w = rng.normal(size=16).astype(np.float32)
    return X, X @ w


def _cfg(**kw):
    base = dict(
        num_workers=4, num_iterations=96, gamma=0.4, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=10, seed=5,
        calibration_iters=8, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


EVERY_RUN = (
    {f"lock_wait_{n}_s" for n in trace.LOCK_NAMES + trace.ROLES}
    | {f"lock_contended_{n}" for n in trace.LOCK_NAMES}
    | {"lock_wait_max_ms", "lock_wait_max_at"}
)


@pytest.mark.parametrize("solver_cls,mode", [
    (ASGD, "run"), (ASAGA, "run"), (ASGD, "run_sync"), (ASAGA, "run_sync"),
])
def test_an_engine_run_carries_every_key_of_the_lock_clock(
        solver_cls, mode, problem, devices8):
    gamma = 0.4 if solver_cls is ASGD else 0.05
    solver = solver_cls(*problem, _cfg(
        gamma=gamma, num_iterations=96 if mode == "run" else 12),
        devices=devices8[:2])
    res = getattr(solver, mode)()
    extras = res.extras
    assert EVERY_RUN <= set(extras)
    numbers = {k: v for k, v in extras.items()
               if k.startswith("lock_") and k != "lock_wait_max_at"}
    assert all(v >= 0 for v in numbers.values()), numbers
    cells = {k: v for k, v in numbers.items() if "_behind_" in k}
    assert all(v > 0 for v in cells.values())  # the non-zero cells only
    for name in trace.LOCK_NAMES:
        assert extras[f"lock_wait_{name}_s"] == pytest.approx(sum(
            v for k, v in cells.items()
            if k.startswith(f"lock_wait_{name}_")))
    for role in trace.ROLES:
        assert extras[f"lock_wait_{role}_s"] == pytest.approx(sum(
            v for k, v in cells.items() if f"_{role}_behind_" in k))
    if solver_cls is ASGD:
        assert extras["lock_contended_history"] == 0
    if mode == "run":
        # a lock wait is busy time: the new counter prices a PART of it
        assert extras["lock_wait_submitter_s"] <= extras["submitter_busy_s"]
        assert extras["lock_wait_updater_s"] <= extras["updater_busy_s"]
    else:
        # one driver thread: nobody is the submitter or the updater
        assert extras["lock_wait_submitter_s"] == 0.0
        assert extras["lock_wait_updater_s"] == 0.0
    at = extras["lock_wait_max_at"]
    assert (at == "") == (extras["lock_wait_max_ms"] == 0.0)
    if at:
        lock, waiter, holder = at.split(":")
        assert lock in trace.LOCK_NAMES
        assert waiter in trace.ROLES and holder in trace.ROLES
    # its count is the solver's
    assert trace.role() == trace.MAIN
    assert solver._calls_in is not UNCOUNTED
    with solver._calls_in as n:
        assert n == 0  # every call took its one off


def test_the_run_builds_its_locks_and_hands_two_of_them_on(
        problem, devices8):
    run = engine_loop.EngineRun(ASAGA(*problem, _cfg(gamma=0.05),
                                      devices=devices8[:2]))
    try:
        names = [lock.name for lock in run.inst.locks]
        assert names == ["state", "key", "context", "history", "pool"]
        assert run.inst.locks[4] is run.sched.pool._lock
        assert run.inst.locks[0] is run.state_lock
        assert run.inst.locks[1] is run.key_lock
        assert run.inst.locks[2] is run.ctx._lock
        assert run.inst.locks[3] is run.history_lock
        assert run.history_lock._inner is run.key_lock._inner
        assert run.calls_in is run.solver._calls_in
        # the run's clock forgets what the warm-up and the set-up waited
        _hold_and_wait(run.state_lock, trace.UPDATER, trace.SUBMITTER)
        assert run.state_lock.contended == 1
        run.inst.on_run_start()
        assert run.state_lock.contended == 0
    finally:
        run.shutdown(True)
        run.inst.close()


@pytest.mark.parametrize("solver_cls", [ASGD, ASAGA])
def test_a_traced_runs_enqueue_spans_carry_the_two_fields(
        solver_cls, problem, tmp_path, devices8):
    log = tmp_path / "run.jsonl"
    gamma = 0.4 if solver_cls is ASGD else 0.05
    trace.reset_aggregator()
    solver_cls(*problem, _cfg(gamma=gamma, trace_sample=1.0,
                              event_log=str(log)),
               devices=devices8[:2]).run()
    spans, _ = trace.load_trace_events(log)
    enqueues = [sp for sp in spans if sp.stage == trace.TASK_ENQUEUE]
    assert len(enqueues) >= 80
    for sp in enqueues:
        assert sp.calls_in is not None and sp.calls_in >= 0
        # (the CPU clock and the wall clock are two clocks: a slack of a
        # tick of either)
        assert 0.0 <= sp.cpu_ms <= sp.dur_ms + 0.5
    # no other stage carries them, and no new span was posted for them
    assert all(sp.calls_in is None and sp.cpu_ms is None
               for sp in spans if sp.stage != trace.TASK_ENQUEUE)
    assert {sp.stage for sp in spans} <= set(trace.STAGES) | {
        trace.TRAJECTORY_EVAL, trace.HISTORY_CHECK}
    snap = trace.aggregator().snapshot()
    assert snap["stages_calls_in"][trace.TASK_ENQUEUE]["count"] == len(
        enqueues)
    assert snap["stages_cpu_ms"][trace.TASK_ENQUEUE]["count"] == len(
        enqueues)
    by = snap["enqueue_ms_by_calls_in"]
    assert set(by) <= set(trace.CALLS_IN_BUCKETS)
    assert sum(b["count"] for b in by.values()) == len(enqueues)
    assert all(0.0 <= b["p50"] and 0.0 <= b["mean"] for b in by.values())
    cpu_by = snap["enqueue_cpu_ms_by_calls_in"]
    assert {b: v["count"] for b, v in cpu_by.items()} == {
        b: v["count"] for b, v in by.items()}


# ------------------------------------------------- on the device trace's clock
def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.extend(ev.name for ev in line.events
                             if ev.name.startswith(trace.ANNOTATION_PREFIX))
    return names


def test_a_serial_threads_wait_is_annotated_and_an_executors_is_not(
        tmp_path):
    import jax

    state, key = ClockedLock("state"), ClockedLock("key")
    context = ClockedLock("context", threading.RLock())
    trace_dir = str(tmp_path / "xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        _hold_and_wait(state, trace.UPDATER, trace.SUBMITTER)
        _hold_and_wait(context, trace.EXECUTOR, trace.UPDATER)
        _hold_and_wait(key, trace.UPDATER, trace.EXECUTOR)
        _hold_and_wait(key, trace.UPDATER, trace.MAIN)
        with state:  # uncontended: nothing
            pass
    finally:
        jax.profiler.stop_trace()
    names = _host_events(trace_dir)
    assert names.count("async.lock.state") == 1
    assert names.count("async.lock.context") == 1
    assert "async.lock.key" not in names
    # the clock is kept either way
    assert key.contended == 2 and state.contended == 1
    # they are holds in the trace module's sense, beside the submitter's two
    assert set(trace.LOCK_STAGES.values()) <= trace.HOLD_STAGES
    assert set(trace.LOCK_STAGES) == set(trace.LOCK_NAMES)


def test_outside_a_profiler_session_a_wait_opens_nothing(monkeypatch):
    opened = []
    monkeypatch.setattr(trace, "_trace_me",
                        lambda *a, **k: opened.append(a))
    lock = ClockedLock("state")
    _hold_and_wait(lock, trace.UPDATER, trace.SUBMITTER)
    assert lock.contended == 1 and opened == []
