"""Run instrumentation: the sidecar subsystems wired INTO solver runs.

The reference's observability and resilience live *inside* jobs, not beside
them: every task launch flows through ``LiveListenerBus`` listeners
(``scheduler/LiveListenerBus.scala:44``), ``EventLoggingListener`` streams the
run to disk (``scheduler/EventLoggingListener.scala:55``), ``MetricsSystem``
polls sources on an interval (``metrics/MetricsSystem.scala:70``), and
``HeartbeatReceiver`` (``HeartbeatReceiver.scala:59``) watches executor
liveness for the scheduler.  :class:`RunInstruments` is the per-run bundle of
those capabilities for this framework's solvers: one object the solver
creates from its :class:`~asyncframework_tpu.solvers.base.SolverConfig`,
posts events to from its hot threads, and closes at the end of the run.

Everything here is optional and off the hot path: posting to the bus is a
non-blocking enqueue; when no event log / metrics sink / heartbeat is
configured the instruments are inert no-ops.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from asyncframework_tpu.metrics.bus import (
    Event,
    GradientMerged,
    ListenerBus,
    ModelSnapshot,
    RoundSubmitted,
    ShardMoved,
    SpeculativeLaunch,
    WorkerLost,
)
from asyncframework_tpu.metrics import trace as trace_mod
from asyncframework_tpu.metrics.eventlog import EventLogWriter
from asyncframework_tpu.metrics.system import CsvSink, JsonlSink, MetricsSystem


class _GlobalTraceFold:
    """Bus listener folding TraceSpan events into the process-global
    aggregator (the benchmark and tools read it) -- on the dispatch thread,
    so the solver's hot threads never pay for histogram updates.  The
    event carries every field the aggregator reads: it is folded as it is."""

    def on_trace_span(self, ev) -> None:
        trace_mod.aggregator().add(ev)

    def on_event(self, event) -> None:
        pass


#: JAX's monitoring event around every executable it builds or loads
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0
_compiles_lock = threading.Lock()
_compiles_hooked = False


def _on_jax_duration(name, _seconds, **_kw) -> None:
    global _compiles
    if name == _COMPILE_EVENT:
        with _compiles_lock:
            _compiles += 1


def compiles_so_far() -> int:
    """Executables JAX has built or loaded in this process since the first
    call of this function, which registers the hook (JAX offers no way to
    take one listener back, so there is one per process, never one per
    run).  A run reports the difference across itself."""
    global _compiles_hooked
    with _compiles_lock:
        if not _compiles_hooked:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration
            )
            _compiles_hooked = True
        return _compiles


class BusyClock:
    """One serial thread's time, split into busy and waiting: the thread
    calls :meth:`waits` before it blocks on its queue (or sleeps) and
    :meth:`works` when it is back.  Two ``perf_counter_ns`` reads a turn,
    always on.  *Busy* is everything else the thread's clock saw: its
    Python, but also lock waits, GIL hand-offs and dispatches that block
    while the device's queue is full.  It bounds the thread's own work
    from above, so ``accepted / busy`` bounds the rate at which the
    thread alone saturates from below.  The lock waits' part has a price
    of its own: :class:`ClockedLock` books every contended wait at the
    engine's locks to the waiting thread's role, and
    ``lock_wait_submitter_s`` / ``lock_wait_updater_s`` of a run's
    ``extras`` say how much of each thread's busy time was standing."""

    __slots__ = ("busy_ns", "wait_ns", "_mark")

    def __init__(self):
        self.start()

    def start(self) -> None:
        self.busy_ns = 0
        self.wait_ns = 0
        self._mark = time.perf_counter_ns()

    def waits(self) -> None:
        now = time.perf_counter_ns()
        self.busy_ns += now - self._mark
        self._mark = now

    def works(self) -> int:
        """Back from the wait: the nanoseconds it took, for a caller that
        keeps a sum of its own beside ``wait_ns``."""
        now = time.perf_counter_ns()
        waited = now - self._mark
        self.wait_ns += waited
        self._mark = now
        return waited

    def waited(self, ns: int) -> None:
        """``ns`` of what was counted as busy was spent blocked in a callee
        that keeps its own account (``JobScheduler.blocked_ns``)."""
        self.busy_ns -= ns
        self.wait_ns += ns


#: the two serial threads: a contended wait of one of them at a clocked
#: lock is a hold in ``metrics/trace.py``'s sense, annotated in a profiler
#: session; an executor's never is
_SERIAL_ROLES = frozenset((trace_mod.SUBMITTER, trace_mod.UPDATER))
#: how CPython's ``RLock`` says who owns it: ``<locked _thread.RLock object
#: owner=140... count=1 at 0x...>`` (``owner=0`` while it is free)
_OWNER = re.compile(r"owner=(\d+)")


class ClockedLock:
    """A lock of the engine's hot path with a clock on the WAIT at it:
    who stood there, behind whom, how long.  An ``RLock`` inside (it is
    the lock that remembers its owner), taken by ``with`` alone, as the
    bare lock it replaces was; a lock that was not re-entrant is not
    entered again by this either.

    Where nobody contends it costs one Python call and reads no clock:
    enter is ``acquire(blocking=False)`` and nothing else, exit is the
    inner lock's own ``__exit__``, in C (every lock is an instance of a
    class of its own that holds it: ``with`` looks ``__exit__`` up on the
    type).  Only where the try fails: the holder is asked of the inner
    lock (its thread id, to ``trace.role_of``: the role that thread said
    where it started), then ``perf_counter_ns``, the blocking acquire,
    ``perf_counter_ns``, and the wait is booked to the pair (this thread's
    role, the holder's) WHILE HOLDING the lock just taken, so the sums
    need no lock of their own.  A lock that names nobody when the wait
    begins is on its way to a thread that WAITED for it and has not had
    the interpreter since (a lock released to a blocked thread is that
    thread's at once; the thread writes itself in as owner when it next
    runs): every thread that gets the lock by waiting leaves its role in
    one cell while it books its wait, and the waiter that was told
    "nobody" reads that cell when its own wait ends.  The holder is read
    from the lock and not from a cell that every holder writes: the 11
    enters of the context's lock that ONE poll of the submitter makes
    would each pay the write and a Python ``__exit__`` (a four-chip run
    read 1% slower so, PERF.md section 6, PR 53).  A contended wait of the
    submitter or the updater also opens ``async.lock.<name>`` in a
    profiler session (``trace.LOCK_STAGES``), so that a chip's gap can
    carry the lock's name; an executor's never does.

    It times WAITS, always on, for a run's ``extras``
    (:func:`lock_wait_counters`).  ``net/lockwatch.WatchedLock`` is the
    DCN plane's debug watchdog and times HOLDS (and keeps the order graph,
    the I/O assertion): nothing of it is redone here.  A ``WatchedLock``
    may be the inner lock (``EngineRun`` hands one in while the watchdog
    is armed, so that it sees the engine's locks too); it names no owner,
    and a wait at it stands behind the last thread that waited there."""

    __slots__ = ("name", "waits_ns", "contended", "max_ns", "max_at",
                 "_inner", "_acquire", "_stage", "_handed_to")

    def __new__(cls, name: str, inner=None,
                alias_of: Optional["ClockedLock"] = None):
        if alias_of is not None:
            inner = alias_of._inner
        elif inner is None:
            inner = threading.RLock()
        mine = type(cls.__name__, (cls,), {
            "__slots__": (), "__exit__": inner.__exit__})
        lock = object.__new__(mine)
        lock._inner, lock._acquire = inner, inner.acquire
        #: one cell, shared with every :meth:`alias`: the role of the last
        #: thread that got the lock by WAITING for it (written under it)
        lock._handed_to = (alias_of._handed_to if alias_of is not None
                           else [trace_mod.NOBODY])
        return lock

    def __init__(self, name: str, inner=None,
                 alias_of: Optional["ClockedLock"] = None):
        self.name = name
        self._stage = trace_mod.LOCK_STAGES.get(name)
        self._zero()

    def _zero(self) -> None:
        #: (waiter's role, holder's role) -> nanoseconds waited
        self.waits_ns: Dict[Tuple[str, str], int] = {}
        self.contended = 0
        self.max_ns = 0
        self.max_at: Optional[Tuple[str, str]] = None

    def alias(self, name: str) -> "ClockedLock":
        """The SAME lock under another name: the waits at the sites that
        take it through the alias are booked to ``name`` (ASAGA takes the
        key lock for its history slices: ``history``)."""
        return ClockedLock(name, alias_of=self)

    def __enter__(self) -> "ClockedLock":
        if not self._acquire(False):
            self._wait()
        return self

    def holder(self) -> str:
        """The role of the thread that holds the lock now, as the lock
        itself names its owner; ``nobody`` where it is free (or is no
        ``RLock``)."""
        owner = _OWNER.search(repr(self._inner))
        ident = int(owner[1]) if owner else 0
        return trace_mod.role_of(ident) if ident else trace_mod.NOBODY

    def _wait(self) -> None:
        me = trace_mod.role()
        behind = self.holder()
        hold = (trace_mod.span(self._stage).begin()
                if me in _SERIAL_ROLES and self._stage else None)
        t0 = time.perf_counter_ns()
        self._acquire()
        waited = time.perf_counter_ns() - t0
        if hold is not None:
            hold.end()
        # the lock is held from here: no other thread writes these.  A lock
        # that named nobody when the wait began was on its way to a thread
        # that had waited for it and had not run yet: that thread has said
        # who it is by now (below, under the lock, before it let go)
        if behind is trace_mod.NOBODY:
            behind = self._handed_to[0]
        self._handed_to[0] = me
        pair = (me, behind)
        self.waits_ns[pair] = self.waits_ns.get(pair, 0) + waited
        self.contended += 1
        if waited > self.max_ns:
            self.max_ns, self.max_at = waited, pair

    def start(self) -> None:
        """The run's clock starts: nothing has waited yet."""
        with self:
            self._zero()

    def read(self) -> Tuple[Dict[Tuple[str, str], int], int, int,
                            Optional[Tuple[str, str]]]:
        """``(waits_ns, contended, max_ns, max_at)``, read under the lock
        itself (they are written under it)."""
        with self:
            return (dict(self.waits_ns), self.contended, self.max_ns,
                    self.max_at)


def lock_wait_counters(locks) -> Dict[str, object]:
    """What a run's clocked locks read, as ``extras`` carry it (scalars).
    Every key but the pairs' is there in every run, 0 where nothing
    waited: ``lock_wait_<lock>_s`` (all waiters) and
    ``lock_contended_<lock>`` (waits counted) for each of
    ``trace.LOCK_NAMES``; ``lock_wait_<role>_s`` (all locks) for each of
    ``trace.ROLES``; the non-zero cells of the full table as
    ``lock_wait_<lock>_<waiter>_behind_<holder>_s``; the longest single
    wait ``lock_wait_max_ms`` and where it was, ``lock_wait_max_at``
    (``"<lock>:<waiter>:<holder>"``, empty where nothing waited)."""
    by_lock = dict.fromkeys(trace_mod.LOCK_NAMES, 0)
    counted = dict.fromkeys(trace_mod.LOCK_NAMES, 0)
    by_role = dict.fromkeys(trace_mod.ROLES, 0)
    pairs: Dict[str, float] = {}
    worst_ns, worst_at = 0, ""
    for lock in locks:
        waits_ns, contended, max_ns, max_at = lock.read()
        counted[lock.name] = counted.get(lock.name, 0) + contended
        by_lock.setdefault(lock.name, 0)
        for (waiter, holder), ns in waits_ns.items():
            by_lock[lock.name] += ns
            by_role[waiter] = by_role.get(waiter, 0) + ns
            key = f"lock_wait_{lock.name}_{waiter}_behind_{holder}_s"
            pairs[key] = pairs.get(key, 0.0) + ns * 1e-9
        if max_ns > worst_ns:
            worst_ns, worst_at = max_ns, ":".join((lock.name, *max_at))
    out: Dict[str, object] = {
        f"lock_wait_{name}_s": ns * 1e-9 for name, ns in by_lock.items()}
    out.update(
        (f"lock_wait_{role}_s", ns * 1e-9) for role, ns in by_role.items())
    out.update(pairs)
    out.update((f"lock_contended_{name}", n) for name, n in counted.items())
    out["lock_wait_max_ms"] = worst_ns * 1e-6
    out["lock_wait_max_at"] = worst_at
    return out


class Occupancy:
    """What each chip was given: every task from its submit (ENTER: the
    submitter, after ``mark_busy``) to its result (LEAVE: the completing
    executor's handler, before ``merge_result`` makes the worker available
    again), on one clock under one small lock.  A traced run's account, for
    EVERY update and not the sampled ones; an untraced run has none.

    From the two events: ``inflight_task_s``, the integral of tasks in
    flight; ``chip_empty_s``, per chip the seconds in which none of its
    workers was between submit and result, so that nothing the engine had
    handed out could run there (a chip never entered is empty for the
    whole run); ``worker_idle_s``, per worker the seconds between a LEAVE
    and the next ENTER, which is ``TrainResult.waiting_time_ms``.  A LEAVE
    with no ENTER before it (a speculative copy's second result) and an
    ENTER of a worker still in flight change nothing."""

    def __init__(self, chip_of: Dict[int, int],
                 clock: Callable[[], float] = time.monotonic):
        self._chip_of = dict(chip_of)
        self._clock = clock
        self._lock = threading.Lock()
        self.start()

    def start(self) -> None:
        """The run's clock starts: every chip is empty from here."""
        now = self._clock()
        chips = set(self._chip_of.values())
        self._mark = now            # to where inflight_task_s is summed
        self._closed = False
        self._inflight = 0
        self._on_chip = dict.fromkeys(chips, 0)
        self._empty_since = dict.fromkeys(chips, now)
        self._entered: Dict[int, int] = {}    # worker in flight -> chip
        self._left_at: Dict[int, float] = {}  # worker -> its last result
        self.inflight_task_s = 0.0
        self.chip_empty_s = dict.fromkeys(chips, 0.0)
        self.worker_idle_s: Dict[int, float] = {}

    def _advance(self, now: float) -> None:
        self.inflight_task_s += self._inflight * (now - self._mark)
        self._mark = now

    def enter(self, cohort) -> Dict[int, float]:
        """The cohort's workers are submitted.  Returns, by worker, the
        seconds since its last result (nothing for a worker's first
        task)."""
        idle: Dict[int, float] = {}
        with self._lock:
            if self._closed:
                return idle
            now = self._clock()
            self._advance(now)
            for wid in cohort:
                if wid in self._entered:
                    continue
                chip = self._entered[wid] = self._chip_of[wid]
                self._inflight += 1
                if self._on_chip[chip] == 0:
                    self.chip_empty_s[chip] += now - self._empty_since[chip]
                self._on_chip[chip] += 1
                left = self._left_at.pop(wid, None)
                if left is None:
                    self.worker_idle_s.setdefault(wid, 0.0)
                else:
                    idle[wid] = now - left
                    self.worker_idle_s[wid] += now - left
        return idle

    def leave(self, wid: int) -> None:
        """The worker's result has come."""
        with self._lock:
            chip = None if self._closed else self._entered.pop(wid, None)
            if chip is None:
                return
            now = self._clock()
            self._advance(now)
            self._inflight -= 1
            self._on_chip[chip] -= 1
            if self._on_chip[chip] == 0:
                self._empty_since[chip] = now
            self._left_at[wid] = now

    def close(self) -> Dict[str, object]:
        """The run's clock stops: the sums up to here, as a run's
        ``extras`` carry them (the dict for an operator, the two scalars
        for a record that keeps scalars).  Later events change nothing."""
        with self._lock:
            if not self._closed:
                self._closed = True
                now = self._clock()
                self._advance(now)
                for chip, n in self._on_chip.items():
                    if n == 0:
                        self.chip_empty_s[chip] += now - self._empty_since[chip]
            empty = list(self.chip_empty_s.values())
            return {
                "inflight_task_s": self.inflight_task_s,
                "chip_empty_s": dict(self.chip_empty_s),
                "chip_empty_max_s": max(empty),
                "chip_empty_mean_s": sum(empty) / len(empty),
            }


def on_device(arr, device, ut=None, calls: Optional["CallsIn"] = None):
    """``arr`` on the worker's chip; a copy from another chip is one
    ``task.model_copy`` (inside ``task.dispatch``; ``ut``: the handle of a
    sampled update whose task stages this copy of the task records;
    ``calls``: the run's count of PJRT calls in progress, which a copy is
    one of).  What
    still copies: a model that is one buffer on the driver's chip (ASAGA's,
    a synchronous run's, a version of the ``VersionedModelStore``, a
    test's plain array) and what followed a re-homed shard late (a key, a
    history slice).  ASGD's model lives on every chip and is handed to a
    task as the buffer on its chip (``EngineRun.model_for``)."""
    if arr.device != device:
        with trace_mod.span(trace_mod.TASK_MODEL_COPY, ut), \
                calls or UNCOUNTED:
            arr = jax.device_put(arr, device)
    return arr


class StepsOut:
    """The steps that are OUT on one chip: enqueued, and not yet known to
    the host to be complete.  Every task of every run adds one when its
    step's enqueue returns and takes it off when ``block_until_ready``
    does, so a sampled task can tell whether its step had the chip's queue
    to itself (``task.device_wait.alone``, metrics/trace.py).  One integer
    under a lock of its own: what an update that is not sampled pays.  A
    task whose executor is lost inside its wait never takes its one off:
    from then on no task of that run on that chip reads as alone.
    :class:`CallsIn` is the other count, and differs in both dimensions:
    calls IN PROGRESS on the host, over the whole run (the interpreter
    lock and the PJRT client are the process's), against steps out on ONE
    chip, which a call that has returned still is."""

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def enqueued(self) -> bool:
        """One more step is out; True where it is the only one."""
        with self._lock:
            self._n += 1
            return self._n == 1

    def completed(self) -> None:
        with self._lock:
            self._n -= 1


class CallsIn:
    """The calls the engine has IN PROGRESS into PJRT, on any thread and to
    any chip: one count a RUN, kept for every task of every run.  ``with
    calls as found:`` adds one in front of a call and takes it off behind
    it; ``found`` is the count on entry, the OTHER calls in progress when
    this one was made.  Around the step's call (:func:`enqueue_step`), a
    task's copies (:func:`on_device`; the gradient's spread over the chips,
    ``engine_loop._spreader``) and the updater's dispatches (ASGD's apply a
    chip; ASAGA's commit, table delta, copies and apply).  A sampled task
    puts ``found`` on its ``task.enqueue`` span as ``calls_in``: n calls
    made at once each take n times as long (PERF.md section 5), and the
    aggregator reads ``task.enqueue``'s time by it.  The count is a list's
    length, one entry a call in progress: ``append`` and ``pop`` are atomic
    under the interpreter lock, so no call is ever lost from it and it
    needs no lock of its own; ``found`` is read an instant before the
    append, and two calls that enter in the same instant may find the same
    number (a gauge for a histogram, not a ticket).  :class:`StepsOut` is
    the other count, and says how the two differ.  A synchronous run's
    driver applies between rounds, with no task out: its calls are not
    counted."""

    __slots__ = ("_in",)

    def __init__(self):
        self._in: list = []

    def __enter__(self) -> int:
        calls = self._in
        found = len(calls)
        calls.append(None)
        return found

    def __exit__(self, exc_type, exc, tb) -> None:
        self._in.pop()


#: the count of a caller that has no run's (a test's bare task, a closure
#: built before a solver's first run): kept, read by nobody
UNCOUNTED = CallsIn()


def enqueue_step(step: Callable, operands: tuple,
                 ut: Optional["trace_mod.UpdateTrace"],
                 calls: CallsIn):
    """The jitted worker step's call alone: the stage ``task.enqueue``
    (``ut``: the handle of a sampled update whose task stages this copy of
    the task records, else None).  Every task counts itself among the
    run's calls in progress (``calls``); a sampled one also reads its
    thread's CPU clock in and out and leaves both on the span:
    ``calls_in``, the calls that were in progress when this one was made,
    and ``cpu_ms``, of which the span's duration less it is the time the
    thread was off the processor (the interpreter lock, a lock of PJRT's,
    a full device queue).  No span is posted for either."""
    with trace_mod.span(trace_mod.TASK_ENQUEUE, ut) as sp, calls as found:
        if ut is None:
            return step(*operands)
        cpu0 = time.thread_time_ns()
        try:
            return step(*operands)
        finally:
            sp.note(calls_in=found,
                    cpu_ms=(time.thread_time_ns() - cpu0) * 1e-6)


def worker_task(dispatch: Callable[[Optional["trace_mod.UpdateTrace"]], tuple],
                delay_ms: float = 0.0,
                ut: Optional["trace_mod.UpdateTrace"] = None,
                worker: int = -1, chip: int = -1,
                width: Optional[int] = None,
                turns=None,
                steps_out: Optional[StepsOut] = None,
                spread: Optional[Callable] = None,
                long_tail: bool = False):
    """The closure every worker task is (ASGD and ASAGA, ``run`` and
    ``run_sync``): ``dispatch(mine)`` moves what the step needs to the
    worker's chip and dispatches the step, returning its outputs, gradient
    first (``task.dispatch``; inside it the copies are ``task.model_copy``
    and the step's call ``task.enqueue``, recorded against ``mine``); then
    the executor thread waits for the gradient (``task.device_wait``;
    completion only, the data stays in HBM).  A sampled update's
    ``task.inbox`` was begun by the submitter and ends here, on entry;
    only the first copy of the task to run finds it open and records the
    task stages, so ``mine`` is the update's handle there and None in a
    retry or a speculative copy (the engine itself knows nothing of
    tracing: they run this same closure).  ``task.wake`` begins where the
    executor puts the task into its inbox (``on_launch``, which
    ``DeviceExecutor.launch_task`` calls) and ends here too.

    ``turns``: the chip's ``engine_loop.DispatchTurns``, where its steps are
    enqueued in the cohort's order: the ticket is taken here, where the
    task is built, and the wait for it is ``task.turn`` (recorded empty
    where the chip has no turns, so that ``task.dispatch`` has the same
    children everywhere).  ``steps_out``: the chip's :class:`StepsOut`,
    kept for every task; a sampled one that was alone there records its
    wait a second time as ``task.device_wait.alone``.

    ``spread``: where the run's model lives on several chips
    (``engine_loop.EngineRun.replicate_model``), what sends the step's
    gradient to every one of them; it is called once the step is enqueued,
    in front of the wait for it (inside ``task.device_wait``: the copies'
    host time is time this thread would wait anyway), and the task's
    result then carries the gradient's buffers by chip in place of ``g``.

    ``worker`` and ``chip`` (the device's id) go on ``task.dispatch``'s
    annotation: a reader of a device trace can tell a dispatch TO the chip
    whose gap it is naming from one to another chip.  So does ``width``,
    a padded-ELL shard's live width: shards of unequal width make steps of
    unequal length, and the annotation tells a wide shard's from a narrow
    one's.

    The injected delay models a slow *machine*: only the first body to run
    it sleeps -- a speculative copy or a replacement executor is a
    different (healthy) host path and must bypass the straggler.  The
    sleep is the stage ``task.delay`` (a child of ``compute``, from the
    closure's entry to the sleep's end; ``async.task.delay`` in a profiler
    session), recorded where ``delay_ms > 0`` and the sleep fires and
    nowhere else; ``long_tail``: the class of the sleeper's multipliers
    (``DelayModel.long_tail``), which the span carries as ``delay_class``.
    A task with no delay pays the one comparison."""
    delay_fired = threading.Event()
    where = {"worker": worker, "chip": chip}
    if width is not None:
        where["width"] = width
    ticket = None if turns is None else turns.ticket()

    def fn():
        mine = None
        if ut is not None:
            # what is recorded in front of the enqueue goes to the sink
            # behind it, on the device's time (``UpdateTrace.hold``)
            ut.hold()
            ut.end(trace_mod.TASK_WAKE)
            if ut.end(trace_mod.TASK_INBOX):
                mine = ut
        try:
            if delay_ms > 0 and not delay_fired.is_set():
                delay_fired.set()
                with trace_mod.span(
                        trace_mod.TASK_DELAY, mine, worker=worker,
                        delay_class="long_tail" if long_tail else "normal"):
                    time.sleep(delay_ms / 1e3)
            with trace_mod.span(trace_mod.TASK_DISPATCH, mine, **where):
                with trace_mod.span(trace_mod.TASK_TURN, mine):
                    if ticket is not None:
                        turns.wait(ticket)
                try:
                    out = dispatch(mine)
                finally:
                    if ticket is not None:
                        turns.served(ticket)
                alone = steps_out is not None and steps_out.enqueued()
        finally:
            if ut is not None:
                ut.release()
        try:
            with trace_mod.span(trace_mod.TASK_DEVICE_WAIT, mine), \
                    trace_mod.span(trace_mod.TASK_DEVICE_WAIT_ALONE,
                                   mine if alone else None):
                if spread is not None:
                    everywhere = spread(out[0])
                out[0].block_until_ready()
        finally:
            if steps_out is not None:
                steps_out.completed()
        return out if spread is None else (everywhere, *out[1:])

    if ut is not None:
        def on_launch():
            ut.begin(trace_mod.TASK_WAKE, inside=trace_mod.TASK_INBOX)

        fn.on_launch = on_launch
    return fn


class RunInstruments:
    """Per-run observability bundle: listener bus + event log + metrics.

    The solver calls the ``on_*`` hooks from its submitter/updater threads;
    they update metrics instruments synchronously (cheap: a lock and an
    append) and post typed events to the asynchronous bus (never blocks).
    """

    def __init__(self, cfg, num_workers: int,
                 chip_of: Optional[Callable[[int], int]] = None):
        """``chip_of(worker_id)``: the chip a worker's shard lives on, for
        a traced run's :class:`Occupancy`."""
        self.cfg = cfg
        self._t0 = time.monotonic()
        self.bus = ListenerBus()
        self.writer: Optional[EventLogWriter] = None
        self.metrics: Optional[MetricsSystem] = None
        self.workers_lost = 0
        self.shards_moved = 0
        self._lock = threading.Lock()
        # the engine's always-on counters (extras(), TrainResult): plain
        # sums and integers, each written by ONE thread.  The updater and
        # the submitter are the two serial resources every update passes.
        self.updater_clock = BusyClock()
        self.submitter_clock = BusyClock()
        #: the run's clocked locks (``EngineRun`` makes them and says so
        #: here): their waits start with the run's clock and reach
        #: ``extras`` through :meth:`engine_counters`
        self.locks: List[ClockedLock] = []
        #: the part of the updater's busy time spent inside its apply
        #: dispatches, which block while the device's queue is full
        self.updater_apply_ns = 0
        #: device dispatches the updater made to apply what it accepted
        #: (ASGD's engine run counts them, one a drain where it folds; 0
        #: from a run that does not count)
        self.apply_dispatches = 0
        #: tasks whose step took the model from a buffer already on its
        #: chip, and tasks that paid a ``device_put`` for it: counted where
        #: a cohort's tasks are built, on that one thread
        #: (``EngineRun.model_for``)
        self.model_reads_local = 0
        self.model_reads_copied = 0
        self.submit_empty_polls = 0   # submitter turns that found no cohort
        #: the sleeps of those turns, by what the turn saw (the two holds
        #: and ``wait.workers`` of metrics/trace.py): they sum to the
        #: submitter's polling wait
        self.submit_wait_ns = {trace_mod.HOLD_BACKLOG: 0,
                               trace_mod.HOLD_BARRIER: 0,
                               trace_mod.WAIT_WORKERS: 0}
        self.drains = 0               # updater wakes that merged something
        self.drain_items_max = 0
        #: staleness -> count over EVERY merged result (not a sample)
        self.staleness_hist: Dict[int, int] = {}
        #: accepted results by worker (the updater's, like the histogram):
        #: workers whose steps differ in length are accepted unequally often
        self.accepted_by_worker: Dict[int, int] = {}
        #: accepted updates behind every trajectory entry
        self.snapshot_updates: List[int] = [0]
        self.monitor = None           # the run's HeartbeatMonitor, if any
        self._compiles0 = compiles_so_far()

        event_log = getattr(cfg, "event_log", None)
        if event_log:
            self.writer = EventLogWriter(event_log)
            self.bus.add_listener(self.writer)
            self.bus.start()

        self.ui = None
        self.live_state = None
        ui_port = getattr(cfg, "ui_port", None)
        # None or negative = off (the conf registry's -1 sentinel); 0 = bind
        # an ephemeral port
        if ui_port is not None and ui_port >= 0:
            from asyncframework_tpu.metrics.live import (
                LiveStateListener,
                LiveUIServer,
            )

            self.live_state = LiveStateListener(num_workers)
            self.bus.add_listener(self.live_state)
            self.bus.start()
            self.ui = LiveUIServer(self.live_state, port=ui_port).start()

        # distributed tracing: the single-process solvers' slice of the
        # lifecycle vocabulary (compute / merge.queue / merge.apply --
        # there is no wire here, so the pull/push stages are the DCN
        # path's).  Sampled spans go to the bus as TraceSpan events (->
        # event log / live UI) and a bus listener folds them into the
        # process-global aggregator (benchmark/run.py reads it).
        # EXPLICIT opt-in only (cfg.trace_sample / --trace-sample /
        # --conf async.trace.sample): the conf default governs the DCN
        # plane, where stages are network-dominated -- here the updater
        # thread IS the measured hot path, and even microsecond-scale
        # per-merge work (or the bus dispatch thread's GIL share)
        # measurably shifts marginal-stability engine runs.  None or 0 =
        # no tracer, zero per-merge work.
        self.tracer: Optional[trace_mod.TraceRecorder] = None
        _rate = getattr(cfg, "trace_sample", None)
        if _rate is not None and float(_rate) > 0:
            _rec = trace_mod.TraceRecorder(
                sample_rate=float(_rate), sink=self._fold_span,
            )
            if _rec.enabled:
                self.tracer = _rec
                # start the bus so the updater pays only a queue put;
                # span fan-out runs on the dispatch thread
                self.bus.add_listener(_GlobalTraceFold())
                self.bus.start()
        #: what each chip was given, in a traced run; None in any other,
        #: which then pays one ``is None`` test a submit and a result
        self.occupancy: Optional[Occupancy] = None
        if self.tracer is not None and chip_of is not None:
            self.occupancy = Occupancy(
                {wid: chip_of(wid) for wid in range(num_workers)}
            )

        metrics_csv = getattr(cfg, "metrics_csv", None)
        metrics_jsonl = getattr(cfg, "metrics_jsonl", None)
        if metrics_csv or metrics_jsonl:
            self.metrics = MetricsSystem()
            if metrics_csv:
                self.metrics.add_sink(CsvSink(metrics_csv))
            if metrics_jsonl:
                self.metrics.add_sink(JsonlSink(metrics_jsonl))
            self._c_accepted = self.metrics.counter("updates.accepted")
            self._c_dropped = self.metrics.counter("updates.dropped")
            self._c_rounds = self.metrics.counter("rounds.submitted")
            self._h_staleness = self.metrics.histogram("staleness")
            self._h_task_ms = self.metrics.histogram("task.ms")
            self._g_updates_per_sec = self.metrics.gauge("updates.per_sec")
            self.metrics.start(getattr(cfg, "metrics_period_s", 1.0))

    # ----------------------------------------------------------------- time
    def now_ms(self) -> float:
        return (time.monotonic() - self._t0) * 1e3

    # ------------------------------------------------------------ run hooks
    def register_queue_depth(self, fn: Callable[[], int]) -> None:
        """Expose the result queue's depth as a polled metrics source."""
        if self.metrics is not None:
            self.metrics.register_source("queue", lambda: {"depth": fn()})
        if self.live_state is not None:
            self.live_state.register_queue_depth(fn)

    def on_round_submitted(
        self, round_idx: int, cohort, model_version: int
    ) -> None:
        if self.bus.heard:  # an event nobody hears is not built
            self.bus.post(
                RoundSubmitted(self.now_ms(), round_idx, tuple(cohort),
                               model_version)
            )
        if self.metrics is not None:
            self._c_rounds.inc()

    def _fold_span(self, span: "trace_mod.Span") -> None:
        # hot-thread cost: one non-blocking queue put (the bus is started
        # whenever the tracer is on); aggregation happens on the dispatch
        # thread via _GlobalTraceFold / LiveStateListener
        self.bus.post(trace_mod.span_event(span, self.now_ms()))

    def start_updates(self, cohort) -> Dict[int, "trace_mod.UpdateTrace"]:
        """The sampling decision, at submit: the handles of the cohort's
        sampled updates by worker id.  Empty when tracing is off."""
        if self.tracer is None:
            return {}
        out = {}
        for wid in cohort:
            ut = self.tracer.start_update(wid)
            if ut is not None:
                out[wid] = ut
        return out

    def run_trace(self) -> Optional["trace_mod.UpdateTrace"]:
        """The handle of a span that belongs to the run and to no update
        (``trajectory.eval``): None when tracing is off."""
        return None if self.tracer is None else self.tracer.start_run()

    @staticmethod
    def begin_compute(uts, model_version: int) -> None:
        """The cohort's sampled updates leave the submitter: ``compute``
        starts and, in the same instant, its first child ``task.inbox``
        (ended by the task closure on entry, :func:`worker_task`)."""
        for ut in uts.values():
            ut.ctx.model_version = model_version
            ut.begin(trace_mod.COMPUTE)
            ut.begin(trace_mod.TASK_INBOX)

    def on_busy(self, cohort, uts, submit_start_ms: float) -> None:
        """The cohort is marked busy (a traced run: ``occupancy`` is
        there): its workers enter their chips, and a sampled update whose
        worker has had a result before gets its ``worker.idle``, the
        account's own interval laid to end where the ``submit`` began."""
        idle = self.occupancy.enter(cohort)
        for wid, ut in uts.items():
            if wid in idle:
                ut.add(trace_mod.WORKER_IDLE,
                       submit_start_ms - idle[wid] * 1e3, submit_start_ms)

    def on_run_start(self) -> None:
        """The run's clock starts (after the solver's warm-up): so do the
        two threads' clocks, the locks' waits, the occupancy account and
        the count of compilations."""
        self.updater_clock.start()
        self.submitter_clock.start()
        for lock in self.locks:
            lock.start()
        if self.occupancy is not None:
            self.occupancy.start()
        self._compiles0 = compiles_so_far()

    def on_drained(self, results) -> tuple:
        """A drain reached the updater: count it and, in a traced run,
        close ``result.queue`` and ``compute`` of its sampled updates and
        return their handles.  Untraced: two counters and ``()``."""
        self.drains += 1
        if len(results) > self.drain_items_max:
            self.drain_items_max = len(results)
        if self.tracer is None:
            return ()
        uts = tuple(r.trace for r in results if r.trace is not None)
        for ut in uts:
            ut.end(trace_mod.RESULT_QUEUE)
            ut.end(trace_mod.COMPUTE)
        return uts

    @staticmethod
    def apply_attrs(merged) -> Dict["trace_mod.UpdateTrace", dict]:
        """What the ``merge.apply`` span of a drain carries for each of its
        sampled updates, from ``(result, accepted)`` pairs past the tau
        filter.  Staleness in TIME is how old the worker's model basis is
        at merge: since its submit."""
        now = trace_mod.now_ms()
        return {
            res.trace: {
                "staleness": int(res.staleness),
                "staleness_ms": now - res.trace.born_ms,
                "accepted": bool(accepted),
            }
            for res, accepted in merged if res.trace is not None
        }

    def on_gradient_merged(self, res, accepted: bool, iteration: int,
                           task_ms: float = 0.0) -> None:
        """One result (a ``PartialResult``) passed the tau filter, either
        way.  Called by the updater after it has let go of its state lock;
        no part of tracing (a sampled update's spans are recorded where
        its stages happen).  ``task_ms`` (submit to drain) feeds the
        metrics sink's ``task.ms`` column only."""
        staleness = res.staleness
        self.staleness_hist[staleness] = (
            self.staleness_hist.get(staleness, 0) + 1
        )
        if accepted:
            self.accepted_by_worker[res.worker_id] = (
                self.accepted_by_worker.get(res.worker_id, 0) + 1
            )
        if self.bus.heard:  # an event nobody hears is not built
            self.bus.post(
                GradientMerged(
                    self.now_ms(), res.worker_id, staleness, accepted,
                    iteration, res.batch_size,
                )
            )
        if self.metrics is not None:
            (self._c_accepted if accepted else self._c_dropped).inc()
            self._h_staleness.update(float(staleness))
            if task_ms:
                self._h_task_ms.update(task_ms)
            el = time.monotonic() - self._t0
            if el > 0:
                self._g_updates_per_sec.set(self._c_accepted.value / el)

    def on_snapshot(self, accepted: int) -> None:
        self.snapshot_updates.append(int(accepted))

    def on_model_read(self, local: bool) -> None:
        if local:
            self.model_reads_local += 1
        else:
            self.model_reads_copied += 1

    def on_worker_lost(self, worker_id: int, reason: str) -> None:
        with self._lock:
            self.workers_lost += 1
        self.bus.post(WorkerLost(self.now_ms(), worker_id, reason))

    def on_shard_moved(self, shard_id: int, new_owner: int, device) -> None:
        with self._lock:
            self.shards_moved += 1
        self.bus.post(
            ShardMoved(self.now_ms(), shard_id, new_owner, str(device))
        )

    def on_speculative_launch(self, job_id: int, worker_id: int) -> None:
        self.bus.post(SpeculativeLaunch(self.now_ms(), job_id, worker_id))

    def post(self, event: Event) -> None:
        self.bus.post(event)

    # ----------------------------------------------------------------- close
    def close(
        self, trajectory: Optional[List[Tuple[float, float]]] = None,
        printer_freq: int = 1,
    ) -> None:
        """Flush trajectory snapshots (objectives are evaluated post-hoc, so
        ``ModelSnapshot`` events are emitted at close) and stop everything.

        Idempotent: the solvers' ``finally`` blocks close WITHOUT a
        trajectory when an exception is unwinding (the event log must get
        its gzip footer exactly when the run crashed); the success path then
        skips its second close.
        """
        with self._lock:
            if getattr(self, "_closed", False):
                return
            self._closed = True
        if trajectory:
            for i, (t_ms, obj) in enumerate(trajectory):
                self.bus.post(
                    ModelSnapshot(t_ms, iteration=i * printer_freq,
                                  objective=float(obj))
                )
        if self.metrics is not None:
            self.metrics.report()  # final sample so short runs get >= 1 row
            self.metrics.stop()
        self.bus.stop()
        if self.ui is not None:
            self.ui.stop()
        if self.writer is not None:
            self.writer.close()

    # ---------------------------------------------------------------- extras
    def extras(self) -> Dict[str, object]:
        """Summary facts for ``TrainResult.extras``."""
        out: Dict[str, object] = {}
        with self._lock:
            if self.workers_lost:
                out["workers_lost"] = self.workers_lost
            if self.shards_moved:
                out["shards_moved"] = self.shards_moved
        if self.bus.dropped_events:
            out["dropped_events"] = self.bus.dropped_events
        if self.ui is not None:
            out["ui_port"] = self.ui.port
        return out

    def engine_counters(self, task_retries: int,
                        one_thread: bool = False) -> Dict[str, object]:
        """The always-on counters, as scalars (every run's info line
        carries them, traced or not).  Call after the fence:
        ``compiles_in_run`` counts from :meth:`on_run_start` to here.
        ``one_thread``: the synchronous drivers submit and drain on one
        thread, whose clock is reported as the updater's."""
        out: Dict[str, object] = {
            "updater_busy_s": self.updater_clock.busy_ns * 1e-9,
            "updater_wait_s": self.updater_clock.wait_ns * 1e-9,
            "updater_apply_s": self.updater_apply_ns * 1e-9,
        }
        if not one_thread:
            out.update({
                "submitter_busy_s": self.submitter_clock.busy_ns * 1e-9,
                "submitter_wait_s": self.submitter_clock.wait_ns * 1e-9,
                "submit_empty_polls": self.submit_empty_polls,
                "submit_hold_backlog_s":
                    self.submit_wait_ns[trace_mod.HOLD_BACKLOG] * 1e-9,
                "submit_hold_barrier_s":
                    self.submit_wait_ns[trace_mod.HOLD_BARRIER] * 1e-9,
                "submit_wait_workers_s":
                    self.submit_wait_ns[trace_mod.WAIT_WORKERS] * 1e-9,
            })
        out.update({
            "drains": self.drains,
            "drain_items_max": self.drain_items_max,
            "apply_dispatches": self.apply_dispatches,
            "model_reads_local": self.model_reads_local,
            "model_reads_copied": self.model_reads_copied,
            "task_retries": int(task_retries),
            "compiles_in_run": compiles_so_far() - self._compiles0,
            # who stood at the engine's locks, behind whom, how long
            **lock_wait_counters(self.locks),
        })
        if self.monitor is not None:
            out["host_stall_max_ms"] = self.monitor.stall_max_ms
            out["host_stalls"] = self.monitor.stalls
        return out


def log_trajectory(path, trajectory, printer_freq: int = 1) -> None:
    """Write a bare trajectory as ModelSnapshot events (for runs that have no
    per-task event stream, e.g. the fused-scan baseline); numbering matches
    :meth:`RunInstruments.close` so report tooling sees one convention."""
    from asyncframework_tpu.metrics.bus import ModelSnapshot
    from asyncframework_tpu.metrics.eventlog import EventLogWriter

    wr = EventLogWriter(path)
    try:
        for i, (t_ms, obj) in enumerate(trajectory):
            wr.on_event(
                ModelSnapshot(t_ms, iteration=i * printer_freq,
                              objective=float(obj))
            )
    finally:
        wr.close()


class FaultTolerantRun:
    """Heartbeat + executor replacement + shard re-homing for one run.

    Wires :class:`~asyncframework_tpu.engine.heartbeat.HeartbeatMonitor` to
    the scheduler's ``on_executor_lost`` (in-flight task resubmission on a
    fresh executor -- the transient-failure path) and, when the same worker
    slot keeps dying (``max_slot_failures``), re-homes its data shard onto a
    surviving worker's device via
    :class:`~asyncframework_tpu.engine.recovery.ShardRecovery` (the
    permanent-loss path; lineage-recomputation analog, SURVEY.md section 5).
    """

    def __init__(
        self,
        scheduler,
        recovery,
        instruments: RunInstruments,
        num_workers: int,
        heartbeat_timeout_ms: float = 2000.0,
        check_interval_s: float = 0.25,
        max_slot_failures: int = 2,
        on_moved=None,
    ):
        from asyncframework_tpu.engine.heartbeat import HeartbeatMonitor

        self._sched = scheduler
        self._recovery = recovery
        self._inst = instruments
        self._nw = num_workers
        self._max_slot_failures = max_slot_failures
        self._on_moved = on_moved  # callback(shard_id, moved_shard)
        self._losses: Dict[int, int] = {}
        self._lock = threading.Lock()
        self.monitor = HeartbeatMonitor(
            scheduler.pool,
            self._on_lost,
            timeout_ms=heartbeat_timeout_ms,
            check_interval_s=check_interval_s,
            on_sibling_lost=scheduler.on_sibling_lost,
            # only a traced run asks for the stacks of the next hold
            dump_on_stall=instruments.tracer is not None,
        )
        instruments.monitor = self.monitor

    def _on_lost(self, worker_id: int) -> None:
        with self._lock:
            n = self._losses.get(worker_id, 0) + 1
            self._losses[worker_id] = n
        self._inst.on_worker_lost(worker_id, f"heartbeat timeout (loss #{n})")
        # replacement executor + in-flight resubmission (DAGScheduler parity)
        self._sched.on_executor_lost(worker_id)
        if n >= self._max_slot_failures and self._recovery is not None:
            # repeated deaths: treat the slot's device home as suspect and
            # re-home the shard to the least-loaded surviving slot
            from asyncframework_tpu.engine.recovery import plan_reassignment

            survivors = [w for w in range(self._nw) if w != worker_id]
            if survivors:
                plan = plan_reassignment(range(self._nw), [worker_id])
                new_owner = plan.moves[worker_id]
                moved = self._recovery.move_shard(worker_id, new_owner)
                self._inst.on_shard_moved(
                    worker_id, new_owner, moved.device
                )
                if self._on_moved is not None:
                    self._on_moved(worker_id, moved)

    def start(self) -> None:
        self.monitor.start()

    def stop(self) -> None:
        self.monitor.stop()
