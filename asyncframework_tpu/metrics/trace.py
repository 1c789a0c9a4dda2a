"""Distributed tracing for the async update loop.

The ASYNC paper's contribution (arXiv:1907.08526) is *bounded-staleness*
asynchrony; ASAP (arXiv:1612.08608) argues the quantity to tune against is
staleness **in time**, not versions.  Neither is measurable when the DCN
plane (PSClient -> ParameterServer over ``net/frame.py``) is a telemetry
black hole.  This module makes one update's life observable end to end:

- a **trace context** ``(trace_id, span_id, worker_id, model_version)``
  rides every frame as an optional ``tc`` header entry, stamped at the one
  framing choke point (``net/frame.send_msg`` consults the thread-local
  context installed here) -- so PULL/PUSH/PULL_SAGA/PUSH_SAGA, topic, and
  master ops are all covered without per-callsite plumbing;
- **lifecycle spans** decompose an update's wall-clock.  Over the DCN
  plane (``parallel/ps_dcn.py``):

  ========== =======================================================
  stage      measured where
  ========== =======================================================
  pull.wait  PS: time the PULL sat in the partial-barrier wave gate
  pull.rtt   worker: whole PULL round trip (client-observed)
  compute    worker: gradient step dispatch + device->host readback
  push.wait  worker: encode/stamp time between compute and the wire
  push.rtt   worker: whole PUSH round trip (client-observed)
  merge.queue PS: PUSH decode + wait for the model lock
  merge.apply PS: time under the lock (tau filter + apply dispatch)
  ========== =======================================================

  In the in-process engine (``ASGD.run`` / ``ASAGA.run``: a submitter
  thread, one executor thread per worker, an updater thread) there is no
  wire, and every stage is recorded where it happens through ONE call,
  :func:`span`.  *Work* stages are what a host thread was doing; they
  also open a ``jax.profiler.TraceAnnotation("async.<stage>")``, so a
  device trace taken around the run shows them in its ``/host:CPU`` plane
  on the device's own clock and an idle gap of the chip can be put down
  to a phase of the program.  *Wait* stages are a thread blocked on a
  queue or on the device: recorded as spans, never annotated (32
  executors blocked in ``block_until_ready`` would own every gap).  The
  *holds* are the exception: waits of ONE thread with a cause the program
  knows, so they are annotated: the submitter's two, in which the recipe
  held workers back, and a contended wait of the submitter or the updater
  at one of the engine's locks (``lock.*``), so that a gap carries the
  recipe's name, or the lock's.  So is
  ``task.delay``, an injected straggler's sleep: a wait with a cause, on
  the few threads of the late workers, and a gap a sleeper leaves carries
  its name.

  ================ ==== ========= ==================================== =======
  stage            kind thread    from -> to                           parent
  ================ ==== ========= ==================================== =======
  worker.idle      wait ex -> sub a worker's last result -> the        -
                                  ``submit`` that takes it (it ends
                                  where that ``submit`` begins; none
                                  before a worker's first task)
  hold.barrier,    hold submitter an empty poll's 1 ms sleep: workers  (annotation
  hold.backlog                    available but fewer than the bucket  only)
                                  asks for / the updater a fleet of
                                  results behind.  With no worker
                                  available (``wait.workers``) nothing
                                  is annotated; each of the three has
                                  its sum in ``TrainResult.extras``
  lock.state,      hold submitter a CONTENDED enter of one of the     (annotation
  lock.key,             or        engine's clocked locks              only)
  lock.context,         updater   (``instrumentation.ClockedLock``):
  lock.history,                   the non-blocking try failed -> the
  lock.pool                       lock is taken.  An executor's wait
                                  is never annotated; every thread's is
                                  in ``extras`` (``lock_wait_*``)
  submit           work submitter cohort chosen -> ``run_job`` returned -
  compute          -    -         submit -> drained by the updater     submit
  task.inbox       wait sub -> ex ``compute``'s start -> ``fn()`` in   compute
                                  (the rest of the submit, the inbox)
  task.wake        wait sub -> ex this task put into the executor's    task.inbox
                                  inbox -> ``fn()`` in: the thread's
                                  wake-up (``task.inbox`` less it is
                                  the submitter's work before the put)
  task.delay       wait executor  ``fn()`` entered -> the injected     compute
                                  sleep's end (``DelayModel``): only a
                                  task that was given a delay and whose
                                  sleep fires, so never a retry or a
                                  speculative copy; ``delay_class`` is
                                  ``normal`` or ``long_tail``
  task.dispatch    work executor  ``fn()`` entered (or its injected    compute
                                  sleep over) -> step returned
  task.turn        wait executor  the wait for this task's turn at the task.dispatch
                                  chip's queue (``DispatchTurns``: a
                                  cohort's order); a chip without
                                  turns records it all the same, empty
  task.model_copy  work executor  ONE ``device_put`` of w/key that     task.dispatch
                                  really copies to the worker's chip
                                  (none in an ASGD run: its model
                                  lives on every chip, a replica each)
  task.enqueue     work executor  the jitted step's call alone, in ->  task.dispatch
                                  returned (no annotation: PJRT's
                                  ``PjitFunction(step)`` is the same
                                  interval in a device trace);
                                  ``calls_in``: the engine's PJRT calls
                                  in progress when it was made
                                  (``instrumentation.CallsIn``),
                                  ``cpu_ms``: this thread's CPU time
                                  inside it
  task.device_wait wait executor  ``block_until_ready`` in -> out;     compute
                                  over several chips ASGD's task first
                                  sends ``g`` to every chip, in here
  task.device_wait wait executor  the same interval a second time, for compute
  .alone                          a task that was ALONE: when its
                                  enqueue returned no other task's
                                  step was out on that chip
  result.queue     wait ex -> upd ``merge_result`` put -> drained      compute
  merge.queue      work updater   drained -> apply starts (state lock, compute
                                  tau filter)
  merge.apply      work updater   the apply dispatch of a drain (one a compute
                                  chip that holds a replica of the
                                  model); ``batch`` = results in it;
                                  ASAGA: with the history paths of
                                  those results and their cross-chip
                                  copies in front of it
  merge.history    work updater   ASAGA, an accepted result: table     merge.apply
                                  delta + history commit dispatched
                                  (no lock held) and published;
                                  once somebody is late, of a worker
                                  that has committed before:
                                  ``delay_class`` (``healthy``,
                                  ``normal``, ``long_tail``) and
                                  ``history_age``, the accepted updates
                                  since that commit
  snapshot,        work updater   annotation only                      -
  checkpoint
  trajectory.eval  work main      after the run's fence: the objective -
                                  of every snapshot (ONE span a run,
                                  ``batch`` = snapshots, ``calls`` =
                                  stacks evaluated; traced runs)
  history.check    work main      ASAGA, after the evaluation: the     -
                                  table read back and ``alpha_bar``
                                  held to its mean (ONE span a run;
                                  ``extras["history_check_s"]`` is
                                  its seconds in every run)
  ================ ==== ========= ==================================== =======

  ``task.inbox + task.dispatch + task.device_wait + result.queue``, and
  ``task.delay`` between the first two where a straggler sleeps, cover
  ``compute`` from its first instant; what is left (the scheduler's
  status update, the handler, GIL hand-offs) is ``compute``'s self time.
  The key lock and the context's lock lie in it too, but no longer
  unmeasured: what a task's thread stood at them is ``extras``'
  ``lock_wait_executor_s``, over every update (wall less ``cpu_ms`` of a
  ``task.enqueue`` is the time its thread was off the processor: the
  interpreter lock, a lock of PJRT's, a full device queue; none of the
  program's locks lies inside it).  Only the first copy of a task
  to run records the task stages: a retry or a speculative copy finds
  ``task.inbox`` closed and records nothing.  ``task.dispatch`` is
  ``task.turn + task.model_copy`` (one a copy) ``+ task.enqueue`` and its
  own time: the closure's Python between them.  A turn's wait lies inside
  ``task.dispatch``, as it always did, and ``task.turn`` is its name.
  ``task.enqueue`` is work by kind, but the call blocks while the
  device's queue is full (32 workers on one chip): its tail is then a
  wait on the device.  For a task that was alone on its chip,
  ``task.device_wait`` is the runtime's launch, the step and the
  completion's way back to the host with no sibling's step in front:
  ``task.device_wait.alone`` less the step's device time is the device's
  side of what a chip is given late.  "Alone" is read from one count a
  chip of the steps that are out (``instrumentation.StepsOut``: every
  task of every run adds one when its enqueue returns and takes it off
  when its wait does); the updater's applies on the driver's chip are not
  in it.

- workers record completed spans into a bounded **lock-light ring buffer**
  (sampled at ``async.trace.sample``, default 1/64, counter-based so the
  first update per worker is always sampled; rate 0 = off with zero wire
  bytes and zero hot-path work) and **piggyback** them on the next PUSH
  header -- exactly like the elastic plane piggybacks adoption orders on
  PULL replies -- so spans survive worker death;
- the PS folds its own server-side spans plus the piggybacked ones into
  the process-global :class:`TraceAggregator` (live UI ``trace`` section:
  per-stage p50/p95/p99 and staleness in versions AND milliseconds) and,
  when given a bus, posts them as ``TraceSpan`` events -> event log ->
  history server.

``bin/async-trace`` (this module's :func:`main`) replays an event log,
reconstructs per-update critical paths, prints a latency-decomposition
table plus a per-worker straggler report, and exports Chrome
``chrome://tracing`` JSON.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
import uuid
import weakref
from collections import OrderedDict, defaultdict, deque
from dataclasses import dataclass, fields
from typing import Callable, Dict, List, Optional, Sequence

# stage names, in canonical critical-path order
PULL_WAIT = "pull.wait"
PULL_RTT = "pull.rtt"
#: pipelined worker loop only (parallel/ps_dcn.py, async.pipeline.depth
#: >= 1): the update loop's RESIDUAL stall -- time the main loop blocked
#: waiting for its prefetched model or for in-flight push-queue space.
#: In the serial loop this time is pull.rtt + push.rtt on the critical
#: path; pipelining overlaps those with compute, and whatever stall is
#: left shows up here.
PIPELINE = "pipeline"
COMPUTE = "compute"
PUSH_WAIT = "push.wait"
PUSH_RTT = "push.rtt"
MERGE_QUEUE = "merge.queue"
MERGE_APPLY = "merge.apply"
#: ASAGA's engine only: inside ``merge.apply``, the history path of an
#: accepted update (table delta + commit dispatches)
MERGE_HISTORY = "merge.history"

# in-process engine stages (module docstring: the second table)
SUBMIT = "submit"
TASK_INBOX = "task.inbox"
TASK_WAKE = "task.wake"
#: an injected straggler's sleep in front of its dispatch
#: (``engine/straggler.py: DelayModel``)
TASK_DELAY = "task.delay"
TASK_DISPATCH = "task.dispatch"
TASK_TURN = "task.turn"
TASK_MODEL_COPY = "task.model_copy"
TASK_ENQUEUE = "task.enqueue"
TASK_DEVICE_WAIT = "task.device_wait"
#: ``task.device_wait`` again, of a task whose step was alone on its chip
TASK_DEVICE_WAIT_ALONE = "task.device_wait.alone"
RESULT_QUEUE = "result.queue"
SNAPSHOT = "snapshot"
CHECKPOINT = "checkpoint"
#: after a run's clock has stopped: the objective of every snapshot
TRAJECTORY_EVAL = "trajectory.eval"
#: after an ASAGA run's evaluation: the table read back and ``alpha_bar``
#: held to the table's mean (``ASAGA._history_extras``)
HISTORY_CHECK = "history.check"
#: a worker between its result and the submit that takes it again
WORKER_IDLE = "worker.idle"
#: why an empty poll of the submitter sent nothing: the recipe's bucket
#: holds the available workers back / the updater is a fleet of results
#: behind / no worker is available (everything is in flight)
HOLD_BARRIER = "hold.barrier"
HOLD_BACKLOG = "hold.backlog"
WAIT_WORKERS = "wait.workers"
#: a contended wait of the submitter or the updater at one of the engine's
#: clocked locks (``instrumentation.ClockedLock``), by the lock's name
LOCK_NAMES = ("state", "key", "context", "history", "pool")
LOCK_STAGES = {name: "lock." + name for name in LOCK_NAMES}

STAGES = (PULL_WAIT, PULL_RTT, PIPELINE, WORKER_IDLE, SUBMIT, COMPUTE,
          TASK_INBOX, TASK_WAKE, TASK_DELAY,
          TASK_DISPATCH, TASK_TURN, TASK_MODEL_COPY, TASK_ENQUEUE,
          TASK_DEVICE_WAIT, TASK_DEVICE_WAIT_ALONE, RESULT_QUEUE,
          PUSH_WAIT, PUSH_RTT,
          MERGE_QUEUE, MERGE_APPLY, MERGE_HISTORY)
#: engine stages in which a host thread WORKS: :func:`span` annotates
#: these on the profiler's clock.  Everything else is a wait (or spans
#: threads, like ``compute``) and is never annotated.
WORK_STAGES = frozenset((SUBMIT, TASK_DISPATCH, TASK_MODEL_COPY, MERGE_QUEUE,
                         MERGE_APPLY, MERGE_HISTORY, SNAPSHOT, CHECKPOINT,
                         TRAJECTORY_EVAL, HISTORY_CHECK))
#: the waits of ONE thread with a cause the program knows: the submitter's
#: two, and a serial thread's stand at a clocked lock.  Annotated like work
#: (one thread, so they cannot crowd a gap as 32 blocked executors would)
HOLD_STAGES = frozenset((HOLD_BARRIER, HOLD_BACKLOG, *LOCK_STAGES.values()))
#: the four children that must cover ``compute`` (with ``task.delay``,
#: where a task has one)
COMPUTE_CHILDREN = (TASK_INBOX, TASK_DISPATCH, TASK_DEVICE_WAIT,
                    RESULT_QUEUE)
#: the four and the stages inside them: what every task's executor
#: records (a delayed one ``task.delay`` besides)
TASK_STAGES = COMPUTE_CHILDREN + (TASK_WAKE, TASK_TURN, TASK_MODEL_COPY,
                                  TASK_ENQUEUE, TASK_DEVICE_WAIT_ALONE)
#: a span's parent, by stage (engine spans; the DCN plane's have none)
PARENT = {COMPUTE: SUBMIT, MERGE_QUEUE: COMPUTE, MERGE_APPLY: COMPUTE,
          MERGE_HISTORY: MERGE_APPLY, TASK_WAKE: TASK_INBOX,
          TASK_TURN: TASK_DISPATCH, TASK_MODEL_COPY: TASK_DISPATCH,
          TASK_ENQUEUE: TASK_DISPATCH, TASK_DEVICE_WAIT_ALONE: COMPUTE,
          TASK_DELAY: COMPUTE,
          **{st: COMPUTE for st in COMPUTE_CHILDREN}}
#: what a work stage (a hold, an injected delay, a lock's wait) is called in
#: a profiler trace
ANNOTATION_PREFIX = "async."
_ANNOTATION_NAME = {st: ANNOTATION_PREFIX + st
                    for st in WORK_STAGES | HOLD_STAGES | {TASK_DELAY}}
#: stages recorded client-side (worker process) vs server-side (PS)
CLIENT_STAGES = (PULL_RTT, PIPELINE, COMPUTE, PUSH_WAIT, PUSH_RTT)
SERVER_STAGES = (PULL_WAIT, MERGE_QUEUE, MERGE_APPLY)
#: the minimum chain proving a cross-process trace survived the wire
CHAIN_STAGES = (PULL_RTT, COMPUTE, PUSH_RTT)


def now_ms() -> float:
    """Wall-clock epoch milliseconds: the one span time base.  Monotonic
    clocks do not compare across processes, and a trace IS cross-process."""
    return time.time() * 1e3


# One random prefix per process + an atomic counter: minting an id costs a
# counter bump and a format, not a uuid4 entropy syscall.  The hot path
# mints four ids per sampled update, and measured on the CPU test rig even
# single-digit microseconds per merge in the updater thread measurably
# shifts marginal-stability ASGD runs -- id minting must be near-free.
_ID_PREFIX = uuid.uuid4().hex[:8]
_ID_COUNTER = itertools.count(1)


def _new_id(n: int = 16) -> str:
    c = next(_ID_COUNTER)
    if n >= 16:
        return _ID_PREFIX + format(c & 0xFFFFFFFF, "08x")
    return _ID_PREFIX[:2] + format(c & 0xFFFFFF, "06x")


@dataclass
class Span:
    """One completed stage of a traced update (host-side record)."""

    stage: str
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    worker_id: int
    model_version: int
    start_ms: float
    dur_ms: float
    staleness: Optional[int] = None
    staleness_ms: Optional[float] = None
    accepted: Optional[bool] = None
    #: wire bytes of the RPC this span covers (pull.rtt/push.rtt: frame
    #: bytes both directions, counted at the net/frame.py choke point) --
    #: latency AND volume decompose per stage
    bytes: Optional[int] = None
    #: how many updates the piece of work this span times served: the
    #: cohort of a ``submit``, the accepted results of the drain behind a
    #: ``merge.apply`` (its duration is the whole drain's, undivided)
    batch: Optional[int] = None
    #: evaluation calls a ``trajectory.eval`` made: the stacks of
    #: ``snapshots_per_call`` snapshots it built, one at a time
    calls: Optional[int] = None
    #: a ``task.delay``: which of the straggler model's two multiplier
    #: classes the sleeper is of, ``normal`` or ``long_tail``; a
    #: ``merge.history`` under the tail: its worker's class, those two or
    #: ``healthy`` (``DelayModel.worker_class``)
    delay_class: Optional[str] = None
    #: a ``merge.history`` under the tail: the accepted updates since its
    #: worker's previous commit, the age of the slice this one replaces
    history_age: Optional[int] = None
    #: a ``task.enqueue``: the engine's calls into PJRT that were in
    #: progress, on any thread, when this one was made
    #: (``instrumentation.CallsIn``), and the CPU time of the calling thread
    #: inside the call (``time.thread_time_ns``): wall less CPU is what the
    #: thread spent off the processor
    calls_in: Optional[int] = None
    cpu_ms: Optional[float] = None

    # wire format: short keys, Nones omitted -- spans ride PUSH headers
    _WIRE = (("s", "stage"), ("t", "trace_id"), ("i", "span_id"),
             ("p", "parent_id"), ("w", "worker_id"), ("v", "model_version"),
             ("b", "start_ms"), ("d", "dur_ms"), ("st", "staleness"),
             ("sm", "staleness_ms"), ("ac", "accepted"), ("by", "bytes"),
             ("n", "batch"), ("c", "calls"), ("dc", "delay_class"),
             ("ci", "calls_in"), ("cp", "cpu_ms"), ("ha", "history_age"))

    def to_wire(self) -> dict:
        out = {}
        for short, name in self._WIRE:
            v = getattr(self, name)
            if v is not None:
                out[short] = v
        return out

    @classmethod
    def from_wire(cls, d: dict) -> "Span":
        kw = {name: d.get(short) for short, name in cls._WIRE}
        kw["stage"] = str(kw["stage"])
        kw["trace_id"] = str(kw["trace_id"])
        kw["span_id"] = str(kw.get("span_id") or _new_id(8))
        # `x or default` would eat legitimate zeros -- model_version 0 is
        # the PS's FIRST served clock, and the first update is exactly the
        # one counter-based sampling always traces
        for name, default in (("worker_id", 0), ("model_version", -1)):
            v = kw.get(name)
            kw[name] = default if v is None else int(v)
        for name in ("start_ms", "dur_ms"):
            v = kw.get(name)
            kw[name] = 0.0 if v is None else float(v)
        return cls(**kw)


#: the attributes a :class:`Span` has a field for (its optional ones); any
#: other attribute of a :func:`span` call goes on the annotation alone
_SPAN_ATTRS = frozenset(
    f.name for f in fields(Span) if f.default is None
)


class TraceContext:
    """The propagated identity of one traced update: ``trace_id`` pins the
    lifecycle, ``span_id`` is the client span covering the in-flight RPC
    (the server's parent), ``worker_id``/``model_version`` locate it."""

    __slots__ = ("trace_id", "span_id", "worker_id", "model_version")

    def __init__(self, trace_id: str, worker_id: int,
                 model_version: int = -1, span_id: str = ""):
        self.trace_id = trace_id
        self.span_id = span_id or _new_id(8)
        self.worker_id = int(worker_id)
        self.model_version = int(model_version)

    def wire(self) -> list:
        return [self.trace_id, self.span_id, self.worker_id,
                self.model_version]

    @classmethod
    def from_wire(cls, tc: Sequence) -> Optional["TraceContext"]:
        try:
            return cls(str(tc[0]), int(tc[2]), int(tc[3]), str(tc[1]))
        except (IndexError, KeyError, TypeError, ValueError):
            # junk from the wire (wrong type, a dict, short list) must
            # never kill a connection handler -- KeyError included: a JSON
            # object's tc[0] raises it, not IndexError
            return None


# ------------------------------------------------------- ambient propagation
# Thread-local current context: net/frame.py's send_msg stamps every frame
# sent while one is installed.  With nothing installed the cost is one TLS
# getattr + branch, and frames are byte-identical to the pre-trace wire.
_tls = threading.local()


def set_current(ctx: Optional[TraceContext]) -> None:
    _tls.ctx = ctx


def current() -> Optional[TraceContext]:
    return getattr(_tls, "ctx", None)


# A thread's role in the in-process engine: who waits for whom at a lock
# (``instrumentation.ClockedLock`` books a wait to the pair of the waiter's
# role and the holder's).  Said ONCE where a thread starts its part
# (``EngineRun.drive``, the updater's entry, ``DeviceExecutor._run``); a
# thread that never said is ``main`` (the caller's thread outside the
# submitter loop, the monitors).  A thread reads its own from a
# thread-local; a waiter asks for the HOLDER's by the holder's thread id
# (:func:`role_of`), which the lock itself knows.  Neither is a lookup of a
# thread's name, and neither happens where a lock is free.
SUBMITTER, UPDATER, EXECUTOR, MAIN = "submitter", "updater", "executor", "main"
ROLES = (SUBMITTER, UPDATER, EXECUTOR, MAIN)
#: the "holder" of a wait whose lock was free again before it could be asked
NOBODY = "nobody"


class _Role(threading.local):
    name = MAIN


#: the calling thread's role is ``thread_role.name``
thread_role = _Role()
#: thread id -> (the role that thread said last, the thread): an id is
#: handed to a new thread once its thread has ended, so a role counts only
#: while the thread that said it lives
_role_by_ident: Dict[int, tuple] = {}


def set_role(role: str) -> None:
    thread_role.name = role
    _role_by_ident[threading.get_ident()] = (
        role, weakref.ref(threading.current_thread()))


def role() -> str:
    return thread_role.name


def role_of(ident: int) -> str:
    """The role of the live thread with this id: ``main`` where it never
    said one (or the thread that did has ended and left its id to
    another)."""
    said = _role_by_ident.get(ident)
    if said is not None:
        thread = said[1]()
        if thread is not None and thread.is_alive():
            return said[0]
    return MAIN


def wire_header() -> Optional[list]:
    """The ``tc`` header value to stamp, or None (tracing off / untraced
    update).  Called by ``net/frame.send_msg`` on every frame."""
    ctx = getattr(_tls, "ctx", None)
    return None if ctx is None else ctx.wire()


# ------------------------------------------------------------- worker side
class UpdateTrace:
    """One sampled update's in-progress trace: collects its spans and, on
    the DCN worker, hands the ambient context to the RPCs.  In the engine
    it is the handle that rides the task closure, the executor, the handler
    and ``PartialResult`` from the submitter to the updater; every span of
    the update is recorded against it by :func:`span`."""

    __slots__ = ("ctx", "_sink", "spans", "born_ms", "ids", "_open",
                 "_held", "_hold_lock")

    def __init__(self, ctx: TraceContext, sink: Callable[[Span], None]):
        self.ctx = ctx
        self._sink = sink
        self.spans: List[Span] = []
        #: when the sampling decision fell (the engine: at submit)
        self.born_ms = now_ms()
        #: stage -> span id of the newest span begun for it (what a child
        #: stage names as its parent, see PARENT)
        self.ids: Dict[str, str] = {}
        self._open: Dict[str, "_Span"] = {}
        #: spans kept back from the sink (:meth:`hold`); None: none are
        self._held: Optional[List[Span]] = None
        self._hold_lock = threading.Lock()

    # ---- a stage that begins on one thread and ends on another (a wait
    # in a queue, ``compute``): its open span rides this handle
    def begin(self, stage: str, inside: Optional[str] = None) -> None:
        """``inside``: only while that stage is open (a task launched again
        after its first copy ran finds ``task.inbox`` closed and begins no
        ``task.wake``)."""
        if inside is None or inside in self._open:
            self._open[stage] = span(stage, self).begin()

    def end(self, stage: str) -> bool:
        """Close what :meth:`begin` opened.  False when there was nothing
        open: the stage was never begun or another thread closed it first
        (a retried or speculative copy of a task finds its ``task.inbox``
        closed by the first copy to run, and records nothing)."""
        sp = self._open.pop(stage, None)
        if sp is None:
            return False
        sp.end()
        return True

    def hold(self) -> None:
        """Keep the spans recorded from here on (by any thread) back from
        the sink until :meth:`release`.  A span's way to the sink wakes the
        thread behind it, which then wants the interpreter: on a host with
        ten busy threads that is 0.1 ms a span for the thread that recorded
        it (PR 41, four chips: three spans inside ``task.dispatch`` made a
        sampled dispatch 0.35 ms longer).  A task's executor holds its
        update's spans from the closure's entry to the step's enqueue and
        hands them over on the device's time."""
        with self._hold_lock:
            if self._held is None:
                self._held = []

    def release(self) -> None:
        """Hand the sink what :meth:`hold` kept back, in the order it was
        recorded; nothing where nothing is held."""
        with self._hold_lock:
            held, self._held = self._held, None
        for sp in held or ():
            self._sink(sp)

    def set_model_version(self, mv: int) -> None:
        """Learned from the pull reply; back-fills spans recorded before
        the version was known (pull.rtt itself)."""
        self.ctx.model_version = int(mv)
        for sp in self.spans:
            if sp.model_version < 0:
                sp.model_version = int(mv)

    def add(self, stage: str, start_ms: float, end_ms: float,
            span_id: Optional[str] = None, parent_id: Optional[str] = None,
            **attrs) -> Span:
        sp = Span(
            stage=stage, trace_id=self.ctx.trace_id,
            span_id=span_id or _new_id(8),
            parent_id=parent_id, worker_id=self.ctx.worker_id,
            model_version=self.ctx.model_version, start_ms=start_ms,
            dur_ms=max(0.0, end_ms - start_ms), **attrs,
        )
        self.spans.append(sp)
        with self._hold_lock:
            if self._held is not None:
                self._held.append(sp)
                return sp
        self._sink(sp)
        return sp

    def rpc_begin(self, stage: str) -> tuple:
        """Mint the RPC span's id, install it as the wire span_id, install
        the ambient context; returns the token ``rpc_end`` needs."""
        span_id = _new_id(8)
        self.ctx.span_id = span_id
        set_current(self.ctx)
        return (stage, span_id, now_ms())

    def rpc_end(self, token: tuple, **attrs) -> Span:
        """Uninstall the ambient context and record the RPC span."""
        set_current(None)
        stage, span_id, t0 = token
        sp = Span(
            stage=stage, trace_id=self.ctx.trace_id, span_id=span_id,
            parent_id=None, worker_id=self.ctx.worker_id,
            model_version=self.ctx.model_version, start_ms=t0,
            dur_ms=max(0.0, now_ms() - t0), **attrs,
        )
        self.spans.append(sp)
        self._sink(sp)
        return sp


_trace_me = None


def _profiling() -> bool:
    """Whether a ``jax.profiler`` session is open.  JAX is imported on the
    first call (this module is also imported by launchers that never load
    it); from then on the name is bound to the profiler's own static
    check, 20 ns a call."""
    global _trace_me, _profiling
    from jax.profiler import TraceAnnotation

    _trace_me = TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


class _NoSpan:
    """What :func:`span` hands out where there is nothing to do (a wait
    stage of an update that is not sampled): one shared object, no
    allocation, no clock."""

    __slots__ = ()
    start_ms = 0.0

    def begin(self) -> "_NoSpan":
        return self

    def end(self) -> None:
        pass

    def note(self, **attrs) -> None:
        pass

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(stage: str, ut=None, **attrs):
    """THE span call of the in-process engine: one stage of the update
    loop, timed where it happens.

        with span(TASK_DISPATCH, ut):
            g, key = step(X, y, w, key)

    - a *work* stage (``WORK_STAGES``), a hold (``HOLD_STAGES``: the
      submitter's two, and a serial thread's contended wait at a clocked
      lock, ``LOCK_STAGES``) and an injected delay (``task.delay``) open a
      profiler annotation ``async.<stage>`` whenever a ``jax.profiler``
      session is open, so the stage shows on the device trace's clock; any
      other wait stage never does.  With no session
      open (one static call to find out, 20 ns) there is no annotation;
    - with ``ut`` it also records a real :class:`Span` per update: start
      and end read here, ``parent_id`` from ``PARENT``, one ``trace_id``
      per update.  ``ut`` is the update's :class:`UpdateTrace`, or several
      where one piece of work serves several updates (a cohort's submit, a
      drain's apply): an iterable of them, or a mapping from each to the
      attributes that are its own (its staleness in the drain).  With
      ``ut`` None or empty (tracing off, nothing sampled) it records
      nothing and reads no clock.

    With neither to do, which is every call of an untraced run outside a
    profiler session, it hands out one shared no-op and allocates nothing.

    Integer ``attrs`` go on the annotation (they show as the event's
    arguments in XProf/Perfetto) and, where a :class:`Span` has the field
    (``batch``, ``staleness``...), on every span; ``worker=``, ``chip=``
    and (a padded-ELL shard's live width) ``width=`` of a
    ``task.dispatch`` are the annotation's alone.  ``with`` and
    ``begin()`` / ``end()`` are the same pair; a stage that ends on
    another thread than it began on rides the handle
    (:meth:`UpdateTrace.begin`).  What is known only at the stage's end
    (a ``task.enqueue``'s ``cpu_ms``) is told the open span:
    ``sp.note(cpu_ms=...)``, span fields only."""
    name = _ANNOTATION_NAME.get(stage)
    if name is not None and not _profiling():
        name = None  # no profiler session is open: nothing to annotate
    if not ut:
        if name is None:
            return _NO_SPAN
        return _Span(stage, name, None, attrs)
    if isinstance(ut, UpdateTrace):
        ut = {ut: None}
    elif not isinstance(ut, dict):
        ut = dict.fromkeys(ut)
    return _Span(stage, name, ut, attrs)


class _Span:
    __slots__ = ("stage", "start_ms", "_name", "_uts", "_attrs", "_ann")

    def __init__(self, stage: str, name: Optional[str],
                 uts: Optional[dict], attrs: dict):
        self.stage = stage
        self._name = name
        self._uts = uts
        self._attrs = attrs
        self._ann = None
        self.start_ms = 0.0

    def begin(self) -> "_Span":
        if self._name is not None:
            self._ann = _trace_me(self._name, **self._attrs)
            self._ann.__enter__()
        if self._uts:
            for ut in self._uts:
                ut.ids[self.stage] = _new_id(8)
            self.start_ms = now_ms()
        return self

    def note(self, **attrs) -> None:
        """More of the stage's attributes, from inside it: they go on the
        span(s) its end records (the annotation was opened without them)."""
        self._attrs.update(attrs)

    def end(self) -> None:
        if self._uts:
            end_ms = now_ms()
            parent = PARENT.get(self.stage)
            attrs = {k: v for k, v in self._attrs.items()
                     if k in _SPAN_ATTRS}
            for ut, own in self._uts.items():
                ut.add(
                    self.stage, self.start_ms, end_ms,
                    span_id=ut.ids[self.stage],
                    parent_id=ut.ids.get(parent) if parent else None,
                    **attrs, **(own or {}),
                )
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        self.end()


class TraceRecorder:
    """Per-process sampling decision + bounded ring of completed spans.

    ``sample_rate`` / ``capacity`` default from conf (``async.trace.sample``
    / ``async.trace.buffer``).  Sampling is counter-based per worker id --
    deterministic, and the FIRST update of every worker is always sampled
    when the rate is > 0, so even a short run yields a complete trace.
    With rate 0 (or a None recorder) the hot path does no tracing work at
    all and no wire bytes are added.
    """

    def __init__(self, sample_rate: Optional[float] = None,
                 capacity: Optional[int] = None,
                 sink: Optional[Callable[[Span], None]] = None):
        if sample_rate is None or capacity is None:
            from asyncframework_tpu.conf import (
                TRACE_BUFFER,
                TRACE_SAMPLE,
                global_conf,
            )

            conf = global_conf()
            if sample_rate is None:
                sample_rate = float(conf.get(TRACE_SAMPLE))
            if capacity is None:
                capacity = int(conf.get(TRACE_BUFFER))
        rate = max(0.0, min(1.0, float(sample_rate)))
        self.sample_rate = rate
        self.interval = 0 if rate <= 0.0 else max(1, round(1.0 / rate))
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._counts: Dict[int, int] = {}
        self._ring: "deque[Span]" = deque(maxlen=self.capacity)
        self._sink = sink
        self.sampled = 0
        self.dropped_spans = 0
        self._ring_len_hw = 0

    @property
    def enabled(self) -> bool:
        return self.interval > 0

    def start_update(self, worker_id: int) -> Optional[UpdateTrace]:
        """The per-update sampling decision; None = not traced."""
        if self.interval == 0:
            return None
        with self._lock:
            n = self._counts.get(worker_id, 0)
            self._counts[worker_id] = n + 1
            if n % self.interval != 0:
                return None
            self.sampled += 1
        return UpdateTrace(
            TraceContext(_new_id(16), worker_id), self._record
        )

    def start_run(self) -> UpdateTrace:
        """The handle of a span that belongs to a whole run and to no
        update (``trajectory.eval``): never sampled away, worker id -1."""
        return UpdateTrace(TraceContext(_new_id(16), -1), self._record)

    def _record(self, span: Span) -> None:
        if self._sink is not None:
            self._sink(span)
            return
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped_spans += 1
            self._ring.append(span)

    def drain_wire(self, max_spans: int = 128) -> List[dict]:
        """Completed spans awaiting shipment, as wire dicts (the PUSH
        piggyback; also drained by BYE so a run's tail spans land).  A
        caller whose send terminally fails should :meth:`requeue` what it
        drained so the spans ride the next attempt instead of vanishing."""
        out: List[dict] = []
        with self._lock:
            while self._ring and len(out) < max_spans:
                out.append(self._ring.popleft().to_wire())
        return out

    def requeue(self, wire_spans: List[dict]) -> None:
        """Put drained-but-undelivered wire spans back at the FRONT of the
        ring (a push that spent its whole retry budget must not silently
        eat its piggyback -- those spans describe exactly the fault window
        a trace exists to explain).  Overflow evicts from the ring's other
        end, counted in ``dropped_spans``."""
        with self._lock:
            for d in reversed(wire_spans):
                try:
                    sp = Span.from_wire(d)
                except Exception:  # noqa: BLE001 - never raise on telemetry
                    continue
                if len(self._ring) == self._ring.maxlen:
                    self.dropped_spans += 1
                self._ring.appendleft(sp)


# ------------------------------------------------------------ aggregation
#: ``task.enqueue``'s median is read by the calls in progress when it was
#: made: alone, beside one, two, three to five, six and more
CALLS_IN_BUCKETS = ("0", "1", "2", "3-5", "6+")


def calls_in_bucket(calls_in: int) -> str:
    return CALLS_IN_BUCKETS[min(calls_in, 3) if calls_in <= 5 else 4]


class TraceAggregator:
    """Folds spans into per-stage latency histograms + staleness (versions
    AND milliseconds) distributions; the ``trace`` section of the live UI
    is one :meth:`snapshot` of this, and ``benchmark/run.py`` reads the
    process-global one (:func:`aggregator`)."""

    def __init__(self, capacity: int = 4096):
        from asyncframework_tpu.metrics.system import Histogram

        self._lock = threading.Lock()
        self._mk = lambda: Histogram(capacity)
        self._stages: Dict[str, "Histogram"] = {}
        self._stage_bytes: Dict[str, "Histogram"] = {}
        self._stage_calls_in: Dict[str, "Histogram"] = {}
        self._stage_cpu_ms: Dict[str, "Histogram"] = {}
        #: ``task.enqueue``'s durations, and its thread's CPU times, by the
        #: calls that were in progress when it was made (``CALLS_IN_BUCKETS``)
        self._enqueue_by_calls_in: Dict[str, "Histogram"] = {}
        self._enqueue_cpu_by_calls_in: Dict[str, "Histogram"] = {}
        self._staleness_v = self._mk()
        self._staleness_ms = self._mk()
        self.spans_total = 0
        self.traces_seen: "OrderedDict[str, None]" = OrderedDict()

    def _fold(self, table: Dict[str, "Histogram"], key: str,
              value: float) -> None:
        h = table.get(key)
        if h is None:
            h = table[key] = self._mk()
        h.update(value)

    def add(self, span: Span) -> None:
        with self._lock:
            self.spans_total += 1
            self._fold(self._stages, span.stage, span.dur_ms)
            if span.bytes is not None:
                self._fold(self._stage_bytes, span.stage, float(span.bytes))
            if span.cpu_ms is not None:
                self._fold(self._stage_cpu_ms, span.stage, float(span.cpu_ms))
            if span.calls_in is not None:
                self._fold(self._stage_calls_in, span.stage,
                           float(span.calls_in))
                if span.stage == TASK_ENQUEUE:
                    bucket = calls_in_bucket(span.calls_in)
                    self._fold(self._enqueue_by_calls_in, bucket, span.dur_ms)
                    if span.cpu_ms is not None:
                        self._fold(self._enqueue_cpu_by_calls_in, bucket,
                                   float(span.cpu_ms))
            if span.staleness is not None:
                self._staleness_v.update(float(span.staleness))
            if span.staleness_ms is not None:
                self._staleness_ms.update(float(span.staleness_ms))
            self.traces_seen[span.trace_id] = None
            while len(self.traces_seen) > 4096:
                self.traces_seen.popitem(last=False)

    def add_wire(self, spans: Sequence[dict]) -> List[Span]:
        out = []
        for d in spans:
            try:
                sp = Span.from_wire(d)
            except Exception:  # noqa: BLE001 - junk from the wire
                continue
            self.add(sp)
            out.append(sp)
        return out

    def snapshot(self) -> dict:
        with self._lock:
            stages = {
                name: self._stages[name].snapshot()
                for name in STAGES if name in self._stages
            }
            # stages outside the canonical vocabulary still show up
            for name in self._stages:
                if name not in stages:
                    stages[name] = self._stages[name].snapshot()
            out = {
                "spans": self.spans_total,
                "traces": len(self.traces_seen),
                "stages_ms": stages,
                "staleness_versions": self._staleness_v.snapshot(),
                "staleness_ms": self._staleness_ms.snapshot(),
            }
            if self._stage_bytes:
                # wire-volume decomposition beside the latency one: rtt
                # spans carry their RPC's frame bytes (net/frame.py)
                out["stages_bytes"] = {
                    name: h.snapshot()
                    for name, h in self._stage_bytes.items()
                }
            # the calls in progress when a stage's call was made, the
            # calling thread's CPU time inside it, and the relation read
            # off: the call's wall time and its CPU time by calls in
            # progress, with counts.  (The MEAN is the CPU time's
            # statistic: where the host's thread clock ticks, 10 ms on the
            # v5e's, a call reads 0 or a tick, and only the mean over many
            # calls converges on what a call costs.)
            for key, table in (("stages_calls_in", self._stage_calls_in),
                               ("stages_cpu_ms", self._stage_cpu_ms)):
                if table:
                    out[key] = {name: h.snapshot()
                                for name, h in table.items()}
            for key, table, keep in (
                    ("enqueue_ms_by_calls_in", self._enqueue_by_calls_in,
                     ("count", "p50", "mean")),
                    ("enqueue_cpu_ms_by_calls_in",
                     self._enqueue_cpu_by_calls_in, ("count", "mean"))):
                if table:
                    by = ((b, table[b].snapshot())
                          for b in CALLS_IN_BUCKETS if b in table)
                    out[key] = {b: {k: h[k] for k in keep} for b, h in by}
            return out

    def reset(self) -> None:
        with self._lock:
            self._stages.clear()
            self._stage_bytes.clear()
            self._stage_calls_in.clear()
            self._stage_cpu_ms.clear()
            self._enqueue_by_calls_in.clear()
            self._enqueue_cpu_by_calls_in.clear()
            self._staleness_v = self._mk()
            self._staleness_ms = self._mk()
            self.spans_total = 0
            self.traces_seen.clear()


_global_lock = threading.Lock()
_global_agg: Optional[TraceAggregator] = None


def aggregator() -> TraceAggregator:
    """The process-global aggregator (live UI / bench read it; the PS and
    RunInstruments write it)."""
    global _global_agg
    with _global_lock:
        if _global_agg is None:
            _global_agg = TraceAggregator()
        return _global_agg


def reset_aggregator() -> None:
    aggregator().reset()


def span_event(span: Span, time_ms: float) -> "object":
    """A :class:`~asyncframework_tpu.metrics.bus.TraceSpan` bus event for a
    span (posting process supplies its run-relative ``time_ms``)."""
    from asyncframework_tpu.metrics.bus import TraceSpan

    return TraceSpan(
        time_ms=time_ms, stage=span.stage, trace_id=span.trace_id,
        span_id=span.span_id, parent_id=span.parent_id,
        worker_id=span.worker_id, model_version=span.model_version,
        start_ms=span.start_ms, dur_ms=span.dur_ms,
        staleness=span.staleness, staleness_ms=span.staleness_ms,
        accepted=span.accepted, bytes=span.bytes, batch=span.batch,
        calls=span.calls, delay_class=span.delay_class,
        calls_in=span.calls_in, cpu_ms=span.cpu_ms,
        history_age=span.history_age,
    )


# ----------------------------------------------- reconstruction (async-trace)
def _pct(vals: List[float], q: float) -> float:
    """Nearest-rank percentile -- THE rule, shared with the live
    histograms so post-hoc decomposition never disagrees with the UI."""
    from asyncframework_tpu.metrics.system import Histogram

    return Histogram._pct(vals, q)


def _stats(vals: List[float]) -> dict:
    vals = sorted(vals)
    n = len(vals)
    return {
        "count": n,
        "mean": sum(vals) / n,
        "p50": _pct(vals, 0.50),
        "p95": _pct(vals, 0.95),
        "p99": _pct(vals, 0.99),
        "max": vals[-1],
    }


def load_trace_events(event_log_path) -> tuple:
    """Replay an event log; returns (TraceSpan events, truncated_records)."""
    from asyncframework_tpu.metrics.bus import TraceSpan
    from asyncframework_tpu.metrics.eventlog import EventLogReader

    reader = EventLogReader(event_log_path)
    spans = [ev for ev in reader.replay(strict=False)
             if isinstance(ev, TraceSpan)]
    return spans, reader.truncated_records


def build_traces(spans) -> "OrderedDict[str, list]":
    """Group spans by trace_id, each ordered along the canonical critical
    path (stage order, then start time)."""
    order = {s: i for i, s in enumerate(STAGES)}
    by_trace: Dict[str, list] = defaultdict(list)
    for sp in spans:
        by_trace[sp.trace_id].append(sp)
    out: "OrderedDict[str, list]" = OrderedDict()
    for tid in sorted(by_trace,
                      key=lambda t: min(s.start_ms for s in by_trace[t])):
        out[tid] = sorted(
            by_trace[tid],
            key=lambda s: (order.get(s.stage, len(STAGES)), s.start_ms),
        )
    return out


def complete_traces(traces: "OrderedDict[str, list]") -> "OrderedDict[str, list]":
    """Traces whose span chain covers the full client critical path
    (pull.rtt -> compute -> push.rtt), i.e. survived the wire round trip."""
    out: "OrderedDict[str, list]" = OrderedDict()
    for tid, spans in traces.items():
        have = {s.stage for s in spans}
        if all(st in have for st in CHAIN_STAGES):
            out[tid] = spans
    return out


def decomposition(spans) -> dict:
    """Per-stage latency stats + staleness distributions from TraceSpan
    events (the post-hoc analog of TraceAggregator.snapshot)."""
    by_stage: Dict[str, List[float]] = defaultdict(list)
    by_bytes: Dict[str, List[float]] = defaultdict(list)
    stale_v: List[float] = []
    stale_ms: List[float] = []
    for sp in spans:
        by_stage[sp.stage].append(float(sp.dur_ms))
        b = getattr(sp, "bytes", None)
        if b is not None:
            by_bytes[sp.stage].append(float(b))
        if sp.staleness is not None:
            stale_v.append(float(sp.staleness))
        if sp.staleness_ms is not None:
            stale_ms.append(float(sp.staleness_ms))
    out = {
        "stages_ms": {
            st: _stats(by_stage[st])
            for st in STAGES if st in by_stage
        },
        "spans": len(spans),
    }
    for st in by_stage:
        if st not in out["stages_ms"]:
            out["stages_ms"][st] = _stats(by_stage[st])
    if by_bytes:
        # wire-volume decomposition beside the latency one: rtt spans
        # carry their RPC's frame bytes (net/frame.py choke point)
        out["stages_bytes"] = {
            st: _stats(v) for st, v in by_bytes.items()
        }
    if stale_v:
        out["staleness_versions"] = _stats(stale_v)
    if stale_ms:
        out["staleness_ms"] = _stats(stale_ms)
    return out


def straggler_report(spans) -> List[dict]:
    """Per-worker critical-path profile, slowest first: who is dragging the
    run, and in which stage."""
    by_worker: Dict[int, Dict[str, List[float]]] = defaultdict(
        lambda: defaultdict(list)
    )
    for sp in spans:
        by_worker[sp.worker_id][sp.stage].append(float(sp.dur_ms))
    rows = []
    for wid, stages in by_worker.items():
        path_ms = sum(
            sum(v) / len(v) for st, v in stages.items()
            if st in CLIENT_STAGES
        )
        rows.append({
            "worker_id": wid,
            "spans": sum(len(v) for v in stages.values()),
            "critical_path_ms": path_ms,
            "mean_ms": {st: sum(v) / len(v) for st, v in stages.items()},
        })
    rows.sort(key=lambda r: -r["critical_path_ms"])
    if rows:
        med = sorted(r["critical_path_ms"] for r in rows)[len(rows) // 2]
        for r in rows:
            r["vs_median"] = (
                round(r["critical_path_ms"] / med, 2) if med > 0 else None
            )
    return rows


def chrome_trace(spans) -> dict:
    """Chrome ``chrome://tracing`` / Perfetto JSON: one complete ("X")
    event per span; pid = worker id, tid separates the worker's client
    stages from the PS-side stages of its updates."""
    events = []
    for sp in spans:
        client = (sp.stage in CLIENT_STAGES or sp.stage in TASK_STAGES
                  or sp.stage == TASK_DELAY)
        args = {"trace_id": sp.trace_id, "model_version": sp.model_version}
        if sp.parent_id:
            args["parent_id"] = sp.parent_id
            args["span_id"] = sp.span_id
        if sp.staleness is not None:
            args["staleness"] = sp.staleness
        if sp.staleness_ms is not None:
            args["staleness_ms"] = sp.staleness_ms
        if sp.accepted is not None:
            args["accepted"] = sp.accepted
        events.append({
            "name": sp.stage,
            "cat": "worker" if client else "ps",
            "ph": "X",
            "ts": sp.start_ms * 1e3,     # microseconds
            "dur": max(sp.dur_ms, 1e-3) * 1e3,
            "pid": int(sp.worker_id),
            "tid": 0 if client else 1,
            "args": args,
        })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"source": "asyncframework-tpu bin/async-trace"},
    }


def _fmt_table(headers: List[str], rows: List[List[object]]) -> str:
    cells = [headers] + [[str(c) for c in r] for r in rows]
    widths = [max(len(r[i]) for r in cells) for i in range(len(headers))]
    lines = []
    for j, row in enumerate(cells):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    """``bin/async-trace <event_log> [--chrome OUT.json] [--json]``."""
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="async-trace",
        description="Reconstruct per-update traces from an event log: "
        "latency decomposition, straggler report, Chrome tracing export.",
    )
    p.add_argument("event_log", help="JSONL(.gz) event log path")
    p.add_argument("--chrome", default=None, metavar="OUT",
                   help="write Chrome chrome://tracing JSON here")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable summary instead of "
                   "tables")
    args = p.parse_args(argv)

    spans, truncated = load_trace_events(args.event_log)
    traces = build_traces(spans)
    complete = complete_traces(traces)
    deco = decomposition(spans)
    stragglers = straggler_report(spans)
    summary = {
        "spans": len(spans),
        "traces": len(traces),
        "complete_traces": len(complete),
        "truncated_records": truncated,
        "decomposition": deco,
        "stragglers": stragglers,
    }
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(chrome_trace(spans), f)
        summary["chrome"] = args.chrome
    if args.json:
        print(json.dumps(summary, default=float))
        # same exit contract as table mode: a trace-less log (sampling
        # off / no event log attached) is a configuration error scripted
        # callers must be able to gate on
        return 0 if spans else 1
    print(f"event log: {args.event_log}")
    print(f"spans: {len(spans)}  traces: {len(traces)}  "
          f"complete chains: {len(complete)}"
          + (f"  truncated records skipped: {truncated}" if truncated
             else ""))
    if not spans:
        print("no TraceSpan events found (was async.trace.sample > 0 and "
              "an event log attached?)", file=sys.stderr)
        return 1
    print("\nlatency decomposition (ms):")
    rows = []
    for st, s in deco["stages_ms"].items():
        rows.append([st, s["count"], f"{s['p50']:.2f}", f"{s['p95']:.2f}",
                     f"{s['p99']:.2f}", f"{s['max']:.2f}",
                     f"{s['mean']:.2f}"])
    print(_fmt_table(["stage", "count", "p50", "p95", "p99", "max", "mean"],
                     rows))
    for key, label in (("staleness_versions", "staleness (versions)"),
                       ("staleness_ms", "staleness (ms)")):
        if key in deco:
            s = deco[key]
            print(f"\n{label}: p50={s['p50']:.2f} p95={s['p95']:.2f} "
                  f"p99={s['p99']:.2f} max={s['max']:.2f}")
    print("\nper-worker straggler report (slowest first):")
    rows = []
    for r in stragglers:
        m = r["mean_ms"]
        rows.append([
            r["worker_id"], r["spans"], f"{r['critical_path_ms']:.2f}",
            r.get("vs_median"),
            f"{m.get(COMPUTE, 0.0):.2f}", f"{m.get(PULL_RTT, 0.0):.2f}",
            f"{m.get(PUSH_RTT, 0.0):.2f}",
        ])
    print(_fmt_table(
        ["worker", "spans", "critical-path ms", "vs median",
         "compute", "pull.rtt", "push.rtt"], rows,
    ))
    if args.chrome:
        print(f"\nchrome tracing JSON: {args.chrome} "
              "(open via chrome://tracing or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via bin/async-trace
    import sys

    sys.exit(main())
