"""Executor liveness monitoring and failure detection.

Parity: ``core/.../HeartbeatReceiver.scala:59`` (driver-side liveness via
periodic executor heartbeats; silent executors are declared dead and their
tasks resubmitted) + standalone Master/Worker heartbeats.  Executors here
touch ``last_heartbeat_ms`` whenever their loop wakes; the monitor thread
compares against a timeout and notifies the scheduler (``on_executor_lost``),
which replaces the executor and resubmits in-flight tasks.
"""

from __future__ import annotations

import faulthandler
import threading
from typing import Callable, Optional

from asyncframework_tpu.engine.executor import ExecutorPool
from asyncframework_tpu.utils.clock import Clock, SystemClock


#: a scan that wakes this much later than its interval is a host stall:
#: something held the monitor thread, and most likely every Python thread
STALL_MS = 100.0
#: with ``dump_on_stall``, how long the interpreter may stand still before
#: every thread's stack is dumped to stderr
STALL_DUMP_S = 1.0
#: ``faulthandler``'s watchdog is ONE per process: at most one monitor of
#: this process arms it (the first traced run to start); a second traced
#: run beside it leaves it alone.  A watchdog armed by anyone else
#: (pytest's ``faulthandler_timeout``, an application's own) cannot be
#: seen from here and is overridden: see README "Tracing".
_dump_owner: Optional["HeartbeatMonitor"] = None
_dump_owner_lock = threading.Lock()


class HeartbeatMonitor:
    def __init__(
        self,
        pool: ExecutorPool,
        on_executor_lost: Callable[[int], None],
        timeout_ms: float = 5000.0,
        check_interval_s: float = 0.5,
        task_timeout_ms: Optional[float] = None,
        clock: Optional[Clock] = None,
        on_sibling_lost=None,
        dump_on_stall: bool = False,
    ):
        """``timeout_ms`` applies to *idle* silence (a dead thread).  A worker
        legitimately goes silent while running a long task (first XLA compile
        is tens of seconds), so busy executors are only timed out when
        ``task_timeout_ms`` is set (hung-task detection, off by default --
        slow tasks are the *straggler* story, handled by cohort selection,
        not by killing workers).

        The monitor is a ticker, so it also gauges the host: how late each
        scan woke (``stall_max_ms``, and ``stalls`` over ``STALL_MS``).
        That is a record only; failure detection does not read it.  With
        ``dump_on_stall`` every scan re-arms ``faulthandler.dump_traceback_
        later(STALL_DUMP_S)``: the next time no scan comes for that long,
        the interpreter's C watchdog dumps all threads' stacks to stderr
        and so names the thread that held the interpreter.  That watchdog
        is process-wide: only one monitor at a time owns it
        (``_dump_owner``)."""
        self._pool = pool
        self._on_lost = on_executor_lost
        # on_sibling_lost(wid, queued_tasks, running_task): a failed
        # dynamic-allocation sibling must NOT escalate to slot loss -- the
        # primary is healthy, and resubmitting ITS in-flight tasks would
        # inflate their attempts (spurious max-failures abort) and
        # duplicate running work.  Only the sibling's own tasks resubmit.
        self._on_sibling_lost = on_sibling_lost
        self._timeout_ms = timeout_ms
        self._task_timeout_ms = task_timeout_ms
        self._interval = check_interval_s
        self._clock = clock or SystemClock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._dump_on_stall = dump_on_stall
        self.stall_max_ms = 0.0
        self.stalls = 0

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="heartbeat-monitor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        global _dump_owner
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        with _dump_owner_lock:
            if _dump_owner is self:
                faulthandler.cancel_dump_traceback_later()
                _dump_owner = None

    def check_once(self) -> list:
        """One scan; returns the worker ids declared lost (test-friendly)."""
        if self._pool.closed:
            return []
        now = self._clock.now_ms()

        def is_bad(ex) -> bool:
            if ex.shutdown_requested:
                return False  # graceful stop, not a failure
            if not ex.alive:
                return True
            if ex.busy:
                return (
                    self._task_timeout_ms is not None
                    and now - ex.busy_since_ms > self._task_timeout_ms
                )
            return now - ex.last_heartbeat_ms > self._timeout_ms

        lost = []
        for wid, ex in list(self._pool.executors.items()):
            if is_bad(ex):
                lost.append(wid)
            # dynamic-allocation siblings carry tasks too: a dead or hung
            # sibling is dropped and ONLY ITS tasks resubmit -- the healthy
            # primary's in-flight work keeps its attempt counts.  Without a
            # resubmission handler the sibling's tasks would be silently
            # discarded (hung jobs), so fall back to escalating the whole
            # slot -- on_lost's resubmission covers them
            for sib in self._pool.siblings_of(wid):
                if is_bad(sib):
                    if self._on_sibling_lost is not None and wid not in lost:
                        queued, running = self._pool.drop_sibling(wid, sib)
                        self._on_sibling_lost(wid, queued, running)
                    else:
                        # no handler, OR the slot is already being
                        # escalated this scan: on_lost's resubmission
                        # covers the sibling's tasks -- relaunching them
                        # here too would double-execute and double-bump
                        # their attempts
                        self._pool.drop_sibling(wid, sib)
                        if wid not in lost:
                            lost.append(wid)
        for wid in lost:
            self._on_lost(wid)
        return lost

    def _arm_stall_dump(self) -> None:
        global _dump_owner
        with _dump_owner_lock:
            if _dump_owner is None:
                _dump_owner = self
            if _dump_owner is not self:
                self._dump_on_stall = False  # another traced run owns it
                return
            try:
                faulthandler.dump_traceback_later(STALL_DUMP_S)
            except (RuntimeError, ValueError, OSError, AttributeError):
                # stderr is gone or is no real file (a captured test run):
                # there is nowhere to dump to
                self._dump_on_stall = False
                _dump_owner = None

    def _run(self) -> None:
        interval_ms = self._interval * 1e3
        # how late a scan WOKE: from the end of the last one, so that the
        # monitor's own work (an executor replaced in check_once) is not
        # reported as a stall of the host
        asleep_since = self._clock.now_ms()
        while True:
            if self._dump_on_stall:
                self._arm_stall_dump()
            if self._stop.wait(self._interval):
                return
            late_ms = self._clock.now_ms() - asleep_since - interval_ms
            if late_ms > self.stall_max_ms:
                self.stall_max_ms = late_ms
            if late_ms > STALL_MS:
                self.stalls += 1
            self.check_once()
            asleep_since = self._clock.now_ms()
