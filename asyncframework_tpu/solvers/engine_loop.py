"""One engine loop: what every run of the engine's solvers does alike.

:class:`EngineRun` is one run: the context, scheduler, delay model,
calibrator, waiting table and instruments; the heartbeat, speculation and
allocation monitors; the restore of the checkpoint fields every solver
saves; the submitter loop; the teardown; the fenced read-back and the
:class:`~asyncframework_tpu.solvers.base.TrainResult`.  A solver's ``run``
and ``run_sync`` are what is left: its state, its updater, its extras.

What differs between solvers reaches this module as a callable or a dict
(the updater, what a cohort's tasks capture, the hook for a re-homed shard,
the solver's own checkpoint and result fields); nothing here asks which
solver it serves.  The updater in particular is taken whole and only
started, joined and clocked: its body stays ONE frame in the solver,
because an accept path behind a per-result call drops its temporaries under
the state lock while the dispatches that read them are in flight (PERF.md
section 6, PR 23 and PR 25: 15% to a third of the update rate on the CPU
rehearsal).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from asyncframework_tpu.broadcast import VersionedModelStore
from asyncframework_tpu.context import AsyncContext
from asyncframework_tpu.engine.allocation import make_allocation_manager
from asyncframework_tpu.engine.barrier import bucket_predicate, partial_barrier
from asyncframework_tpu.engine.scheduler import ASYNC, JobScheduler
from asyncframework_tpu.engine.speculation import SpeculationMonitor
from asyncframework_tpu.engine.straggler import DelayModel
from asyncframework_tpu.metrics import timeseries, trace
from asyncframework_tpu.solvers.base import (
    DelayCalibrator,
    FlopsAccountingMixin,
    SolverCheckpointer,
    TrainResult,
    WaitingTimeTable,
    collect_checked,
)
from asyncframework_tpu.solvers.instrumentation import (
    FaultTolerantRun,
    RunInstruments,
    StepsOut,
)


class EngineSolver(FlopsAccountingMixin):
    """What the engine's solvers share below their run methods.  Hosts
    provide ``cfg``, ``devices``, ``ds``, ``_recovery``, ``_sparse``,
    ``_eval`` (the trajectory's loss evaluation), ``_path_extras``,
    optionally ``_step_nonzeros`` and ``_step_walked`` (by worker, the
    non-zeros a padded-ELL step samples on average and the slots it
    gathers and scatter-adds for them) and ``_result_payload``: what of a
    worker step's outputs ``(..., new_key)`` rides the ``PartialResult`` to the
    updater (everything but the key)."""

    #: the steps out on each chip, by device (``instrumentation.StepsOut``:
    #: what ``_make_task`` hands ``worker_task``): a run's own, set where
    #: the run is built; none before a solver's first run
    _steps_out: Dict = {}

    def _collect_checked(self, ctx: AsyncContext, waiter, timeout_s: float,
                         pool=None, cohort=None, collected=None):
        """Shared fail-fast drain (solvers/base.py): surfaces job aborts,
        and -- given the pool -- aborts promptly with the per-worker
        liveness diagnostic when a cohort executor dies unreplaced,
        instead of hanging for the full run timeout."""
        grace = (
            4.0 * self.cfg.heartbeat_interval_s + 2.0
            if self.cfg.heartbeat else 0.5
        )
        return collect_checked(
            ctx, waiter, timeout_s, pool=pool, cohort=cohort,
            dead_grace_s=grace, collected=collected,
        )

    def _shard_device(self, wid: int):
        return self.devices[wid % len(self.devices)]

    def _handler(self, run: "EngineRun", submit_clock: int, uts):
        """The result handler of one submitted cohort (it runs on the
        completing executor's thread): advance the worker's key, then
        queue the step's payload for the updater."""
        ctx, now_ms = run.ctx, run.now_ms
        worker_keys, key_lock = run.worker_keys, run.key_lock
        pinned = run.pinned
        payload_of = self._result_payload
        occupancy = run.inst.occupancy  # None unless the run is traced
        submit_wall = now_ms()
        par_recs = int(self.cfg.batch_rate * self.ds.n / self.cfg.num_workers)

        def handler(wid: int, result):
            # The key slot MUST advance before merge_result flips the worker
            # available -- otherwise the spinning submitter can re-dispatch
            # this worker with its previous key and replay the same mask.
            with key_lock:
                worker_keys[wid] = result[-1]
                pinned.pop(wid, None)  # the task is back: it pins no model
            ut = uts.get(wid) if uts else None
            if occupancy is not None:
                occupancy.leave(wid)
            if ut is not None:
                ut.begin(trace.RESULT_QUEUE)
            ctx.merge_result(
                wid,
                payload_of(result),
                submit_clock=submit_clock,
                elapsed_ms=now_ms() - submit_wall,
                batch_size=par_recs,
                trace=ut,
            )

        return handler

    def _evaluate_trajectory(
        self, snapshots: List[Tuple[float, jax.Array]], ut=None,
        counters: Optional[Dict[str, object]] = None,
    ) -> List[Tuple[float, float]]:
        """One-pass objective evaluation for all snapshots (optVars parity):
        stack snapshots into (S, d); per shard one matmul gives (S,) losses.
        A padded-ELL shard is walked in row blocks, one gather a block for
        ``_eval.snapshots_per_call`` snapshots: the stack is cut to that
        many a call (the last padded with the first snapshot again), so
        one executable serves every trajectory length.  ONE call's stack is
        alive at a time: it is built, every shard's sums of it are read
        back, and it is dropped before the next is stacked (at d = 54.7M a
        stack of eight is 1.75 GB beside the snapshots it copies).

        It is the stage ``trajectory.eval`` (``ut``: the run's trace handle
        in a traced run; ``batch`` = snapshots, ``calls`` = stacks).
        ``counters`` (a run's ``extras``) is told what it cost:
        ``trajectory_eval_s`` on the host's clock, to the read-back of the
        last shard's sums; ``eval_blocks``, the gathers made (one a row
        block a call; a dense shard is one block); ``eval_snapshots``;
        ``eval_calls`` and ``eval_stack_rows``, the stacks built and the
        model-sized rows of one; and for padded ELL ``eval_slots``, the
        slots of the blocks those gathers walked as they are STORED (a
        clamped last block counted whole), and ``eval_live_slots``, the
        slots they picked: the blocks at the width the evaluation reads
        (equal where it was built at the stored width)."""
        t0 = time.perf_counter()
        handles = [h for (_t, h) in snapshots]
        per_call = getattr(self._eval, "snapshots_per_call", len(handles))
        calls = -(-len(handles) // per_call)
        blocks = slots = live_slots = 0
        totals = np.zeros(calls * per_call, np.float64)
        with trace.span(trace.TRAJECTORY_EVAL, ut, batch=len(handles),
                        calls=calls):
            for lo in range(0, len(handles), per_call):
                group = handles[lo:lo + per_call]
                group += handles[:1] * (per_call - len(group))
                W = jnp.stack(group)
                on = {W.device: W}  # this call's (per_call, d), by device
                parts = []
                for wid in range(self.cfg.num_workers):
                    shard = self._recovery.shard(wid)  # follows re-homed shards
                    if self._sparse:
                        arrays = (shard.cols, shard.vals, shard.y)
                        stored = shard.cols.shape[1]
                        n_blocks = self._eval.blocks(shard.size, stored)
                        walked = n_blocks * self._eval.block_rows(
                            shard.size, stored)
                        slots += walked * stored
                        live_slots += walked * self._eval.width(stored)
                    else:
                        arrays = (shard.X, shard.y)
                        n_blocks = 1
                    blocks += n_blocks
                    if shard.device not in on:
                        on[shard.device] = jax.device_put(W, shard.device)
                    parts.append(self._eval(*arrays, on[shard.device]))
                # the read-back is the fence: the stack is dead after it.
                # Shard by shard in worker order, as the sums always were
                # added: a trajectory is the same to the bit
                for part in parts:
                    totals[lo:lo + per_call] += np.asarray(part, np.float64)
                del W, on, parts
        totals = totals[:len(handles)] / self.ds.n
        traj = [(t, float(l)) for (t, _), l in zip(snapshots, totals)]
        if counters is not None:
            counters.update(
                trajectory_eval_s=time.perf_counter() - t0,
                eval_blocks=blocks, eval_snapshots=len(handles),
                eval_calls=calls, eval_stack_rows=per_call,
            )
            if self._sparse:
                counters["eval_slots"] = slots
                counters["eval_live_slots"] = live_slots
        # continuous telemetry: the finished run's loss-vs-wallclock curve
        # lands in the process-global convergence history (the /api/status
        # `convergence` section the in-process live UI serves)
        timeseries.fold_trajectory(traj)
        return traj


class EngineRun:
    """One run of an :class:`EngineSolver`, asynchronous or (``sync``) one
    driver thread that submits to all and drains all each round.

    ``solver.cfg`` is read here, when the run is built: a solver object
    is reusable, and its ``cfg`` may be re-assigned between runs."""

    def __init__(self, solver: EngineSolver, sync: bool = False):
        cfg = solver.cfg
        nw = cfg.num_workers
        self.solver, self.cfg, self.sync = solver, cfg, sync
        self._built = time.monotonic()
        self.ctx: AsyncContext = AsyncContext()
        self.sched = JobScheduler(num_workers=nw, devices=solver.devices)
        # non-blocking submit in both modes: a sync run drains on the driver
        self.sched.set_mode(ASYNC)
        solver.scheduler = self.sched  # exposed for fault-injection tests/tools
        solver._steps_out = {dev: StepsOut() for dev in solver.devices}
        self.delay_model = DelayModel(cfg.coeff, nw, cfg.seed)
        # sync counts rounds, not accepted gradients: the reference's
        # k < 100*numPart window covers the first 100 full-drain rounds.
        # An explicit calibration_iters overrides (in rounds).
        self.calibrator = DelayCalibrator(
            100 if sync and cfg.calibration_iters is None
            else cfg.effective_calibration_iters()
        )
        self.waiting = WaitingTimeTable()
        self.inst = RunInstruments(
            cfg, nw, chip_of=lambda wid: solver._shard_device(wid).id
        )
        self.inst.register_queue_depth(self.ctx.size)
        self.ckpt: Optional[SolverCheckpointer] = None
        #: every worker's PRNG chain, on its shard's device; ``key_lock``
        #: guards the slots (a solver may keep further per-worker handle
        #: slots under the same lock)
        self.worker_keys: Dict[int, jax.Array] = {}
        self.key_lock = threading.Lock()
        #: the model handle ``w``, the accepted count ``k`` and the run's
        #: counters, under ``state_lock``; the solver adds its own fields
        self.state: Dict[str, object] = {}
        self.state_lock = threading.Lock()
        self.stop = threading.Event()
        #: the account of model-sized device buffers (``d`` f32 each: 3 kB
        #: in the dense cells, 219 MB at d = 54.7M) this run's engine
        #: holds.  ``pinned``: the model version each task that is out was
        #: handed, by worker, as the handle's ``id`` (the account itself
        #: pins nothing), under ``key_lock``, and the most distinct ones at
        #: a submit; the other maxima are the updater's readings
        #: (:meth:`count_copies`), one a drain
        self.pinned: Dict[int, int] = {}
        self.copies = {"results_held_max": 0, "versions_pinned_max": 0,
                       "model_copies_peak": 0}
        self._snapshot_ids: set = set()
        self._snapshots_counted = 0
        self._ft = self._spec = self._alloc = None
        #: what :meth:`drive` read of its own end (a sync run has none),
        #: and when its submitter loop left
        self._tail: Optional[Dict[str, object]] = None
        self._loop_exit = 0.0

    # ------------------------------------------------------------ the state
    def cold_start(self) -> None:
        """``w = 0`` on the driver's device; every worker's PRNG chain at
        its start, resident on its shard's device."""
        solver, cfg = self.solver, self.cfg
        self._set_state(
            jnp.zeros(solver.ds.d, jnp.float32), 0,
            {wid: jax.random.fold_in(jax.random.PRNGKey(cfg.seed), wid)
             for wid in range(cfg.num_workers)},
        )

    def restore(self, name: str) -> Optional[Dict]:
        """Resume from ``cfg.checkpoint_dir``'s latest checkpoint of solver
        ``name``, or start cold: the model, the accepted-update counter,
        the logical clock and every worker's PRNG chain come back exactly
        where they stopped.  Returns the checkpoint (None on a cold start)
        for the solver's own fields."""
        solver = self.solver
        self.ckpt = SolverCheckpointer(
            self.cfg, name, solver.ds.d, solver.ds.n
        )
        ck = self.ckpt.restore()
        if ck is None:
            self.cold_start()
        else:
            self.ctx.set_current_time(int(ck["clock"]))
            self._set_state(
                jnp.asarray(ck["w"]), int(ck["k"]),
                {wid: jnp.asarray(key)
                 for wid, key in ck["worker_keys"].items()},
            )
        return ck

    def _set_state(self, w, k: int, keys: Dict) -> None:
        solver = self.solver
        self.worker_keys.update(
            (wid, jax.device_put(key, solver._shard_device(wid)))
            for wid, key in keys.items()
        )
        self.state.update(
            w=jax.device_put(w, solver.driver_device), k=k, accepted=0,
            dropped=0, rounds=0, flops=0.0,
        )

    def save(self, k: int, w, **fields) -> None:
        """Checkpoint the fields every solver saves plus the solver's own
        ``fields`` (host values)."""
        with self.key_lock:
            keys_h = {
                wid: np.asarray(kv) for wid, kv in self.worker_keys.items()
            }
        self.ckpt.save(
            k, w=np.asarray(w), clock=self.ctx.get_current_time(),
            worker_keys=keys_h, **fields,
        )

    # ---------------------------------------------------------- the monitors
    def start_monitors(self, on_moved: Optional[Callable] = None) -> None:
        """Heartbeat (executor replacement, shard re-homing), speculation
        and dynamic allocation, each where ``cfg`` asks for it.
        ``on_moved(shard_id, moved_shard)``: what of the solver's state
        follows a re-homed shard to its new device."""
        cfg, sched, inst = self.cfg, self.sched, self.inst
        if cfg.heartbeat:
            self._ft = FaultTolerantRun(
                sched, self.solver._recovery, inst, cfg.num_workers,
                heartbeat_timeout_ms=cfg.heartbeat_timeout_ms,
                check_interval_s=cfg.heartbeat_interval_s,
                max_slot_failures=cfg.max_slot_failures,
                on_moved=on_moved,
            )
            self._ft.start()
        if cfg.speculation:
            # in a sync run too: the reference runs speculation on its
            # synchronous stages, where one straggler stalls the round
            self._spec = SpeculationMonitor(
                sched, quantile=cfg.speculation_quantile,
                multiplier=cfg.speculation_multiplier,
                min_time_ms=cfg.speculation_min_ms,
                on_launch=inst.on_speculative_launch,
            )
            self._spec.start()
        self._alloc = make_allocation_manager(cfg, sched)

    def shutdown(self, run_ok: bool) -> None:
        """Stop what :meth:`start_monitors` started, and the scheduler."""
        for monitor in (self._ft, self._spec, self._alloc):
            if monitor is not None:
                monitor.stop()
        self.sched.shutdown()
        if not run_ok:
            self.inst.close()  # crash path: flush/seal the event log now

    # -------------------------------------------------------------- the clock
    def start_clock(self) -> None:
        """The trajectory's clock starts (after the solver's warm-up)."""
        self.start_wall = time.monotonic()
        self._clock0 = self.ctx.get_current_time()  # results so far: none
        self.inst.on_run_start()
        self.snapshots: List[Tuple[float, jax.Array]] = [
            (0.0, self.state["w"])
        ]

    def now_ms(self) -> float:
        return (time.monotonic() - self.start_wall) * 1e3

    # ------------------------------------------------- the model-sized state
    def pin(self, cohort, w_pub) -> None:
        """The submitter hands ``cohort`` the model version ``w_pub``: each
        task holds it until its result is back (the handler unpins)."""
        version = id(w_pub)
        with self.key_lock:
            for wid in cohort:
                self.pinned[wid] = version
            self.copies["versions_pinned_max"] = max(
                self.copies["versions_pinned_max"],
                len(set(self.pinned.values())))

    def count_copies(self, results_held: int, stack_rows: int = 0) -> None:
        """One reading of the account, by the thread that holds
        ``state_lock`` (the updater at a drain; the main thread once the
        run is over): ``results_held`` results computed and not yet
        applied, and the distinct model-sized buffers in all: the live model, those
        versions and the snapshots (one buffer may be all three), the
        results, and ``stack_rows`` rows of an evaluation call's stack."""
        with self.key_lock:
            versions = set(self.pinned.values())
        seen = self._snapshot_ids
        for _t, handle in self.snapshots[self._snapshots_counted:]:
            seen.add(id(handle))  # a snapshot lives as long as the run
        self._snapshots_counted = len(self.snapshots)
        copies = self.copies
        copies["results_held_max"] = max(
            copies["results_held_max"], results_held)
        versions.add(id(self.state["w"]))
        copies["model_copies_peak"] = max(
            copies["model_copies_peak"],
            len(seen) + len(versions - seen) + results_held + stack_rows,
        )

    # -------------------------------------------------------- submitter loop
    def drive(self, updater: Callable[[], None], thread_name: str,
              make_tasks: Callable) -> None:
        """Start ``updater`` on its thread and submit cohorts from this one
        until the iteration budget is spent, the updater stops the run or
        ``run_timeout_s`` passes; then tear the run down.

        ``make_tasks(cohort, w_pub, uts)`` gives the cohort's task closures
        by worker id: what a task captures (under which lock) is the
        solver's.  The updater owns ``state`` past ``w`` and ``k``; it ends
        with ``stop.set()``."""
        cfg, ctx, sched, inst = self.cfg, self.ctx, self.sched, self.inst
        solver, waiting, now_ms = self.solver, self.waiting, self.now_ms
        state, state_lock, stop = self.state, self.state_lock, self.stop
        clock = inst.submitter_clock
        nw, budget, ratio = cfg.num_workers, cfg.num_iterations, cfg.bucket_ratio
        # stale-read experiment (ASYNCbroadcast.value(index) parity; the
        # reference's main user is SparkASAGAThread.scala:268): workers read
        # model version (latest - offset)
        store = (
            VersionedModelStore(cfg.max_live_versions)
            if cfg.stale_read_offset is not None
            else None
        )
        upd = threading.Thread(target=updater, name=thread_name, daemon=True)
        upd.start()
        waiters: deque = deque(maxlen=4 * nw)  # recent jobs, failure check
        bucket = bucket_predicate(ctx, nw, ratio)
        submitted = 0
        deadline = time.monotonic() + cfg.run_timeout_s
        run_ok = False
        try:
            while not stop.is_set() and time.monotonic() < deadline:
                failed = next((x.failed for x in waiters if x.failed), None)
                if failed is not None:
                    raise RuntimeError("async job aborted") from failed
                with state_lock:
                    if state["k"] >= budget:
                        break
                # cold workers (no STAT entry) always selected; warm workers
                # only when the availability threshold is met (the reference's
                # wait loop + ASYNCbarrier combination).  Nothing is
                # submitted while the updater is a whole fleet of results
                # behind: a worker is available again the moment its result
                # is QUEUED, so a device that outruns the updater (32
                # workers at 0.6 ms a step, PERF.md section 6, PR 26) would
                # otherwise fill the queue without bound, with gradients
                # seconds old whose recorded staleness still reads under nw
                behind = ctx.size() >= nw
                cohort = [] if behind else partial_barrier(ctx, nw, bucket)
                if not cohort:
                    # the account of what the device was NOT given, where
                    # that is decided: the sleep goes to what this turn
                    # saw.  The updater a fleet behind; workers available
                    # and the recipe's bucket holding them (fewer than its
                    # threshold: a worker freed since the barrier looked
                    # does not count); or everything in flight.  The two
                    # holds show in a profiler session (metrics/trace.py)
                    if behind:
                        hold = trace.HOLD_BACKLOG
                    elif 0 < ctx.available_workers() < bucket.threshold:
                        hold = trace.HOLD_BARRIER
                    else:
                        hold = trace.WAIT_WORKERS
                    inst.submit_empty_polls += 1
                    with trace.span(hold):
                        clock.waits()
                        time.sleep(0.001)
                        inst.submit_wait_ns[hold] += clock.works()
                    continue
                # the sampling decision falls here, at submit: a sampled
                # update's handle rides its task closure, the handler and
                # the PartialResult to the updater
                uts = inst.start_updates(cohort)
                with trace.span(trace.SUBMIT, uts.values(),
                                batch=len(cohort)) as sub:
                    with state_lock:
                        w_pub = state["w"]  # immutable handle = model version
                        model_version = state["k"]
                    if store is not None:
                        # ASYNCbroadcast parity: publish this round's model
                        # as a new version, then point workers at (latest -
                        # offset).  The version's device buffer is resolved
                        # HERE, at submit time: a straggling worker must not
                        # re-query the store later (the version may have
                        # been evicted by newer publishes); the captured
                        # handle keeps the array alive regardless of store
                        # eviction.
                        v = store.publish(np.asarray(w_pub))
                        live = store.live_versions()
                        tv = max(live[0], v - cfg.stale_read_offset)
                        w_pub = store.value(solver.driver_device, version=tv)
                        model_version = v
                    ts = ctx.get_current_time()
                    ctx.set_last_time(ts)
                    ctx.mark_busy(cohort)
                    submitted += len(cohort)
                    if inst.occupancy is not None:
                        inst.on_busy(cohort, uts, sub.start_ms)
                    waiting.on_submit(cohort, now_ms())
                    if uts:
                        inst.begin_compute(uts, model_version)
                    self.pin(cohort, w_pub)
                    fns = make_tasks(cohort, w_pub, uts)
                    with state_lock:
                        state["rounds"] += 1
                        round_idx = state["rounds"]
                    # post BEFORE launching: a fast worker could otherwise
                    # merge (and the live UI could observe accepted>0)
                    # before its round's RoundSubmitted event exists
                    inst.on_round_submitted(round_idx, cohort, model_version)
                    waiter = sched.run_job(
                        fns, solver._handler(self, ts, uts)
                    )
                waiters.append(waiter)
            run_ok = True
        finally:
            clock.waits()  # the loop's last busy stretch
            # the run's last seconds: nothing is accepted from here on,
            # and ``elapsed_s`` runs on to the fence (``result``)
            t_exit = time.monotonic()
            with state_lock:
                merged = state["accepted"] + state["dropped"]
            stop.set()
            upd.join(timeout=10)
            t_joined = time.monotonic()
            self.shutdown(run_ok)
            self._loop_exit = t_exit
            self._tail = {
                "run_tail_join_s": t_joined - t_exit,
                "run_tail_shutdown_s": time.monotonic() - t_joined,
                # submitted and not merged when the loop left: their
                # steps finish (or not) inside the tail, uncounted
                "inflight_at_stop": submitted - merged,
            }

    # ------------------------------------------------------------ the result
    def result(self, checkpoint: Optional[Callable[[], Dict]] = None,
               more_extras: Optional[Callable[[], Dict]] = None
               ) -> TrainResult:
        """Fence, stop the clock and assemble the result from ``state``.
        ``checkpoint()``: the solver's own fields of the final checkpoint;
        ``more_extras()``: its own ``extras``, computed after the clock has
        stopped and the counters are read."""
        cfg, inst, sched, state = self.cfg, self.inst, self.sched, self.state
        with self.state_lock:
            final_k, final_w_dev = state["k"], state["w"]
            accepted, rounds = state["accepted"], state["rounds"]
            dropped = state["dropped"]
        # materialize BEFORE taking elapsed: the readback of the final
        # model is also the fence (it waits for every apply before it), so
        # elapsed/updates_per_sec cover the work actually done, not merely
        # dispatched.  Whether block_until_ready alone suffices here is
        # ROADMAP Design 8; the result needs final_w on the host anyway.
        t_fence = time.monotonic()
        final_w = np.asarray(final_w_dev)
        occupied = (inst.occupancy.close()
                    if inst.occupancy is not None else {})
        t_end = time.monotonic()
        elapsed = t_end - self.start_wall
        self.snapshots.append((elapsed * 1e3, final_w_dev))
        inst.on_snapshot(accepted)
        # a sync run's one driver thread is clocked as the updater
        clock = inst.updater_clock if self.sync else inst.submitter_clock
        clock.waited(sched.blocked_ns)
        extras = {
            **inst.engine_counters(sched.task_retries, one_thread=self.sync),
            **inst.extras(), **self.solver._path_extras,
            # what every result, snapshot and apply moves: the f32 model
            "model_bytes": 4 * self.solver.ds.d,
            **occupied,
            # the call of run() to the clock's start: the run built, the
            # checkpoint restored, the solver's warm-up of its hot path
            "run_lead_s": self.start_wall - self._built,
        }
        # how often each worker was accepted, and what the accepted steps
        # sampled of the data on average: shards of unequal width make
        # workers of unequal speed, and the narrow ones come round oftener
        by_worker = [inst.accepted_by_worker.get(wid, 0)
                     for wid in range(cfg.num_workers)]
        extras.update(accepted_by_worker_min=min(by_worker),
                      accepted_by_worker_max=max(by_worker))
        for name, by_shard in (
                ("nonzero_slots_per_step_mean", "_step_nonzeros"),
                ("walked_slots_per_step_mean", "_step_walked")):
            per_step = getattr(self.solver, by_shard, None)
            if per_step and sum(by_worker):
                extras[name] = sum(
                    c * z for c, z in zip(by_worker, per_step)
                ) / sum(by_worker)
        if self._tail is not None:
            # drive()'s own end: from the submitter loop's exit to the
            # fence's end nothing is accepted, and all of it lies inside
            # elapsed_s.  results_unmerged: of the tasks in flight at the
            # exit, those whose result came and was never applied
            extras.update(
                self._tail, run_tail_s=t_end - self._loop_exit,
                run_tail_fence_s=t_end - t_fence,
                results_unmerged=(
                    self.ctx.get_current_time() - self._clock0
                    - accepted - dropped
                ),
            )
        t = time.monotonic()
        if self.ckpt is not None and self.ckpt.enabled:
            self.save(final_k, final_w_dev,
                      **(checkpoint() if checkpoint is not None else {}))
        extras["checkpoint_s"] = time.monotonic() - t
        traj = self.solver._evaluate_trajectory(
            self.snapshots, inst.run_trace(), extras
        )
        # the account's last reading: every snapshot and one call's stack
        with self.state_lock:
            self.count_copies(0, extras["eval_stack_rows"])
        extras.update(self.copies, snapshots_held=len(self.snapshots))
        if self._spec is not None:
            extras["speculated"] = self._spec.speculated_count()
            extras["speculation_wins"] = sched.speculative_wins()
        if self._alloc is not None:
            extras["executors_added"], extras["executors_removed"] = (
                self._alloc.counts()
            )
        t = time.monotonic()
        inst.close(traj, cfg.printer_freq)
        extras["close_s"] = time.monotonic() - t
        if more_extras is not None:
            extras = {**more_extras(), **extras}
        # one update a round in a sync run, one an accepted gradient else
        updates = rounds if self.sync else accepted
        return TrainResult(
            final_w=final_w,
            trajectory=traj,
            elapsed_s=elapsed,
            accepted=accepted,
            dropped=dropped,
            rounds=rounds,
            max_staleness=self.ctx.max_staleness(),
            avg_delay_ms=self.calibrator.avg_delay_ms,
            updates_per_sec=updates / elapsed if elapsed > 0 else 0.0,
            total_flops=state["flops"],
            waiting_time_ms=(
                {} if inst.occupancy is None else
                {wid: s * 1e3
                 for wid, s in inst.occupancy.worker_idle_s.items()}
            ),
            extras=extras,
            snapshot_updates=inst.snapshot_updates,
            staleness_hist=dict(sorted(inst.staleness_hist.items())),
        )
