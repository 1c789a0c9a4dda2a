"""ASGD: asynchronous (and synchronous) stochastic gradient descent.

The TPU-native re-design of the reference's flagship drivers:

- async mode ~ ``SparkASGDThread.scala`` -- two driver threads (submitter +
  updater) around an :class:`AsyncContext`; per-worker gradients stream in and
  are applied under a staleness bound ``taw``; cohorts are selected by a
  partial barrier over worker availability; stragglers can be injected after a
  calibration phase.
- sync mode ~ ``SparkASGDSync.scala`` -- the same non-blocking submission
  machinery, but each round drains exactly ``num_workers`` results and applies
  one accumulated update (the "barrier in the driver").

TPU-first hot path: every array the algorithm touches stays in device HBM.
Worker tasks are one fused jit (mask + gradient) on the worker's device; the
updater's accept path is one fused jit (scaled axpy + on-device iteration
counter); the model and snapshots are immutable device handles (old handle ==
old model version -- the versioned-broadcast capability with zero copies).
The host moves only handles and Python ints, so per-update cost is two
dispatches, not two transfers.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from asyncframework_tpu.broadcast import VersionedModelStore
from asyncframework_tpu.context import AsyncContext
from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.engine.barrier import bucket_predicate, partial_barrier
from asyncframework_tpu.engine.recovery import ShardRecovery
from asyncframework_tpu.engine.scheduler import ASYNC, JobScheduler
from asyncframework_tpu.engine.speculation import SpeculationMonitor
from asyncframework_tpu.engine.straggler import DelayModel
from asyncframework_tpu.ops import steps
from asyncframework_tpu.ops.gradients import dense_step_path
from asyncframework_tpu.solvers.base import (
    DelayCalibrator,
    FlopsAccountingMixin,
    make_allocation_manager,
    SolverCheckpointer,
    SolverConfig,
    TrainResult,
    WaitingTimeTable,
    check_hbm_plan,
    collect_checked,
    resolve_dataset,
)
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.solvers.instrumentation import (
    FaultTolerantRun,
    RunInstruments,
    on_device,
    worker_task,
)


# minimum drained-batch size for the stacked one-dispatch apply: below
# this, the stack copy costs more than the dispatches it saves.  Shared by
# the runtime drain and the warm-up gate so the pre-compile always covers
# the path the updater actually takes.
BATCH_DRAIN_MIN = 3


class ASGD(FlopsAccountingMixin):
    def __init__(
        self,
        X,
        y: Optional[np.ndarray],
        config: SolverConfig,
        devices: Optional[list] = None,
    ):
        """``X`` may be a host array (sharded here) or a pre-built
        :class:`ShardedDataset` (e.g. generated on device), with ``y=None``."""
        self.cfg = config
        self.devices = list(devices) if devices is not None else jax.devices()
        check_hbm_plan(X, config, self.devices, history_table=False)
        self.ds = resolve_dataset(X, y, config.num_workers, self.devices)
        self.driver_device = self.devices[0]
        self._sparse = bool(getattr(self.ds, "is_sparse", False))
        if self._sparse:
            if config.loss != "least_squares":
                raise ValueError(
                    "sparse shards currently support least_squares only"
                )
            self._step = steps.make_sparse_asgd_worker_step(
                config.batch_rate, self.ds.d
            )
            self._eval = steps.make_sparse_trajectory_loss_eval()
        else:
            self._step = steps.make_asgd_worker_step(
                config.batch_rate, config.loss
            )
            self._eval = steps.make_trajectory_loss_eval(config.loss)
        self._task_rows = self._step.task_rows  # flop accounting
        # which program a dense step is here, for every result's extras:
        # every shard has one width and dtype, so shard 0 speaks for all
        self._path_extras = {} if self._sparse else {
            "dense_step_path": dense_step_path(self.ds.shard(0).X)
        }
        self._apply = steps.make_asgd_apply(
            config.gamma, config.batch_rate, self.ds.n, config.num_workers
        )
        self._sync_apply = steps.make_sync_apply(
            config.gamma, config.batch_rate, self.ds.n
        )
        # all shard access routes through the recovery view so a re-homed
        # shard is transparently picked up by later rounds and by evaluation
        self._recovery = ShardRecovery(self.ds, self.devices)

    # ------------------------------------------------------------------ async
    def run(self) -> TrainResult:
        """Asynchronous mode (SparkASGDThread parity)."""
        cfg = self.cfg
        nw = cfg.num_workers
        ctx: AsyncContext = AsyncContext()
        sched = JobScheduler(num_workers=nw, devices=self.devices)
        sched.set_mode(ASYNC)
        self.scheduler = sched  # exposed for fault-injection tests/tools
        delay_model = DelayModel(cfg.coeff, nw, cfg.seed)
        calibrator = DelayCalibrator(cfg.effective_calibration_iters())
        waiting = WaitingTimeTable()
        inst = RunInstruments(cfg, nw)
        inst.register_queue_depth(ctx.size)
        ft = None
        if cfg.heartbeat:
            ft = FaultTolerantRun(
                sched, self._recovery, inst, nw,
                heartbeat_timeout_ms=cfg.heartbeat_timeout_ms,
                check_interval_s=cfg.heartbeat_interval_s,
                max_slot_failures=cfg.max_slot_failures,
            )
            ft.start()
        spec = None
        if cfg.speculation:
            spec = SpeculationMonitor(
                sched, quantile=cfg.speculation_quantile,
                multiplier=cfg.speculation_multiplier,
                min_time_ms=cfg.speculation_min_ms,
                on_launch=inst.on_speculative_launch,
            )
            spec.start()
        alloc = make_allocation_manager(cfg, sched)
        # stale-read experiment: workers read version (latest - offset)
        store = (
            VersionedModelStore(cfg.max_live_versions)
            if cfg.stale_read_offset is not None
            else None
        )

        d = self.ds.d
        ckpt = SolverCheckpointer(cfg, "asgd", d, self.ds.n)
        ck = ckpt.restore()
        if ck is not None:
            # Resume: model, accepted-update counter, logical clock, and every
            # worker's PRNG chain come back exactly where they stopped.
            k0 = int(ck["k"])
            ctx.set_current_time(int(ck["clock"]))
            w = jax.device_put(jnp.asarray(ck["w"]), self.driver_device)
            k_dev = jax.device_put(jnp.float32(k0), self.driver_device)
            worker_keys: Dict[int, jax.Array] = {
                wid: jax.device_put(jnp.asarray(key), self._shard_device(wid))
                for wid, key in ck["worker_keys"].items()
            }
        else:
            k0 = 0
            w = jax.device_put(jnp.zeros(d, jnp.float32), self.driver_device)
            k_dev = jax.device_put(jnp.float32(0.0), self.driver_device)
            # per-worker device-resident PRNG chains
            worker_keys = {
                wid: jax.device_put(
                    jax.random.fold_in(jax.random.PRNGKey(cfg.seed), wid),
                    self._shard_device(wid),
                )
                for wid in range(nw)
            }
        key_lock = threading.Lock()

        state = {
            "w": w,
            "k_dev": k_dev,
            "k": k0,
            "accepted": 0,
            "dropped": 0,
            "rounds": 0,
            "flops": 0.0,
        }
        state_lock = threading.Lock()
        stop = threading.Event()
        apply_batch = steps.make_asgd_apply_batch(
            cfg.gamma, cfg.batch_rate, self.ds.n, nw, cfg.drain_batch
        )
        self._warm_hot_path(apply_batch, max(cfg.drain_batch, 1))
        start_wall = time.monotonic()
        inst.on_run_start()
        snapshots: List[Tuple[float, jax.Array]] = [(0.0, w)]

        def now_ms() -> float:
            return (time.monotonic() - start_wall) * 1e3

        # ---------------------------------------------------- updater thread
        def save_checkpoint(save_k: int, save_w) -> None:
            with key_lock:
                keys_h = {wid: np.asarray(kv) for wid, kv in worker_keys.items()}
            ckpt.save(
                save_k,
                w=np.asarray(save_w),
                clock=ctx.get_current_time(),
                worker_keys=keys_h,
            )

        # per-accepted-count mask cache: rebuilt host constants would cost
        # an extra transfer per drain.  Short drains pad the gradient LIST
        # with this cached zero handle so the stacked G is always exactly
        # (max_drain, d) -- ONE stack shape, ONE compile (a per-mcount
        # stack/concat would compile a fresh executable for every distinct
        # drain size, inside the timed loop)
        _mask_cache: Dict[int, jax.Array] = {}
        _zero_g = jax.device_put(
            jnp.zeros(d, jnp.float32), self.driver_device
        )

        def updater():
            max_drain = max(cfg.drain_batch, 1)
            clock = inst.updater_clock
            while not stop.is_set():
                with state_lock:
                    if state["k"] >= cfg.num_iterations:
                        break
                clock.waits()
                try:
                    results = [ctx.collect_all(timeout=cfg.collect_timeout_s)]
                except queue.Empty:
                    continue
                finally:
                    clock.works()
                # opportunistic drain: everything already queued, up to the
                # batch cap, folds into one device dispatch below
                while len(results) < max_drain:
                    try:
                        results.append(ctx.collect_all(timeout=0))
                    except queue.Empty:
                        break
                do_save = False
                # the drain's sampled updates (metrics/trace.py; () in an
                # untraced run): their result.queue and compute end here;
                # merge.queue is the lock and the filter, merge.apply the
                # dispatch below
                uts = inst.on_drained(results)
                merge_queue = trace.span(trace.MERGE_QUEUE, uts).begin()
                with state_lock:
                    k = state["k"]
                    # never apply past the iteration budget: trim the batch
                    room = cfg.num_iterations - k
                    merged = []
                    accepted_g = []
                    for res in results:
                        state["flops"] += self._task_flops(res.worker_id)
                        task_ms = waiting.on_finish(res.worker_id, now_ms())
                        accepted = res.staleness <= cfg.taw
                        if accepted and len(accepted_g) >= room:
                            # beyond the iteration budget: ignored, like
                            # the old per-result loop's break-at-limit
                            continue
                        merged.append(
                            (res, accepted, k + len(accepted_g), task_ms)
                        )
                        if accepted:
                            g = res.data
                            if g.device != self.driver_device:
                                g = jax.device_put(g, self.driver_device)
                            calibrator.record(k + len(accepted_g), task_ms)
                            accepted_g.append(g)
                        else:
                            state["dropped"] += 1
                    merge_queue.end()
                    if uts:
                        # what each sampled update's merge.apply carries
                        uts = inst.apply_attrs((m[0], m[1]) for m in merged)
                    t_apply = time.perf_counter_ns()
                    with trace.span(trace.MERGE_APPLY, uts,
                                    batch=len(accepted_g)):
                        if len(accepted_g) >= BATCH_DRAIN_MIN:
                            # stack+apply = 2 dispatches replacing m.  The
                            # list is padded with the cached zero handle to
                            # the fixed max_drain length and masked, so
                            # stack AND apply_batch each compile ONCE,
                            # never per drained batch size.
                            mcount = len(accepted_g)
                            padded = accepted_g + [_zero_g] * (
                                max_drain - mcount
                            )
                            G = jnp.stack(padded)
                            mask = _mask_cache.get(mcount)
                            if mask is None:
                                mask = jax.device_put(
                                    jnp.asarray(
                                        [1.0] * mcount
                                        + [0.0] * (max_drain - mcount),
                                        jnp.float32,
                                    ),
                                    self.driver_device,
                                )
                                _mask_cache[mcount] = mask
                            state["w"], state["k_dev"] = apply_batch(
                                state["w"], G, mask, state["k_dev"]
                            )
                        else:
                            for g in accepted_g:
                                state["w"], state["k_dev"] = self._apply(
                                    state["w"], g, state["k_dev"]
                                )
                    inst.updater_apply_ns += time.perf_counter_ns() - t_apply
                    if accepted_g:
                        k_new = k + len(accepted_g)
                        state["k"] = k_new
                        state["accepted"] += len(accepted_g)
                        # snapshot when the batch crossed a printer boundary
                        # (the single-apply path snapshotted at each
                        # k % printer_freq == 0; a batch may cover several)
                        if any(
                            (k + j) % cfg.printer_freq == 0
                            for j in range(len(accepted_g))
                        ):
                            with trace.span(trace.SNAPSHOT):
                                snapshots.append((now_ms(), state["w"]))
                                inst.on_snapshot(state["accepted"])
                        # range check: a batch jumping over a checkpoint
                        # boundary must still save
                        do_save = ckpt.should_save_range(k, k_new)
                        save_k, save_w = state["k"], state["w"]
                # outside the lock, as ever: the events and the counters
                for res, accepted, at_k, task_ms in merged:
                    inst.on_gradient_merged(res, accepted, at_k, task_ms)
                if do_save:
                    with trace.span(trace.CHECKPOINT):
                        save_checkpoint(save_k, save_w)
                if calibrator.maybe_finalize(state["k"]):
                    delay_model.calibrate(calibrator.avg_delay_ms)
            clock.waits()  # the loop's last busy stretch
            stop.set()

        upd = threading.Thread(target=updater, name="ps-updater", daemon=True)
        upd.start()

        # ---------------------------------------------------- submitter loop
        from collections import deque

        waiters: deque = deque(maxlen=4 * nw)  # recent jobs, failure check
        deadline = time.monotonic() + cfg.run_timeout_s
        run_ok = False
        try:
            while not stop.is_set() and time.monotonic() < deadline:
                failed = next((x.failed for x in waiters if x.failed), None)
                if failed is not None:
                    raise RuntimeError("async job aborted") from failed
                with state_lock:
                    if state["k"] >= cfg.num_iterations:
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
                cohort = [] if ctx.size() >= nw else partial_barrier(
                    ctx, nw, bucket_predicate(ctx, nw, cfg.bucket_ratio)
                )
                if not cohort:
                    inst.submit_empty_polls += 1
                    inst.submitter_clock.waits()
                    time.sleep(0.001)
                    inst.submitter_clock.works()
                    continue
                # the sampling decision falls here, at submit: a sampled
                # update's handle rides its task closure, the handler and
                # the PartialResult to the updater
                uts = inst.start_updates(cohort)
                with trace.span(trace.SUBMIT, uts.values(),
                                batch=len(cohort)):
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
                        w_pub = store.value(self.driver_device, version=tv)
                        model_version = v
                    ts = ctx.get_current_time()
                    ctx.set_last_time(ts)
                    ctx.mark_busy(cohort)
                    waiting.on_submit(cohort, now_ms())
                    if uts:
                        inst.begin_compute(uts, model_version)
                    with key_lock:
                        keys = {wid: worker_keys[wid] for wid in cohort}
                    fns = {
                        wid: self._make_task(
                            wid, w_pub, keys[wid], delay_model, uts.get(wid)
                        )
                        for wid in cohort
                    }
                    with state_lock:
                        state["rounds"] += 1
                        round_idx = state["rounds"]
                    # post BEFORE launching: a fast worker could otherwise
                    # merge (and the live UI could observe accepted>0)
                    # before its round's RoundSubmitted event exists
                    inst.on_round_submitted(round_idx, cohort, model_version)
                    waiter = sched.run_job(
                        fns,
                        self._handler(
                            ctx, ts, now_ms, worker_keys, key_lock, uts
                        ),
                    )
                waiters.append(waiter)
            run_ok = True
        finally:
            inst.submitter_clock.waits()  # the loop's last busy stretch
            stop.set()
            upd.join(timeout=10)
            if ft is not None:
                ft.stop()
            if spec is not None:
                spec.stop()
            if alloc is not None:
                alloc.stop()
            sched.shutdown()
            if not run_ok:
                inst.close()  # crash path: flush/seal the event log now

        with state_lock:
            final_k, final_w_dev = state["k"], state["w"]
        # materialize BEFORE taking elapsed: the readback of the final
        # model is also the fence (it waits for every apply before it), so
        # elapsed/updates_per_sec cover the work actually done, not merely
        # dispatched.  Whether block_until_ready alone suffices here is
        # ROADMAP Design 6; the result needs final_w on the host anyway.
        final_w = np.asarray(final_w_dev)
        elapsed = time.monotonic() - start_wall
        snapshots.append((elapsed * 1e3, final_w_dev))
        inst.on_snapshot(state["accepted"])
        inst.submitter_clock.waited(sched.blocked_ns)
        extras = {**inst.engine_counters(sched.task_retries), **inst.extras(),
                  **self._path_extras}
        if ckpt.enabled:
            save_checkpoint(final_k, final_w_dev)
        traj = self._evaluate_trajectory(snapshots)
        if spec is not None:
            extras["speculated"] = spec.speculated_count()
            extras["speculation_wins"] = sched.speculative_wins()
        if alloc is not None:
            extras["executors_added"], extras["executors_removed"] = (
                alloc.counts()
            )
        inst.close(traj, cfg.printer_freq)
        return TrainResult(
            final_w=final_w,
            trajectory=traj,
            elapsed_s=elapsed,
            accepted=state["accepted"],
            dropped=state["dropped"],
            rounds=state["rounds"],
            max_staleness=ctx.max_staleness(),
            avg_delay_ms=calibrator.avg_delay_ms,
            updates_per_sec=state["accepted"] / elapsed if elapsed > 0 else 0.0,
            total_flops=state["flops"],
            waiting_time_ms=waiting.snapshot(),
            extras=extras,
            snapshot_updates=inst.snapshot_updates,
            staleness_hist=dict(sorted(inst.staleness_hist.items())),
        )

    # ----------------------------------------------------------------- fused
    def run_fused(self) -> TrainResult:
        """Device-resident accept loop: the taw=inf full-wave recipe fused
        into ``lax.scan`` rounds -- zero host work per update, so the
        engine's per-update host dispatch is not on the path.

        Placement, by design: EVERY shard is moved onto the first device
        (``driver_device``) before the scan -- the fused loop is a
        one-device program however many devices the solver was given, and
        its data must fit that one device's memory.

        Scope guard: this is the fast path for exactly the reference's
        headline recipes (``taw = inf``, no straggler injection); anything
        needing the runtime -- finite taw, speculation, fault tolerance,
        dynamic allocation -- runs the engine path.  Dense and padded-ELL
        sparse shards both fuse.  See ``steps.make_fused_asgd_rounds`` for
        the semantics argument.
        """
        cfg = self.cfg
        nw = cfg.num_workers
        if cfg.taw < nw - 1:
            # the fused execution's staleness is bounded by nw-1 BY
            # CONSTRUCTION (one wave in flight, applied in order), so for
            # any taw >= nw-1 it is a valid bounded-staleness execution of
            # the recipe -- ASGD's `staleness <= taw` filter would never
            # fire.  That covers the reference's ASGD headline recipes
            # (taw 2e7 / inf, the reference repo's README.md:64 rows);
            # only genuinely tight bounds need the engine.
            raise ValueError(
                f"run_fused admits taw >= num_workers-1 = {nw - 1} (its "
                "wave staleness never exceeds that); a tighter taw needs "
                "the engine's tau filter -- use run()"
            )
        if cfg.coeff != 0.0:
            raise ValueError(
                "run_fused cannot inject stragglers (no host between "
                "updates); use run()"
            )
        d = self.ds.d
        drv = self.driver_device
        shards = []
        for wid in range(nw):
            shard = self._recovery.shard(wid)
            if self._sparse:
                parts = (shard.cols, shard.vals, shard.y)
            else:
                parts = (shard.X, shard.y)
            if parts[0].device != drv:  # all shards ride the PS device
                parts = tuple(jax.device_put(a, drv) for a in parts)
            shards.append(parts)
        sparse_d = d if self._sparse else None
        total_rounds = max(1, -(-cfg.num_iterations // nw))

        def make_runner(length):
            rr = steps.make_fused_asgd_rounds(
                cfg.gamma, cfg.batch_rate, self.ds.n, shards,
                loss=cfg.loss, rounds_per_call=length, sparse_d=sparse_d,
            )

            def run(carry):
                w, k, keys = carry
                w, k, keys, W_snap = rr(w, k, keys)
                return (w, k, keys), W_snap

            return run

        w = jax.device_put(jnp.zeros(d, jnp.float32), drv)
        k = jax.device_put(jnp.float32(0.0), drv)
        keys = jax.device_put(jnp.stack([
            jax.random.fold_in(jax.random.PRNGKey(cfg.seed), wid)
            for wid in range(nw)
        ]), drv)
        from asyncframework_tpu.solvers.base import run_fused_plan

        (w, k, keys), snapshots, start_wall, done_rounds = run_fused_plan(
            make_runner, (w, k, keys), total_rounds, nw, cfg.printer_freq,
            w_of=lambda c: c[0],
        )
        final_w = np.asarray(w)  # fence BEFORE elapsed (see run())
        elapsed = time.monotonic() - start_wall
        accepted = done_rounds * nw
        snapshots.append((elapsed * 1e3, w))
        traj = self._evaluate_trajectory(snapshots)
        flops = sum(
            self._task_flops(wid) for wid in range(nw)
        ) * done_rounds
        return TrainResult(
            final_w=final_w,
            trajectory=traj,
            elapsed_s=elapsed,
            accepted=accepted,
            dropped=0,
            rounds=done_rounds,
            max_staleness=nw - 1,  # by construction of the full wave
            avg_delay_ms=0.0,
            updates_per_sec=accepted / elapsed if elapsed > 0 else 0.0,
            total_flops=flops,
            waiting_time_ms={},
            extras={"fused": True,
                    "rounds_per_call": min(16, total_rounds),
                    **self._path_extras},
        )

    # ------------------------------------------------------------------ sync
    def run_sync(self) -> TrainResult:
        """SparkASGDSync parity: submit to all, drain all, one update/round."""
        cfg = self.cfg
        nw = cfg.num_workers
        ctx: AsyncContext = AsyncContext()
        sched = JobScheduler(num_workers=nw, devices=self.devices)
        sched.set_mode(ASYNC)  # non-blocking submit + driver-side drain
        self.scheduler = sched  # exposed for fault-injection tests/tools
        delay_model = DelayModel(cfg.coeff, nw, cfg.seed)
        # sync counts rounds, not accepted gradients: the reference's
        # k < 100*numPart window covers the first 100 full-drain rounds.
        # An explicit calibration_iters overrides (in rounds).
        calibrator = DelayCalibrator(
            cfg.calibration_iters if cfg.calibration_iters is not None else 100
        )
        waiting = WaitingTimeTable()
        inst = RunInstruments(cfg, nw)
        inst.register_queue_depth(ctx.size)
        ft = None
        if cfg.heartbeat:
            ft = FaultTolerantRun(
                sched, self._recovery, inst, nw,
                heartbeat_timeout_ms=cfg.heartbeat_timeout_ms,
                check_interval_s=cfg.heartbeat_interval_s,
                max_slot_failures=cfg.max_slot_failures,
            )
            ft.start()
        spec = None
        if cfg.speculation:
            # the reference runs speculation on its synchronous stages: the
            # full drain is exactly where one straggler stalls the round
            spec = SpeculationMonitor(
                sched, quantile=cfg.speculation_quantile,
                multiplier=cfg.speculation_multiplier,
                min_time_ms=cfg.speculation_min_ms,
                on_launch=inst.on_speculative_launch,
            )
            spec.start()
        alloc = make_allocation_manager(cfg, sched)

        w = jax.device_put(jnp.zeros(self.ds.d, jnp.float32), self.driver_device)
        k_dev = jax.device_put(jnp.float32(0.0), self.driver_device)
        worker_keys = {
            wid: jax.device_put(
                jax.random.fold_in(jax.random.PRNGKey(cfg.seed), wid),
                self._shard_device(wid),
            )
            for wid in range(nw)
        }
        self._warm_hot_path(sync=True)
        start_wall = time.monotonic()
        inst.on_run_start()
        snapshots: List[Tuple[float, jax.Array]] = [(0.0, w)]

        def now_ms():
            return (time.monotonic() - start_wall) * 1e3

        rounds = 0
        flops = 0.0
        run_ok = False
        # one driver thread submits and drains: its time outside the
        # blocking collect is the barrier's host work
        clock = inst.updater_clock
        try:
            for k in range(cfg.num_iterations):
                cohort = list(range(nw))
                uts = inst.start_updates(cohort)
                with trace.span(trace.SUBMIT, uts.values(), batch=nw):
                    ts = ctx.get_current_time()
                    ctx.mark_busy(cohort)
                    waiting.on_submit(cohort, now_ms())
                    if uts:
                        inst.begin_compute(uts, k)
                    key_lock = threading.Lock()
                    fns = {
                        wid: self._make_task(
                            wid, w, worker_keys[wid], delay_model,
                            uts.get(wid),
                        )
                        for wid in cohort
                    }
                    inst.on_round_submitted(k, cohort, model_version=k)
                    waiter = sched.run_job(
                        fns,
                        self._handler(
                            ctx, ts, now_ms, worker_keys, key_lock, uts
                        ),
                    )
                acc = None
                reported = set()
                drained = []
                for _ in range(nw):
                    clock.waits()
                    try:
                        res = self._collect_checked(
                            ctx, waiter, cfg.run_timeout_s, pool=sched.pool,
                            cohort=cohort, collected=reported,
                        )
                    finally:
                        clock.works()
                    inst.on_drained((res,))
                    drained.append((res, True))
                    reported.add(res.worker_id)
                    g = res.data
                    flops += self._task_flops(res.worker_id)
                    task_ms = waiting.on_finish(res.worker_id, now_ms())
                    calibrator.record(k, task_ms)
                    inst.on_gradient_merged(res, True, k, task_ms)
                    if g.device != self.driver_device:
                        g = jax.device_put(g, self.driver_device)
                    acc = g if acc is None else steps.add_grads(acc, g)
                with trace.span(trace.MERGE_APPLY,
                                inst.apply_attrs(drained) if uts else None,
                                batch=nw):
                    w, k_dev = self._sync_apply(w, acc, k_dev)
                rounds += 1
                if k % cfg.printer_freq == 0:
                    with trace.span(trace.SNAPSHOT):
                        snapshots.append((now_ms(), w))
                        inst.on_snapshot(rounds * nw)
                if calibrator.maybe_finalize(k):
                    delay_model.calibrate(calibrator.avg_delay_ms)
            run_ok = True
        finally:
            clock.waits()  # the loop's last busy stretch
            if ft is not None:
                ft.stop()
            if spec is not None:
                spec.stop()
            if alloc is not None:
                alloc.stop()
            sched.shutdown()
            if not run_ok:
                inst.close()  # crash path: flush/seal the event log now

        final_w = np.asarray(w)  # fence: see the async path's comment
        elapsed = time.monotonic() - start_wall
        snapshots.append((elapsed * 1e3, w))
        inst.on_snapshot(rounds * nw)
        clock.waited(sched.blocked_ns)
        extras = {
            **inst.engine_counters(sched.task_retries, one_thread=True),
            **inst.extras(),
        }
        traj = self._evaluate_trajectory(snapshots)
        if spec is not None:
            extras["speculated"] = spec.speculated_count()
            extras["speculation_wins"] = sched.speculative_wins()
        if alloc is not None:
            extras["executors_added"], extras["executors_removed"] = (
                alloc.counts()
            )
        inst.close(traj, cfg.printer_freq)
        return TrainResult(
            final_w=final_w,
            trajectory=traj,
            elapsed_s=elapsed,
            accepted=rounds * nw,
            rounds=rounds,
            max_staleness=ctx.max_staleness(),
            avg_delay_ms=calibrator.avg_delay_ms,
            updates_per_sec=rounds / elapsed if elapsed > 0 else 0.0,
            total_flops=flops,
            waiting_time_ms=waiting.snapshot(),
            extras=extras,
            snapshot_updates=inst.snapshot_updates,
            staleness_hist=dict(sorted(inst.staleness_hist.items())),
        )

    # ---------------------------------------------------------------- helpers
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

    def _warm_hot_path(
        self, apply_batch=None, max_drain: int = 0, sync: bool = False
    ) -> None:
        """Compile this mode's hot-path executables before the trajectory
        clock starts.

        Parity: the reference's first iteration always blocks precisely to
        warm Spark's caches (``DAGScheduler.scala:641-656`` ``first_iter``);
        the TPU analog is XLA compilation of the worker step, the accept
        path, and the batched drain, which would otherwise land inside the
        timed region on their first invocation (~1 s on a real chip).

        jit caches per input SHAPE, so every distinct shard shape is warmed
        (shards differ by one row when ``n % num_workers != 0``).  Async
        warms ``_apply`` + ``apply_batch``; sync warms ``_sync_apply`` +
        ``add_grads``.  All dummies are fresh device buffers, so donated
        arguments never touch live state.
        """
        d = self.ds.d
        drv = self.driver_device
        g = None
        seen = set()
        for wid in range(self.cfg.num_workers):
            shard = self._recovery.shard(wid)
            dev = shard.device
            # key on (shape, device): jit executables are cached per device
            # commitment, so equal-shaped shards on different chips each
            # need their own warm compile
            shape_key = (
                (shard.cols.shape if self._sparse else shard.X.shape), dev
            )
            if shape_key in seen:
                continue
            seen.add(shape_key)
            w0 = jax.device_put(jnp.zeros(d, jnp.float32), dev)
            key = jax.device_put(jax.random.PRNGKey(0), dev)
            if self._sparse:
                g, _ = self._step(shard.cols, shard.vals, shard.y, w0, key)
            else:
                g, _ = self._step(shard.X, shard.y, w0, key)
        if g.device != drv:
            g = jax.device_put(g, drv)
        wd = jax.device_put(jnp.zeros(d, jnp.float32), drv)
        kd = jax.device_put(jnp.float32(0.0), drv)
        if sync:
            acc = jax.device_put(jnp.zeros(d, jnp.float32), drv)
            acc = steps.add_grads(acc, g)
            wd, kd = self._sync_apply(wd, acc, kd)
        else:
            wd, kd = self._apply(wd, g, kd)
            if apply_batch is not None and max_drain >= BATCH_DRAIN_MIN:
                # stack of max_drain vectors, exactly like the drain path
                # builds G -- warms the stack executable too, not just
                # apply_batch
                zero = jax.device_put(jnp.zeros(d, jnp.float32), drv)
                G = jnp.stack([zero] * max_drain)
                mask = jax.device_put(
                    jnp.zeros((max_drain,), jnp.float32), drv
                )
                wd, kd = apply_batch(wd, G, mask, kd)
        wd.block_until_ready()

    def _make_task(self, wid: int, w_pub, key, delay_model: DelayModel,
                   ut=None):
        # recovery view: a re-homed shard is transparently computed on its
        # new device; w and the PRNG chain follow the shard's home
        shard = self._recovery.shard(wid)
        dev = shard.device
        step = self._step
        sparse = self._sparse

        def dispatch():
            w_local = on_device(w_pub, dev)
            key_local = on_device(key, dev)
            if sparse:
                return step(shard.cols, shard.vals, shard.y, w_local, key_local)
            return step(shard.X, shard.y, w_local, key_local)

        return worker_task(dispatch, delay_model.delay_ms(wid), ut)

    def _handler(
        self, ctx: AsyncContext, submit_clock: int, now_ms, worker_keys,
        key_lock, uts,
    ):
        submit_wall = now_ms()
        par_recs = int(self.cfg.batch_rate * self.ds.n / self.cfg.num_workers)

        def handler(wid: int, result):
            g, new_key = result
            # The key slot MUST advance before merge_result flips the worker
            # available -- otherwise the spinning submitter can re-dispatch
            # this worker with its previous key and replay the same mask.
            with key_lock:
                worker_keys[wid] = new_key
            ut = uts.get(wid) if uts else None
            if ut is not None:
                ut.begin(trace.RESULT_QUEUE)
            ctx.merge_result(
                wid,
                g,
                submit_clock=submit_clock,
                elapsed_ms=now_ms() - submit_wall,
                batch_size=par_recs,
                trace=ut,
            )

        return handler

    def _evaluate_trajectory(
        self, snapshots: List[Tuple[float, jax.Array]]
    ) -> List[Tuple[float, float]]:
        """One-pass objective evaluation for all snapshots (optVars parity):
        stack snapshots into (S, d); per shard one matmul gives (S,) losses."""
        W = jnp.stack([h for (_t, h) in snapshots])
        totals = np.zeros(len(snapshots), np.float64)
        for wid in range(self.cfg.num_workers):
            shard = self._recovery.shard(wid)  # follows re-homed shards
            Wd = W
            if Wd.device != shard.device:
                Wd = jax.device_put(W, shard.device)
            if self._sparse:
                part = self._eval(shard.cols, shard.vals, shard.y, Wd)
            else:
                part = self._eval(shard.X, shard.y, Wd)
            totals += np.asarray(part, np.float64)
        totals /= self.ds.n
        traj = [(t, float(l)) for (t, _), l in zip(snapshots, totals)]
        # continuous telemetry: the finished run's loss-vs-wallclock curve
        # lands in the process-global convergence history (the /api/status
        # `convergence` section the in-process live UI serves)
        from asyncframework_tpu.metrics import timeseries as _ts

        _ts.fold_trajectory(traj)
        return traj
