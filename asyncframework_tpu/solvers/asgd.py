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

import itertools
import operator
import queue
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.engine.straggler import DelayModel
from asyncframework_tpu.ops import steps
from asyncframework_tpu.solvers.base import (
    SolverConfig,
    TrainResult,
    run_fused_plan,
)
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.solvers.engine_loop import (
    EngineRun,
    EngineSolver,
    ModelReplicas,
)
from asyncframework_tpu.solvers.instrumentation import (
    enqueue_step,
    on_device,
    worker_task,
)


class ASGD(EngineSolver):
    def __init__(
        self,
        X,
        y: Optional[np.ndarray],
        config: SolverConfig,
        devices: Optional[list] = None,
    ):
        """``X`` may be a host array (sharded here) or a pre-built
        :class:`ShardedDataset` (e.g. generated on device), with ``y=None``."""
        self._place(X, y, config, devices, history=False)
        self._apply = steps.make_asgd_apply(
            config.gamma, config.batch_rate, self.ds.n, config.num_workers
        )
        self._apply_fold = steps.make_asgd_apply_fold(
            config.gamma, config.batch_rate, self.ds.n, config.num_workers
        )
        self._sync_apply = steps.make_sync_apply(
            config.gamma, config.batch_rate, self.ds.n
        )

    #: a step returns ``(g, new_key)``: the gradient rides to the updater
    _result_payload = staticmethod(operator.itemgetter(0))

    # ------------------------------------------------------------------ async
    def run(self) -> TrainResult:
        """Asynchronous mode (SparkASGDThread parity)."""
        cfg = self.cfg
        run = EngineRun(self)
        run.restore("asgd")
        ctx, inst, waiting = run.ctx, run.inst, run.waiting
        calibrator, ckpt = run.calibrator, run.ckpt
        state, state_lock, stop = run.state, run.state_lock, run.stop
        d = self.ds.d
        nw, freq = cfg.num_workers, cfg.printer_freq
        # where the model lives: one buffer on the driver's chip, or,
        # where the shards lie on several, a replica on each of ``chips``.
        # The updater then applies every drain to every replica (the same
        # arithmetic on the same operands: the same bits), a task reads
        # the replica on its shard's chip and no step waits for a copy
        # queued behind another chip's steps (PERF.md section 6, PR 47)
        chips = run.replicate_model()
        calls_in = run.calls_in  # the applies are PJRT calls like a step's

        def resident(dev):
            # what the fold takes beside the drain's gradients, resident
            # before the clock starts so that a drain transfers nothing:
            # the ONE zero handle that pads a short drain's tuple to the
            # fold's arity, and every count of live slots as a device
            # scalar (a Python int is a 0.2 ms transfer a dispatch on the
            # v5e: 0.53 ms a fold against 0.34, PERF.md section 6, PR 31)
            return (
                (jax.device_put(jnp.zeros(d, jnp.float32), dev),) * nw,
                [jax.device_put(jnp.float32(m), dev) for m in range(nw + 1)],
            )

        # the on-device iteration counter resumes where k stopped
        k0 = jnp.float32(state["k"])
        if chips is None:
            state["k_dev"] = jax.device_put(k0, self.driver_device)
            zeros, counts = resident(self.driver_device)
        else:
            state["k_dev"] = jax.device_put([k0] * len(chips), chips)
            folds = [resident(dev) for dev in chips]
        run.start_monitors()
        self._warm_hot_path()
        run.start_clock()
        snapshots, now_ms = run.snapshots, run.now_ms

        def updater():
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
                # the drain takes what is there: every result already
                # queued, up to the arity the fold below is compiled for
                # (the submitter's backlog bound keeps the queue near nw;
                # a rest waits for the next wake)
                results.extend(itertools.islice(ctx.drain(), nw - 1))
                do_save = False
                # the drain's sampled updates (metrics/trace.py; () in an
                # untraced run): their result.queue and compute end here;
                # merge.queue is the lock and the filter, merge.apply a
                # dispatch below
                uts = inst.on_drained(results)
                merge_queue = trace.span(trace.MERGE_QUEUE, uts).begin()
                with state_lock:
                    k = state["k"]
                    # the account of model-sized buffers, read where the
                    # most results are held: this drain and what has come
                    # since (engine_loop.EngineRun.count_copies)
                    run.count_copies(len(results) + ctx.size())
                    # never apply past the iteration budget: trim the drain
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
                            # the step's gradient, on the model's one chip;
                            # over several, its buffers by chip (the
                            # task's thread sent them: worker_task)
                            calibrator.record(k + len(accepted_g), task_ms)
                            accepted_g.append(res.data)
                        else:
                            state["dropped"] += 1
                merge_queue.end()
                m = len(accepted_g)
                # ONE dispatch a drain (a chip), split only where a
                # snapshot is due: snapshot j holds the model after update
                # j * printer_freq + 1, folded or not (a reader of the
                # trajectory reckons its updates so, benchmark/target.py),
                # so a dispatch ends ON that update.  The dispatches are
                # made OUTSIDE the state lock: this thread alone writes
                # the model and the counter, and over several chips a
                # drain is a dispatch a chip, 1.5 ms in which the
                # submitter, which takes the lock at every poll and twice
                # a cohort, would stand still (PERF.md section 6, PR 47);
                # what a dispatch made is published under the lock, the
                # model and its count together
                ends = [j + 1 for j in range(-k % freq, m, freq)]
                if not ends or ends[-1] < m:
                    ends.append(m)
                w_new, k_dev = state["w"], state["k_dev"]
                lo = 0
                for hi in ends:
                    n = hi - lo
                    in_it = uts
                    if uts:
                        # the sampled updates of THIS dispatch, each with
                        # what its merge.apply carries; a dropped one
                        # rides with the slot it was filtered before, or
                        # with the last
                        top = hi if hi < m else m + 1
                        in_it = inst.apply_attrs(
                            (r, acc) for r, acc, at_k, _ in merged
                            if lo <= at_k - k < top
                        )
                    t_apply = time.perf_counter_ns()
                    with trace.span(trace.MERGE_APPLY, in_it, batch=n), \
                            calls_in:
                        if chips is not None:
                            if n:
                                # the same dispatch a chip, each on its
                                # own buffer of every operand
                                rows = accepted_g[lo:hi]
                                ws = list(w_new)
                                for c, (pad, count_of) in enumerate(folds):
                                    if n == 1:
                                        ws[c], k_dev[c] = self._apply(
                                            ws[c], rows[0][c], k_dev[c])
                                    else:
                                        ws[c], k_dev[c] = self._apply_fold(
                                            ws[c],
                                            tuple(r[c] for r in rows)
                                            + pad[n:],
                                            count_of[n], k_dev[c],
                                        )
                                w_new = ModelReplicas(ws)
                        elif n == 1:
                            w_new, k_dev = self._apply(
                                w_new, accepted_g[lo], k_dev)
                        elif n:
                            w_new, k_dev = self._apply_fold(
                                w_new, tuple(accepted_g[lo:hi]) + zeros[n:],
                                counts[n], k_dev,
                            )
                    inst.updater_apply_ns += (
                        time.perf_counter_ns() - t_apply
                    )
                    if n:
                        inst.apply_dispatches += 1
                        with state_lock:
                            state["w"], state["k_dev"] = w_new, k_dev
                            state["k"] = k + hi
                            state["accepted"] += n
                            if (k + hi - 1) % freq == 0:
                                with trace.span(trace.SNAPSHOT):
                                    snapshots.append((now_ms(), w_new))
                                    inst.on_snapshot(state["accepted"])
                    lo = hi
                if m:
                    # range check: a drain jumping over a checkpoint
                    # boundary must still save
                    do_save = ckpt.should_save_range(k, k + m)
                    save_k, save_w = state["k"], w_new
                # outside the lock, as ever: the events and the counters
                for res, accepted, at_k, task_ms in merged:
                    inst.on_gradient_merged(res, accepted, at_k, task_ms)
                if do_save:
                    with trace.span(trace.CHECKPOINT):
                        run.save(save_k, save_w)
                if calibrator.maybe_finalize(state["k"]):
                    run.delays_calibrated(state["accepted"])
            clock.waits()  # the loop's last busy stretch
            stop.set()

        run.drive(updater, "ps-updater", self._task_maker(run))
        return run.result()

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
        # all shards ride the PS device
        shards = [self._recovery.shard(wid).on(drv).operands
                  for wid in range(nw)]
        total_rounds = max(1, -(-cfg.num_iterations // nw))

        def make_runner(length):
            rr = self._programs.fused_rounds(
                cfg.gamma, n=self.ds.n, shards=shards,
                rounds_per_call=length,
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
        (w, k, keys), snapshots, start_wall, done_rounds = run_fused_plan(
            make_runner, (w, k, keys), total_rounds, nw, cfg.printer_freq,
            w_of=lambda c: c[0],
        )
        final_w = np.asarray(w)  # fence BEFORE elapsed (EngineRun.result)
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
            extras={"fused": True,
                    "rounds_per_call": min(16, total_rounds),
                    **self._programs.extras},
        )

    # ------------------------------------------------------------------ sync
    def run_sync(self) -> TrainResult:
        """SparkASGDSync parity: submit to all, drain all, one update/round."""
        cfg = self.cfg
        nw = cfg.num_workers
        run = EngineRun(self, sync=True)
        ctx, sched, inst = run.ctx, run.sched, run.inst
        waiting, calibrator = run.waiting, run.calibrator
        run.cold_start()
        w = run.state["w"]
        k_dev = jax.device_put(jnp.float32(0.0), self.driver_device)
        run.start_monitors()
        make_tasks = self._task_maker(run)
        self._warm_hot_path(sync=True)
        run.start_clock()
        snapshots, now_ms = run.snapshots, run.now_ms

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
                with trace.span(trace.SUBMIT, uts.values(), batch=nw) as sub:
                    ts = ctx.get_current_time()
                    ctx.mark_busy(cohort)
                    if inst.occupancy is not None:
                        inst.on_busy(cohort, uts, sub.start_ms)
                    waiting.on_submit(cohort, now_ms())
                    if uts:
                        inst.begin_compute(uts, k)
                    fns = make_tasks(cohort, w, uts)
                    inst.on_round_submitted(k, cohort, model_version=k)
                    waiter = sched.run_job(fns, self._handler(run, ts, uts))
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
                    run.delays_calibrated(rounds * nw)
            run_ok = True
        finally:
            clock.waits()  # the loop's last busy stretch
            run.shutdown(run_ok)
        run.state.update(w=w, accepted=rounds * nw, rounds=rounds, flops=flops)
        return run.result()

    # ---------------------------------------------------------------- helpers
    def _warm_hot_path(self, sync: bool = False) -> None:
        """Compile this mode's hot-path executables before the trajectory
        clock starts.

        Parity: the reference's first iteration always blocks precisely to
        warm Spark's caches (``DAGScheduler.scala:641-656`` ``first_iter``);
        the TPU analog is XLA compilation of the worker step, the accept
        path, and the folded drain, which would otherwise land inside the
        timed region on their first invocation (~1 s on a real chip).

        jit caches per input SHAPE, so every distinct shard shape is warmed
        (shards differ by one row when ``n % num_workers != 0``).  Async
        warms ``_apply`` + ``_apply_fold``; sync warms ``_sync_apply`` +
        ``add_grads``.  All dummies are fresh device buffers, so donated
        arguments never touch live state.
        """
        d = self.ds.d
        g = None
        seen = set()
        for wid in range(self.cfg.num_workers):
            shard = self._recovery.shard(wid)
            dev = shard.device
            # key on (shape, device): jit executables are cached per device
            # commitment, so equal-shaped shards on different chips each
            # need their own warm compile
            shape_key = (shard.shape, dev)
            if shape_key in seen:
                continue
            seen.add(shape_key)
            w0 = jax.device_put(jnp.zeros(d, jnp.float32), dev)
            key = jax.device_put(jax.random.PRNGKey(0), dev)
            g, _ = self._step(*shard.operands, w0, key)
        # the accept path on every chip the updater will call it on: the
        # driver's, or each that holds a replica of the model
        for drv in self._spread or [self.driver_device]:
            g = jax.device_put(g, drv)
            wd = jax.device_put(jnp.zeros(d, jnp.float32), drv)
            kd = jax.device_put(jnp.float32(0.0), drv)
            if sync:
                acc = jax.device_put(jnp.zeros(d, jnp.float32), drv)
                acc = steps.add_grads(acc, g)
                wd, kd = self._sync_apply(wd, acc, kd)
            else:
                # (``g`` is donated: the next chip's is a copy of ``wd``)
                wd, kd = self._apply(wd, g, kd)
                # the fold as the updater calls it: ONE arity, the count
                # as data, so no drain's size compiles inside the window
                zero = jax.device_put(jnp.zeros(d, jnp.float32), drv)
                wd, kd = self._apply_fold(
                    wd, (zero,) * self.cfg.num_workers,
                    jax.device_put(jnp.float32(2.0), drv), kd,
                )
            g = wd
        wd.block_until_ready()

    def _make_task(self, wid: int, w_pub, key, delay_model: DelayModel,
                   ut=None):
        # recovery view: a re-homed shard is transparently computed on its
        # new device; w and the PRNG chain follow the shard's home
        # (what the step is called with is read HERE, once a task: not in
        # ``dispatch``, on the executor's thread)
        operands = self._recovery.shard(wid).operands
        dev = operands[0].device
        step, calls = self._step, self._calls_in

        def dispatch(ut):
            w_local = on_device(w_pub, dev, ut, calls)
            key_local = on_device(key, dev, ut, calls)
            return enqueue_step(step, (*operands, w_local, key_local), ut,
                                calls)

        # (an injected delay sleeps in front of the dispatch: a straggler
        # takes no turn, or the workers behind it would wait for its sleep)
        delay_ms = delay_model.delay_ms(wid)
        late = delay_ms > 0
        return worker_task(dispatch, delay_ms, ut, worker=wid, chip=dev.id,
                           width=self._programs.widths[wid],
                           turns=None if late else self._turns.get(dev),
                           steps_out=self._steps_out.get(dev),
                           spread=self._spread.get(dev),
                           long_tail=late and delay_model.long_tail(wid))

    def _task_maker(self, run: EngineRun):
        """``make_tasks`` of this run (``EngineRun.drive``): a task captures
        its worker's key, read under the run's key lock."""
        worker_keys, key_lock = run.worker_keys, run.key_lock
        delay_model = run.delay_model

        def make_tasks(cohort, w_pub, uts):
            with key_lock:
                keys = {wid: worker_keys[wid] for wid in cohort}
            # _make_task is looked up per cohort: a test may replace it on
            # the instance
            return {
                wid: self._make_task(
                    wid, run.model_for(wid, w_pub), keys[wid], delay_model,
                    uts.get(wid)
                )
                for wid in cohort
            }

        return make_tasks
