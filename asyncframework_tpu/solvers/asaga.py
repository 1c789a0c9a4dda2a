"""ASAGA: asynchronous SAGA with a per-sample gradient-history table.

Parity targets: ``SparkASAGAThread.scala`` (async) / ``SparkASAGASync.scala``.
For least squares a per-sample gradient is ``scalar_i * x_i`` with
``scalar_i = x_i . w - y_i``, so the history compresses to one f32 per sample
(``ScalarMap``, ``SparkASAGAThread.scala:114``).

TPU re-design of the history table: the reference keeps a driver-side
``HashMap[Long, Double]`` and ships sampled entries to workers each round
(``sampledMap``, lines 280-294).  Here each worker's slice of the table is a
dense f32 array **resident in its device HBM** (8.1M samples == 32 MB total --
trivial), so the worker's history-corrected gradient needs *no* host traffic
at all: ``g = X^T (mask * (diff - alpha))`` reads the local slice.  Candidate
new scalars (``diff``) ride back as device handles; the updater *commits* them
into the worker's slice only for accepted (non-stale) results -- exactly the
reference's driver-controlled ScalarMap merge, as an on-device
``where(mask, diff, alpha)``.

Update rule on accept (``SparkASAGAThread.scala:210-213``):
``w -= gamma * (g/parRecs + alpha_bar)``; ``alpha_bar += g/N``.
Staleness filter quirk preserved: ASAGA accepts iff ``k - staleness <= taw``
(the ASGD driver tests ``staleness <= taw``) -- see the updater in
``SparkASAGAThread.scala:184``.
"""

from __future__ import annotations

import operator
import queue
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.engine.recovery import ShardRecovery
from asyncframework_tpu.engine.straggler import DelayModel
from asyncframework_tpu.ops import steps
from asyncframework_tpu.ops.gradients import (
    dense_step_path,
    make_sparse_grad_sum,
)
from asyncframework_tpu.solvers.base import (
    SolverConfig,
    TrainResult,
    check_hbm_plan,
    resolve_dataset,
    run_fused_plan,
)
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.solvers.engine_loop import EngineRun, EngineSolver
from asyncframework_tpu.solvers.instrumentation import on_device, worker_task


#: Every so-many-th accept of ``ASAGA.run`` pays the exact table delta
#: whether its slice stands or not.  On the chip the slice stands on 99.9%
#: of the accepts (PERF.md section 6, PR 28), so a profiler window of a few
#: seconds can hold no ``jit_saga_table_delta`` at all, and what reads that
#: module's device time would find nothing: the standing sample keeps the
#: exact path run, and timed, in every window of 256 updates, for 1/256 of
#: its cost (0.008 ms an update at mnist8m's shape).  On a slice that
#: stands both sides give the same vector.
EXACT_DELTA_EVERY = 256


class ASAGA(EngineSolver):
    def __init__(
        self,
        X,
        y: Optional[np.ndarray],
        config: SolverConfig,
        devices: Optional[list] = None,
    ):
        """``X`` may be a host array (sharded here) or a pre-built
        :class:`ShardedDataset` (e.g. generated on device), with ``y=None``."""
        if config.loss != "least_squares":
            raise ValueError(
                "ASAGA's scalar history compression requires least_squares "
                "(gradient = scalar * x); got " + config.loss
            )
        self.cfg = config
        self.devices = list(devices) if devices is not None else jax.devices()
        check_hbm_plan(X, config, self.devices, history_table=True)
        self.ds = resolve_dataset(X, y, config.num_workers, self.devices)
        self.driver_device = self.devices[0]
        self._sparse = bool(getattr(self.ds, "is_sparse", False))
        # the sparse programs read a shard at the dataset's live width
        self._live_width = live = (
            self.ds.checked_live_width() if self._sparse else None)
        if self._sparse:
            self._step = steps.make_sparse_saga_worker_step(
                config.batch_rate, self.ds.d, live_width=live
            )
            self._commit = steps.make_sparse_saga_commit()
            self._table_delta = steps.make_sparse_table_delta(self.ds.d)
            # X^T alpha over a whole padded-ELL shard (_history_drift)
            self._table_mean_grad = make_sparse_grad_sum(self.ds.d)
            self._eval = steps.make_sparse_trajectory_loss_eval(
                live_width=live
            )
        else:
            self._step = steps.make_saga_worker_step(config.batch_rate)
            self._table_delta = steps.make_saga_table_delta()
            self._eval = steps.make_trajectory_loss_eval("least_squares")
        self._task_rows = self._step.task_rows  # flop accounting
        # which program the step is here, for every result's extras: the
        # gather of a sparse step's model, on the largest shard; a dense
        # step's products (every shard has one width and dtype, so shard 0
        # speaks for all)
        if self._sparse:
            live = min(int(self.ds.shard(0).cols.shape[1]), live)
            self._path_extras = {
                "sparse_live_width": live,
                "sparse_gather_path": self._step.gather_path(
                    max(self.ds.partition_sizes().values()), live
                ),
            }
        else:
            self._path_extras = {
                "dense_step_path": dense_step_path(self.ds.shard(0).X)
            }
        self._apply = steps.make_saga_apply(
            config.gamma, config.batch_rate, self.ds.n, config.num_workers
        )
        # the accept path's apply where the step's g is the table delta
        # too: one buffer through two arguments may not be donated
        self._apply_g_is_delta = steps.make_saga_apply(
            config.gamma, config.batch_rate, self.ds.n, config.num_workers,
            donate_g=False,
        )
        self._recovery = ShardRecovery(self.ds, self.devices)

    #: a task returns ``(g, ...payload..., commits, new_key)``, its step's
    #: outputs around the commit count of the history slice the step read
    #: (``_make_task``): all but the key ride to the updater, as a tuple
    _result_payload = staticmethod(operator.itemgetter(slice(None, -1)))

    # ------------------------------------------------------------------ async
    def run(self) -> TrainResult:
        cfg = self.cfg
        run = EngineRun(self)
        ck = run.restore("asaga")
        ctx, inst, waiting = run.ctx, run.inst, run.waiting
        calibrator, delay_model, ckpt = run.calibrator, run.delay_model, run.ckpt
        state, state_lock, stop = run.state, run.state_lock, run.stop
        hot_lock = run.key_lock  # guards the alpha slots too
        if ck is not None:
            # resume: the running history mean and the full per-worker
            # history table come back with the run's common fields
            alpha_bar = jax.device_put(
                jnp.asarray(ck["alpha_bar"]), self.driver_device
            )
            alpha: Dict[int, jax.Array] = {
                wid: jax.device_put(jnp.asarray(a), self._shard_device(wid))
                for wid, a in ck["alpha"].items()
            }
        else:
            alpha_bar, alpha = self._zero_history()
        # how often each slot of ``alpha`` was assigned (under hot_lock):
        # a task carries its slice's count to the updater
        commits = dict.fromkeys(alpha, 0)
        # history_ns: the updater's time inside the history path's
        # dispatches (a part of updater_apply_s); reused / recomputed: the
        # accepts that took the step's g for the table delta, and those
        # that paid the second read of the shard
        state.update(ab=alpha_bar, history_ns=0, reused=0, recomputed=0)
        run.start_monitors(self._history_follows(run, alpha, commits))
        self._warm_hot_path()
        run.start_clock()
        snapshots, now_ms = run.snapshots, run.now_ms

        def history_fields(ab) -> Dict:
            with hot_lock:
                alpha_h = {wid: np.asarray(a) for wid, a in alpha.items()}
            return {"alpha_bar": np.asarray(ab), "alpha": alpha_h}

        def updater():
            clock = inst.updater_clock
            while not stop.is_set():
                with state_lock:
                    if state["k"] >= cfg.num_iterations:
                        break
                clock.waits()
                try:
                    res = ctx.collect_all(timeout=cfg.collect_timeout_s)
                except queue.Empty:
                    continue
                finally:
                    clock.works()
                # a sampled update (metrics/trace.py; () in an untraced
                # run): its result.queue and compute end here; merge.queue
                # is the state lock and the tau filter, merge.apply the
                # accept path's dispatches: merge.history (the history
                # commit, and the table delta where the slice moved),
                # cross-chip copies, apply
                uts = inst.on_drained((res,))
                g = res.data[0]
                task_ms = waiting.on_finish(res.worker_id, now_ms())
                do_save = False
                merge_queue = trace.span(trace.MERGE_QUEUE, uts).begin()
                with state_lock:
                    state["flops"] += self._task_flops(res.worker_id)
                    k = state["k"]
                    # the account of model-sized buffers: this result and
                    # those queued behind it (EngineRun.count_copies)
                    run.count_copies(1 + ctx.size())
                    # ASAGA acceptance quirk: k - staleness <= taw
                    accepted = k - res.staleness <= cfg.taw
                    merge_queue.end()
                    if uts:
                        uts = inst.apply_attrs(((res, accepted),))
                    t_apply = time.perf_counter_ns()
                    # The accept path stays INLINE in this frame: its
                    # temporaries (payload, delta, g) then live until the
                    # next result overwrites them.  In a helper they die on
                    # return, under the state lock, while the dispatches
                    # that read them are still in flight -- measured on the
                    # CPU rehearsal at a third of the update rate.
                    with trace.span(trace.MERGE_APPLY, uts,
                                    batch=int(accepted)):
                        if accepted:
                            shard = self._recovery.shard(res.worker_id)
                            t_hist = time.perf_counter_ns()
                            with trace.span(trace.MERGE_HISTORY,
                                            tuple(uts)), hot_lock:
                                wid = res.worker_id
                                alpha_cur = alpha[wid]
                                # a shard re-homed while this result was in
                                # flight leaves the payload on the old
                                # device; normalize onto the slice's
                                # current home
                                home = alpha_cur.device
                                payload = tuple(
                                    jax.device_put(a, home)
                                    if a.device != home else a
                                    for a in res.data[1:-1]
                                )
                                # No assignment to the slot since the task
                                # captured its slice: the step's g IS the
                                # table's change (make_saga_worker_step)
                                # and the shard is not read again.  Else
                                # the worker's last result was committed,
                                # or its shard re-homed (a payload moved
                                # above is always on this side), after the
                                # task was made: the exact delta against
                                # the slice at commit.  A resumed run
                                # starts like a cold one: the restored
                                # slices at count 0, nothing in flight.
                                # The standing sample is on this side too.
                                reuse = (
                                    res.data[-1] == commits[wid]
                                    and (k + 1) % EXACT_DELTA_EVERY != 0
                                )
                                if self._sparse:
                                    diff, idx, valid, c_sel, v_sel = payload
                                    if not reuse:
                                        delta = self._table_delta(
                                            c_sel, v_sel, diff, alpha_cur,
                                            idx,
                                        )
                                    alpha[wid] = self._commit(
                                        alpha_cur, diff, idx, valid
                                    )
                                else:
                                    diff, mask = payload
                                    if not reuse:
                                        delta = self._table_delta(
                                            shard.X, diff, mask, alpha_cur
                                        )
                                    alpha[wid] = steps.saga_commit_history(
                                        alpha_cur, diff, mask
                                    )
                                commits[wid] += 1
                            state["history_ns"] += (
                                time.perf_counter_ns() - t_hist
                            )
                            if g.device != self.driver_device:
                                g = jax.device_put(g, self.driver_device)
                            if reuse:
                                state["reused"] += 1
                                state["w"], state["ab"] = (
                                    self._apply_g_is_delta(
                                        state["w"], state["ab"], g, g
                                    )
                                )
                            else:
                                state["recomputed"] += 1
                                if delta.device != self.driver_device:
                                    delta = jax.device_put(
                                        delta, self.driver_device
                                    )
                                state["w"], state["ab"] = self._apply(
                                    state["w"], state["ab"], g, delta
                                )
                        else:
                            state["dropped"] += 1
                    inst.updater_apply_ns += time.perf_counter_ns() - t_apply
                    if accepted:
                        state["k"] = k + 1
                        state["accepted"] += 1
                        calibrator.record(k, task_ms)
                        if k % cfg.printer_freq == 0:
                            with trace.span(trace.SNAPSHOT):
                                snapshots.append((now_ms(), state["w"]))
                                inst.on_snapshot(state["accepted"])
                        do_save = ckpt.should_save(state["k"])
                        save_k, save_w, save_ab = (
                            state["k"], state["w"], state["ab"]
                        )
                # outside the lock, as ever: the event and the counters
                inst.on_gradient_merged(res, accepted, k, task_ms)
                if do_save:
                    with trace.span(trace.CHECKPOINT):
                        run.save(save_k, save_w, **history_fields(save_ab))
                if calibrator.maybe_finalize(state["k"]):
                    delay_model.calibrate(calibrator.avg_delay_ms)
            clock.waits()  # the loop's last busy stretch
            stop.set()

        run.drive(updater, "saga-updater",
                  self._task_maker(run, alpha, commits))
        return run.result(
            checkpoint=lambda: history_fields(state["ab"]),
            more_extras=lambda: {
                **self._history_extras(alpha, state["ab"]),
                "updater_history_s": state["history_ns"] * 1e-9,
                "history_reused": state["reused"],
                "history_recomputed": state["recomputed"],
            },
        )

    # ----------------------------------------------------------------- fused
    def run_fused(self) -> TrainResult:
        """Device-resident ASAGA (semantics in
        ``steps.make_fused_saga_rounds``, scope guards as in
        ``ASGD.run_fused`` plus the ASAGA taw quirk below).  Dense and
        padded-ELL sparse shards; the history slices live as scan carry,
        so the whole table stays in HBM across rounds.  As there, every
        shard is moved onto the first device: a one-device program."""
        cfg = self.cfg
        nw = cfg.num_workers
        if cfg.taw < cfg.num_iterations:
            # ASAGA's preserved acceptance quirk fires on the ITERATION
            # COUNT, not staleness: accept iff k - staleness <= taw
            # (SparkASAGAThread.scala:184; the updater at run()). A finite
            # taw therefore changes which of the k = 0..num_iterations-1
            # updates the engine accepts, and only taw >= num_iterations
            # guarantees the filter never fires -- unlike ASGD, whose
            # staleness-based filter is bounded by the wave (nw-1).
            raise ValueError(
                "fused ASAGA requires taw >= num_iterations (the ASAGA "
                "filter quirk `k - staleness <= taw` binds on iteration "
                "count); a tighter taw needs the engine's filter -- use "
                "run()"
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
            if parts[0].device != drv:
                parts = tuple(jax.device_put(a, drv) for a in parts)
            shards.append(parts)
        sparse_d = d if self._sparse else None
        total_rounds = max(1, -(-cfg.num_iterations // nw))

        def make_runner(length):
            rr = steps.make_fused_saga_rounds(
                cfg.gamma, cfg.batch_rate, self.ds.n, shards,
                rounds_per_call=length, sparse_d=sparse_d,
                live_width=self._live_width,
            )

            def run(carry):
                w, ab, alphas, keys = carry
                w, ab, alphas, keys, W_snap = rr(w, ab, alphas, keys)
                return (w, ab, alphas, keys), W_snap

            return run

        w = jax.device_put(jnp.zeros(d, jnp.float32), drv)
        ab = jax.device_put(jnp.zeros(d, jnp.float32), drv)
        alphas = tuple(
            jax.device_put(
                jnp.zeros(parts[-1].shape[0], jnp.float32), drv
            )
            for parts in shards
        )
        keys = jax.device_put(jnp.stack([
            jax.random.fold_in(jax.random.PRNGKey(cfg.seed), wid)
            for wid in range(nw)
        ]), drv)
        ((w, ab, alphas, keys), snapshots, start_wall,
         done_rounds) = run_fused_plan(
            make_runner, (w, ab, alphas, keys), total_rounds, nw,
            cfg.printer_freq, w_of=lambda c: c[0],
        )
        final_w = np.asarray(w)  # fence BEFORE elapsed
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
            max_staleness=nw - 1,
            avg_delay_ms=0.0,
            updates_per_sec=accepted / elapsed if elapsed > 0 else 0.0,
            total_flops=flops,
            extras={
                "fused": True,
                "rounds_per_call": min(16, total_rounds),
                "alpha_bar": np.asarray(ab),
                # final history slices (engine parity: run() exposes
                # extras["alpha"]), and what the invariant test checks
                "alpha": {
                    wid: np.asarray(a) for wid, a in enumerate(alphas)
                },
                **self._path_extras,
            },
        )

    # ------------------------------------------------------------------- sync
    def run_sync(self) -> TrainResult:
        """SparkASAGASync parity: drain all workers per round, merge all
        histories, apply one accumulated update with ``parRecs = b*N``."""
        cfg = self.cfg
        nw = cfg.num_workers
        run = EngineRun(self, sync=True)
        ctx, sched, inst = run.ctx, run.sched, run.inst
        waiting, calibrator = run.waiting, run.calibrator
        hot_lock = run.key_lock  # guards the alpha slots too
        sync_apply = steps.make_saga_apply(
            cfg.gamma, cfg.batch_rate, self.ds.n, 1,  # parRecs = b*N
            donate_g=False,  # the drain passes acc as both g and delta
        )
        run.cold_start()
        w = run.state["w"]
        alpha_bar, alpha = self._zero_history()
        # the tasks' commit counts (run()) have no reader here: a round's
        # tasks are all made before, and drained after, any of its commits
        commits = dict.fromkeys(alpha, 0)
        run.start_monitors(self._history_follows(run, alpha, commits))
        make_tasks = self._task_maker(run, alpha, commits)
        self._warm_hot_path(apply=sync_apply, sync=True)
        run.start_clock()
        snapshots, now_ms = run.snapshots, run.now_ms

        rounds = 0
        flops = 0.0
        run_ok = False
        # one driver thread submits and drains (see ASGD.run_sync)
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
                    g = res.data[0]
                    flops += self._task_flops(res.worker_id)
                    task_ms = waiting.on_finish(res.worker_id, now_ms())
                    calibrator.record(k, task_ms)
                    inst.on_gradient_merged(res, True, k, task_ms)
                    with hot_lock:
                        alpha_cur = alpha[res.worker_id]
                        # a shard re-homed mid-round leaves this result's
                        # payload on the old device; commit on the slice's
                        # current home.  The sync drain's commit needs only
                        # diff/idx/valid -- never transfer the (cap, K)
                        # c_sel/v_sel arrays it would just discard.
                        home = alpha_cur.device
                        needed = res.data[1:4 if self._sparse else 3]
                        payload = tuple(
                            jax.device_put(a, home) if a.device != home
                            else a
                            for a in needed
                        )
                        if self._sparse:
                            diff, idx, valid = payload
                            alpha[res.worker_id] = self._commit(
                                alpha_cur, diff, idx, valid
                            )
                        else:
                            diff, mask = payload
                            alpha[res.worker_id] = steps.saga_commit_history(
                                alpha_cur, diff, mask
                            )
                    if g.device != self.driver_device:
                        g = jax.device_put(g, self.driver_device)
                    acc = g if acc is None else steps.add_grads(acc, g)
                # sync drain has no dispatch overlap: table delta == g
                with trace.span(trace.MERGE_APPLY,
                                inst.apply_attrs(drained) if uts else None,
                                batch=nw):
                    w, alpha_bar = sync_apply(w, alpha_bar, acc, acc)
                rounds += 1
                if k % cfg.printer_freq == 0:
                    with trace.span(trace.SNAPSHOT):
                        snapshots.append((now_ms(), w))
                        inst.on_snapshot(rounds * nw)
                if calibrator.maybe_finalize(k):
                    run.delay_model.calibrate(calibrator.avg_delay_ms)
            run_ok = True
        finally:
            clock.waits()  # the loop's last busy stretch
            run.shutdown(run_ok)
        run.state.update(w=w, accepted=rounds * nw, rounds=rounds, flops=flops)
        return run.result(
            more_extras=lambda: self._history_extras(alpha, alpha_bar)
        )

    # ---------------------------------------------------------------- helpers
    def _zero_history(self):
        """``(alpha_bar, alpha)`` of a cold start: the mean history gradient
        on the driver's device, and the table, one slice per worker,
        resident in its HBM."""
        alpha_bar = jax.device_put(
            jnp.zeros(self.ds.d, jnp.float32), self.driver_device
        )
        alpha = {
            wid: jax.device_put(
                jnp.zeros(self.ds.shard(wid).size, jnp.float32),
                self._shard_device(wid),
            )
            for wid in range(self.cfg.num_workers)
        }
        return alpha_bar, alpha

    def _history_extras(self, alpha: Dict[int, jax.Array], alpha_bar) -> Dict:
        """The final history state, as ``run()`` and ``run_sync()`` expose
        it in ``extras`` (call after the run's clock has stopped)."""
        return {
            "alpha": {wid: np.asarray(a) for wid, a in alpha.items()},
            "alpha_bar": np.asarray(alpha_bar),
            "history_drift": self._history_drift(alpha, alpha_bar),
        }

    def _history_follows(self, run: EngineRun, alpha: Dict[int, jax.Array],
                         commits: Dict[int, int]):
        """The run's hook for a re-homed shard: its history slice and PRNG
        chain follow it to the new device.  The slot is assigned, so its
        count moves on: a result in flight takes the exact table delta."""
        hot_lock, worker_keys = run.key_lock, run.worker_keys

        def on_shard_moved(shard_id, moved):
            with hot_lock:
                alpha[shard_id] = jax.device_put(alpha[shard_id], moved.device)
                commits[shard_id] += 1
                worker_keys[shard_id] = jax.device_put(
                    worker_keys[shard_id], moved.device
                )

        return on_shard_moved

    def _task_maker(self, run: EngineRun, alpha: Dict[int, jax.Array],
                    commits: Dict[int, int]):
        """``make_tasks`` of this run (``EngineRun.drive``): a task captures
        its worker's key, history slice and the slice's commit count, read
        under one hold of the lock that guards all three."""
        hot_lock, worker_keys = run.key_lock, run.worker_keys
        delay_model = run.delay_model

        def make_tasks(cohort, w_pub, uts):
            with hot_lock:
                captured = {
                    wid: (worker_keys[wid], alpha[wid], commits[wid])
                    for wid in cohort
                }
            # _make_task is looked up per cohort: a test may replace it on
            # the instance
            return {
                wid: self._make_task(
                    wid, w_pub, *captured[wid], delay_model, uts.get(wid),
                )
                for wid in cohort
            }

        return make_tasks

    def _history_drift(self, alpha: Dict[int, jax.Array], alpha_bar) -> float:
        """``max |alpha_bar - sum_i alpha_i x_i / n|`` over ``max |sum_i y_i
        x_i / n|``: how far the running mean history gradient is from the
        table it summarises, in units of the mean gradient at ``w = 0``.
        The accept path keeps it at f32 rounding (1e-7 to 1e-6): its delta
        is the step's ``g`` where the slice the step read still stands and
        the exact table delta where it does not.  A delta that rounds its
        vector reads 1e-4 and more; the reference's ``delta == g`` taken
        on EVERY accept grows with every overlapped dispatch.  The scale is
        the data's, not ``max |alpha_bar|``: ``alpha_bar`` is a mean
        gradient and goes to zero as the run converges, while what rounding
        left in it early stays, so that ratio climbs to 1e-3 by itself.
        Two passes over every shard on its device (dense: the table
        delta's own executable, with the whole slice, then the labels, as
        ``diff``: nothing new is compiled), summed on the host in float64.
        Call after the run's clock has stopped."""
        mean = np.zeros(self.ds.d, np.float64)
        scale = np.zeros(self.ds.d, np.float64)
        for wid, a in alpha.items():
            shard = self._recovery.shard(wid)
            if self._sparse:
                parts = [self._table_mean_grad(shard.cols, shard.vals, v)
                         for v in (a, shard.y)]
            else:
                one, zero = jnp.ones_like(a), jnp.zeros_like(a)
                parts = [self._table_delta(shard.X, v, one, zero)
                         for v in (a, shard.y)]
            mean += np.asarray(parts[0], np.float64)
            scale += np.asarray(parts[1], np.float64)
        unit = float(np.max(np.abs(scale)))
        if unit == 0.0:
            return 0.0
        ab = np.asarray(alpha_bar, np.float64)
        return float(np.max(np.abs(ab * self.ds.n - mean))) / unit

    def _warm_hot_path(self, apply=None, sync: bool = False) -> None:
        """Compile this mode's hot-path executables before the trajectory
        clock starts (reference parity: the always-blocking first iteration,
        ``DAGScheduler.scala:641-656`` -- without this the first accepted
        gradient pays ~1 s of XLA compile inside the timed region on a real
        chip).

        jit caches per input SHAPE, so every distinct (shard shape, history
        slice size) pair is warmed -- shards differ by one row/sample when
        ``n % num_workers != 0``.  The async accept path uses the table
        delta and both instances of the apply (an accept takes either
        side); the sync drain instead accumulates with ``add_grads`` and
        passes ``acc`` as both g and delta -- each mode warms only what it
        runs.  Dummies are fresh buffers, so donated arguments never touch
        live state."""
        apply = apply if apply is not None else self._apply
        d = self.ds.d
        drv = self.driver_device
        g = delta = None
        seen = set()
        for wid in range(self.cfg.num_workers):
            shard = self._recovery.shard(wid)
            dev = shard.device
            # key on (shape, size, device): jit executables are cached per
            # device commitment, so equal-shaped shards on different chips
            # each need their own warm compile
            shape_key = (
                (shard.cols.shape if self._sparse else shard.X.shape),
                shard.size,
                dev,
            )
            if shape_key in seen:
                continue
            seen.add(shape_key)
            w0 = jax.device_put(jnp.zeros(d, jnp.float32), dev)
            a0 = jax.device_put(jnp.zeros(shard.size, jnp.float32), dev)
            key = jax.device_put(jax.random.PRNGKey(0), dev)
            if self._sparse:
                g, diff, idx, valid, c_sel, v_sel, _ = self._step(
                    shard.cols, shard.vals, shard.y, w0, a0, key
                )
                if not sync:
                    delta = self._table_delta(c_sel, v_sel, diff, a0, idx)
                self._commit(a0, diff, idx, valid)
            else:
                g, diff, mask, _ = self._step(shard.X, shard.y, w0, a0, key)
                if not sync:
                    delta = self._table_delta(shard.X, diff, mask, a0)
                steps.saga_commit_history(a0, diff, mask)
        if g.device != drv:
            g = jax.device_put(g, drv)
        wd = jax.device_put(jnp.zeros(d, jnp.float32), drv)
        ab = jax.device_put(jnp.zeros(d, jnp.float32), drv)
        if sync:
            acc = jax.device_put(jnp.zeros(d, jnp.float32), drv)
            acc = steps.add_grads(acc, g)
            wd, ab = apply(wd, ab, acc, acc)
        else:
            if delta.device != drv:
                delta = jax.device_put(delta, drv)
            # both instances of the accept path; the donating one last
            wd, ab = self._apply_g_is_delta(wd, ab, g, g)
            wd, ab = apply(wd, ab, g, delta)
        wd.block_until_ready()

    def _make_task(self, wid, w_pub, key, alpha_slice, slice_commits: int,
                   delay_model: DelayModel, ut=None):
        """A worker task: the step against ``alpha_slice``, whose commit
        count rides the task's own return to the updater (between the
        step's payload and the key; ``_result_payload``), which learns
        from it whether the slice still stands when the result is
        accepted."""
        shard = self._recovery.shard(wid)  # follows re-homed shards
        dev = shard.device
        step = self._step
        sparse = self._sparse

        def dispatch(ut):
            # a slice/key captured around a concurrent shard re-home may
            # still live on the old device; normalize onto the shard's home
            w_local = on_device(w_pub, dev, ut)
            a_local = on_device(alpha_slice, dev, ut)
            key_local = on_device(key, dev, ut)
            # (g, ...payload..., new_key) -- the payload arity differs
            # between the dense (diff, mask) and compacted sparse
            # (diff_sel, idx, valid, c_sel, v_sel) steps
            with trace.span(trace.TASK_ENQUEUE, ut):
                if sparse:
                    out = step(shard.cols, shard.vals, shard.y, w_local,
                               a_local, key_local)
                else:
                    out = step(shard.X, shard.y, w_local, a_local, key_local)
            return (*out[:-1], slice_commits, out[-1])

        return worker_task(dispatch, delay_model.delay_ms(wid), ut,
                           worker=wid, chip=dev.id,
                           steps_out=self._steps_out.get(dev))
