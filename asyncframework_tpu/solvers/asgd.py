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

Both schedules are the engine's (``solvers/engine_loop.py``): ``run`` hands
``EngineRun.updater`` the tau filter's predicate and what a segment of a
drain dispatches (a chip), ``run_sync`` hands ``EngineRun.drive_sync`` a
round's apply.
"""

from __future__ import annotations

import operator
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
from asyncframework_tpu.solvers.engine_loop import (
    EngineRun,
    EngineSolver,
    ModelReplicas,
)
from asyncframework_tpu.solvers.instrumentation import enqueue_step, on_device


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
        state, d, nw = run.state, self.ds.d, cfg.num_workers
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

        # over several chips a result is a buffer a chip, and a segment's
        # rows are kept until the next one's dispatches drop them: dying
        # with the frame's ``live`` under ``state_lock`` (``EngineRun.
        # updater``, 1) they cost the four-chip cell 1.1% of its rate and
        # the submitter 1.8 s of 20 at that lock (PERF.md section 6, PR 61)
        rows = None

        def dispatch(live, at_k, alone):
            # ONE dispatch a segment (a chip): the serial path's program
            # for one result, the fold for several
            nonlocal rows
            n = len(live)
            w, k_dev = state["w"], state["k_dev"]
            with calls_in:
                if chips is not None:
                    # the same dispatch a chip, each on its own buffer of
                    # every operand (a result's buffers by chip: the
                    # task's thread sent them, ``worker_task``)
                    rows = [res.data for res in live]
                    ws = list(w)
                    for c, (pad, count_of) in enumerate(folds):
                        if n == 1:
                            ws[c], k_dev[c] = self._apply(
                                ws[c], rows[0][c], k_dev[c])
                        else:
                            ws[c], k_dev[c] = self._apply_fold(
                                ws[c], tuple(r[c] for r in rows) + pad[n:],
                                count_of[n], k_dev[c],
                            )
                    w = ModelReplicas(ws)
                elif n == 1:
                    w, k_dev = self._apply(w, live[0].data, k_dev)
                else:
                    w, k_dev = self._apply_fold(
                        w, tuple(res.data for res in live) + zeros[n:],
                        counts[n], k_dev,
                    )
            return {"w": w, "k_dev": k_dev}

        taw = cfg.taw
        run.drive(
            run.updater(lambda res, at_k: res.staleness <= taw, dispatch),
            "ps-updater", self._task_maker(run))
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
        traj = self._evaluate_trajectory([*snapshots, (elapsed * 1e3, w)])
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
        run = EngineRun(self, sync=True)
        run.cold_start()
        k_dev = jax.device_put(jnp.float32(0.0), self.driver_device)
        run.start_monitors()
        make_tasks = self._task_maker(run)
        self._warm_hot_path(sync=True)
        run.start_clock()

        def apply_round(w, acc):
            nonlocal k_dev
            w, k_dev = self._sync_apply(w, acc, k_dev)
            return w

        run.drive_sync(make_tasks, operator.attrgetter("data"), apply_round)
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

        return self._worker_task(dispatch, wid, dev, delay_model, ut)
