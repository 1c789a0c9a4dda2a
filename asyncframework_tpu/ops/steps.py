"""Fused on-device step functions for the async parameter server.

The profiling reality of TPU hot paths (and the design rule that follows):
compute dispatch costs microseconds, but any *blocking* host<->device transfer
costs the interconnect round-trip.  So the whole per-update cycle --
mask sampling, gradient, tau-accepted model update, SAGA history commit --
stays on device; the host threads shuttle only opaque array *handles* and
integer metadata.  JAX array immutability gives model/history versioning for
free: every update produces a new handle, and an old handle IS an old version
(the ``ASYNCbroadcast`` stale-read capability with zero copies).

Parity notes per builder:
- ``make_asgd_worker_step``: the per-round sample+gradient task
  (``SparkASGDThread.scala:311-318``): Bernoulli(b) mask + summed
  least-squares gradient.  The PRNG key is a device-resident chain split
  inside the step (no per-call host->device seed transfer).
- ``make_asgd_apply``: the updater's accept path
  (``SparkASGDThread.scala:185-189``): ``w -= gamma/sqrt(k/numPart+1) *
  g/(b*N/numPart)`` with the iteration counter ``k`` ALSO device-resident.
- ``make_sync_apply``: the sync drain's update (``SparkASGDSync.scala:267-272``):
  ``w -= gamma/sqrt(k+1) * accGrad/(b*N)``.
- ``make_saga_worker_step`` / ``make_saga_apply`` / ``saga_commit_history``:
  the ASAGA decomposition (``SparkASAGAThread.scala:199-213,369-380``) with
  the per-sample scalar history table resident in HBM, sharded by worker.
- Every worker step and apply names its phases for the device trace with
  ``jax.named_scope`` (``sample``, ``residual``, ``grad``; the sparse
  steps also ``compact`` and ``gather``; ``apply``): an HLO op's
  ``op_name`` then says which phase it belongs to when a trace is opened
  in XProf or Perfetto.  Where the dense step is a one-pass kernel
  (``gradients.dense_step_path``) ``residual`` and ``grad`` are one
  custom call, named ``dense_onepass`` or ``dense_onepass_tiles`` under
  ``grad``.  Metadata only:
  the compiled program and its compile-cache key are unchanged, and the
  jitted functions keep their Python names (the benchmark matches
  ``jit_step``).
- ``make_trajectory_loss_eval``: the drivers' final one-pass objective
  evaluation over all snapshots (``SparkASGDThread.scala:386-401``) -- all
  snapshots stacked into one (S, d) matrix so a shard's whole trajectory
  costs a single matmul.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from asyncframework_tpu.metrics import profiler as _prof
from asyncframework_tpu.utils import flops as _flops
from asyncframework_tpu.ops.gradients import (
    clamped_block,
    dense_masked_grad,
    dense_step_path,
    dense_tiles_share,
    least_squares_grad_sum,
    logistic_grad_sum,
    make_sparse_grad_sum,
    mm_f32,
    row_blocks,
    saga_commit_history,  # re-exported: the solvers' committed-history op
    sample_walk,
    sparse_gather_path,
    sparse_margins,
    sparse_scatter_path,
    sparse_sorted_pairs,
    walk_accumulator_resident,
    walk_tile,
)
from asyncframework_tpu.ops.program_store import LoadedByShape


# ---------------------------------------------------------------- builders
def make_pipelined_transfer(device) -> Tuple[Callable, Callable]:
    """``(stage, readback)`` -- the two host<->device overlap points of
    the pipelined DCN worker loop (``parallel/ps_dcn.py``,
    ``async.pipeline.depth`` >= 1).

    ``device`` may be a single ``jax.Device`` or any ``Sharding`` --
    the mesh worker path passes ``replicated_sharding(mesh)`` so the
    staged put replicates the pulled model over every mesh device (P
    transfer-engine copies behind the same double buffer).

    ``stage(w_host)`` puts the NEXT model version on the device.  It is
    called on the prefetch thread the moment the pull reply decodes, and
    ``jax.device_put`` dispatches asynchronously -- so the host->device
    copy of model v(k+1) rides the transfer engine while step k's compute
    is still running (double buffering: two model versions briefly live
    on device; the old one is dropped when the loop advances).

    ``readback(g)`` completes a gradient's device->host copy (blocking
    ``np.asarray``).  In the pipelined loop the push that follows it is
    a bare windowed send -- the ACK wait that serialized the serial
    loop's readback -> push -> pull chain is a separate reaper thread's
    problem.
    """

    def stage(w_host: np.ndarray):
        return jax.device_put(w_host, device)

    def readback(g) -> np.ndarray:
        return np.asarray(g)

    return stage, readback


def _grad_sum_for(loss: str):
    """The masked gradient sum ``(X, y, w, mask) -> g`` of a dense loss."""
    if loss == "least_squares":
        return least_squares_grad_sum
    if loss == "logistic":
        return logistic_grad_sum
    raise ValueError(f"unknown loss {loss!r}")


def _counts_rows(step, task_rows):
    """Attach ``step.task_rows(n_rows)``: how many rows of an ``n_rows``
    shard the step's products run over.  The solvers' flop accounting
    (``solvers/base.py``) asks the step it runs, so the count cannot drift
    from what the builder decided."""
    step.task_rows = task_rows
    return step


def _dense_sampled_gradient(X, y, w, key, batch_rate, grad_sum):
    """``(g_sum, new_key)``: the dense worker computation -- advance the
    key chain, draw the Bernoulli(b) mask over the shard's rows, sum the
    masked per-sample gradients over the WHOLE shard.  ONE definition,
    used by the engine worker step AND the fused rounds -- the fused
    path's sampling-parity claim depends on these staying bit-identical
    (same discipline as :func:`_sparse_compacted_gradient`)."""
    with jax.named_scope("sample"):
        key, sub = jax.random.split(key)
        mask = jax.random.bernoulli(
            sub, batch_rate, (X.shape[0],)
        ).astype(jnp.float32)
    return grad_sum(X, y, w, mask, batch_rate=batch_rate), key


def make_asgd_worker_step(batch_rate: float, loss: str = "least_squares"):
    """jit (X, y, w, key) -> (g_sum, new_key); mask drawn on device.

    The gradient is the reference's sampled sum exactly, over the whole
    shard WHERE IT LIES: ``r = X w - y`` then ``X^T (mask * r)``
    (``gradients.dense_masked_grad``).  The TPU stores a dense ``(n, d)``
    shard whose ``d`` is no multiple of the 128-lane tile (784, 2000: every
    recipe this repo has) column-major, rows minor, so that no row is
    padded (PERF.md section 3, "how the shard is stored").  Reading a
    sampled tenth of the rows is therefore not a tenth of the traffic: a
    row gather first relays the whole shard, and packing the sampled row
    ids costs a serial scatter on top (the compacted dense step took 16.6
    ms on a 1.0M x 784 bf16 shard, PERF.md section 6, PR 24).  With this
    storage the floor of any step is ONE read of the shard, and on the TPU
    that is what this step does: the one-pass Pallas kernel
    (``pallas_kernels.dense_onepass``) computes both products from each
    block of ``X.T`` in VMEM, 2.14 ms at 740 GB/s where XLA's two fusions
    took 4.24 ms at 755 GB/s each (v5e, PERF.md section 6, PR 26).  Two
    reads remain where ``gradients.dense_step_path`` says
    ``"two_products"``: off the TPU, and at lane-aligned widths, where the
    shard is stored row-major and ``X.T`` would be a real transpose.  What
    CAN be left unread is a lane tile of 128 rows none of which was drawn:
    the step hands ``batch_rate`` to the chooser, which at a thin draw
    (0.01: 27.6% of the tiles) picks the kernel over the list of the
    others, and at this recipe's 0.1 (one tile in a million) does not.  The
    sparse (padded-ELL) step does compact: its gather saves real traffic.
    """
    grad_sum = _grad_sum_for(loss)

    @jax.jit
    def step(X, y, w, key):
        return _dense_sampled_gradient(X, y, w, key, batch_rate, grad_sum)

    return _counts_rows(
        _prof.wrap_dispatch(step, "kernel.dispatch", "asgd_worker_step"),
        lambda n_rows: n_rows,
    )


def make_asgd_apply(gamma: float, batch_rate: float, n: int, num_workers: int):
    """jit (w, g, k) -> (w', k+1).  ``k`` is a device f32 scalar.

    Buffer donation: ``g`` and ``k`` are donated -- XLA writes ``w'`` into the
    dead gradient's buffer, so the accept path allocates nothing at steady
    state.  ``w`` itself is NOT donated: an old ``w`` handle IS an old model
    version (in-flight workers and trajectory snapshots hold them), and
    donating it would invalidate every retained version.
    """
    par_recs = batch_rate * n / num_workers

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def apply(w, g, k):
        with jax.named_scope("apply"):
            lr = gamma / jnp.sqrt(k / num_workers + 1.0)
            return w - (lr / par_recs) * g, k + 1.0

    return _prof.wrap_dispatch(apply, "kernel.dispatch", "asgd_apply")


def make_sync_apply(gamma: float, batch_rate: float, n: int):
    """jit (w, acc_g, k) -> (w', k+1) -- full-drain synchronous update.

    ``acc_g`` and ``k`` are donated (dead after the round); ``w`` is kept
    alive for snapshots -- see :func:`make_asgd_apply`.
    """

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def apply(w, acc_g, k):
        with jax.named_scope("apply"):
            lr = gamma / jnp.sqrt(k + 1.0)
            return w - (lr / (batch_rate * n)) * acc_g, k + 1.0

    return _prof.wrap_dispatch(apply, "kernel.dispatch", "sync_apply")


def make_saga_worker_step(batch_rate: float):
    """jit (X, y, w, alpha, key) -> (g, diff, mask, new_key).

    ``g = X^T (mask * (diff - alpha))`` is the history-corrected gradient sum;
    ``diff`` are candidate new history scalars (committed only on accept).
    ``g`` keeps its vector in f32 and promotes the shard, exactly as
    :func:`make_saga_table_delta` does and for its reason: while the slice
    is unchanged between dispatch and accept, ``g`` IS the table's change,
    on every backend and for every storage dtype, which is what lets the
    sync drain, ``run_fused`` and the DCN plane take ``delta == g``, and
    the async engine on every accept that finds the slice as the step
    read it.

    The byte model is :func:`make_asgd_worker_step`'s: ONE read of the
    shard on the TPU (the one-pass kernel takes ``alpha`` in and writes
    ``diff`` out beside ``g``: 12 bytes a row on top of the shard), two
    where ``gradients.dense_step_path`` says so, and at the recipe's own
    ``batch_rate`` of 0.01 LESS than one: the kernel over the list of the
    lane tiles that hold a sampled row reads 72% of the shard (``diff``
    is then 0 at the rows of the other tiles: the commit selects by
    ``mask`` and the delta weighs by it).  An accepted update pays
    a second read on the updater's side only where the slice moved on
    while the step was in flight (``ASAGA.run`` counts both kinds): the
    table delta is a product against the history AT COMMIT, which no
    worker step can know.
    """

    @jax.jit
    def step(X, y, w, alpha, key):
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(
                sub, batch_rate, (X.shape[0],)
            ).astype(jnp.float32)
        g, diff = dense_masked_grad(
            X, y, w, mask, alpha=alpha, batch_rate=batch_rate)
        return g, diff, mask, key

    return _counts_rows(
        _prof.wrap_dispatch(step, "kernel.dispatch", "saga_worker_step"),
        lambda n_rows: n_rows,
    )


def make_saga_apply(
    gamma: float,
    batch_rate: float,
    n: int,
    num_workers: int,
    donate_g: bool = True,
):
    """jit (w, alpha_bar, g, delta) -> (w', alpha_bar').

    ``w' = w - gamma*g/parRecs - gamma*alpha_bar``;
    ``alpha_bar' = alpha_bar + delta/N`` (``SparkASAGAThread.scala:210-213``
    uses ``delta == g``; see :func:`make_saga_table_delta` for why the TPU
    build distinguishes them).

    Donation: ``alpha_bar`` is always donated (its old value is never
    retained).  ``g`` is donated only when ``donate_g`` -- a caller that
    passes the SAME buffer as both ``g`` and ``delta`` sets
    ``donate_g=False``, since a buffer may not be donated while also read
    through another argument: the sync drain with its accumulator, and
    the async engine on an accept whose step read the history slice that
    still stands (``g`` IS the delta there).  The async engine calls the
    donating instance on the other accepts, with the delta it recomputed.
    ``w`` is never donated (old handles are live model versions).
    """
    par_recs = batch_rate * n / num_workers
    donate = (1, 2) if donate_g else (1,)

    @functools.partial(jax.jit, donate_argnums=donate)
    def apply(w, alpha_bar, g, delta):
        with jax.named_scope("apply"):
            w2 = w - (gamma / par_recs) * g - gamma * alpha_bar
            ab2 = alpha_bar + delta / n
            return w2, ab2

    return _prof.wrap_dispatch(apply, "kernel.dispatch", "saga_apply")


def make_saga_table_delta():
    """jit (X, diff, mask, alpha_cur) -> X^T (mask * (diff - alpha_cur)).

    The exact change the commit makes to the mean history gradient.  The
    reference advances ``alphaBar`` by the *worker-computed* ``g``, which was
    evaluated against the history at *dispatch* time; with asynchronous overlap
    the same worker's earlier result may commit in between, so the reference's
    ``alphaBar`` drifts from the true table mean (benign there: 6 s task
    latencies make overlapped same-worker dispatch rare; on a TPU with fast
    overlapped rounds the drift diverges constant-step ASAGA in ~500 updates).
    Recomputing the delta against the *current* table slice at commit time
    keeps the ``alpha_bar == mean(table)`` invariant exact, at the cost of
    one extra matvec on every accepted update whose slice was replaced
    between its dispatch and its accept; on the others the engine takes
    the worker's ``g``, which is this product already (``ASAGA.run``).

    The vector stays f32 and the SHARD is promoted (``X.T @ v``, not
    ``mm_f32``, which would round ``v`` to a bf16 shard's dtype): the
    commit writes the f32 ``diff`` into the table, so only a delta of f32
    terms keeps ``alpha_bar`` the mean of what the table holds; rounding
    ``mask * (diff - alpha_cur)`` to bf16 would put a relative 2^-9 error a
    row into ``alpha_bar`` on every accept, which never leaves it.  That is
    the guarantee, not a speed-up to take.  The promotion costs no copy:
    the TPU compiler fuses the ``convert`` into the reduce that reads the
    shard where it lies (``{0,1}``, PERF.md section 3), 0 bytes of
    temporaries at ``bf16[1012500,784]``; ``tests/test_step_layout.py``
    checks the compiled program.  The worker's ``g``
    (:func:`make_saga_worker_step`) is the same product over the history at
    dispatch time.

    The jitted function is named for a device trace: its XLA module is
    ``jit_saga_table_delta`` (the benchmark's ``history_device_ms``).
    """

    @jax.jit
    def saga_table_delta(X, diff, mask, alpha_cur):
        with jax.named_scope("history.delta"):
            return X.T @ (mask * (diff - alpha_cur))

    return saga_table_delta


def make_asgd_apply_fold(
    gamma: float, batch_rate: float, n: int, num_workers: int
):
    """jit (w, gs, m, k) -> (w', k + m) -- the first ``m`` of a drain's
    gradient handles applied in ONE dispatch.

    ``gs`` is a tuple of FIXED length (the engine's updater pads a short
    drain with one cached zero handle to ``num_workers``), and how many of
    its slots count is data (``m``, a device f32 scalar like ``k``), so
    there is one executable whatever the drain's size.  Exactness: the serial accept path is ``w <- w - c_j
    g_j`` with step sizes ``c_j = (gamma / sqrt(k_j/P + 1)) / parRecs``
    that do not depend on ``w``, so a drain folds into one chain of the
    same subtractions in the same order, ``k_j`` advancing over the live
    slots; a slot past ``m`` subtracts ``0 * g``.  The reference drains
    its whole queue per updater wake for the same reason
    (``SparkASGDThread.scala:154-158``); here the drain is also one device
    op.  ``w`` is never donated (an old handle is a model version, see
    :func:`make_asgd_apply`) and neither are the gradients: the padding
    repeats one buffer, and a buffer may not be donated twice.
    """
    par_recs = batch_rate * n / num_workers

    @functools.partial(jax.jit, donate_argnums=(3,))
    def apply_fold(w, gs, m, k):
        with jax.named_scope("apply"):
            j = jnp.arange(len(gs), dtype=jnp.float32)
            lr = gamma / jnp.sqrt((k + j) / num_workers + 1.0)
            coeff = jnp.where(j < m, lr / par_recs, 0.0)
            for c, g in zip(coeff, gs):
                w = w - c * g
            return w, k + m

    return _prof.wrap_dispatch(apply_fold, "kernel.dispatch", "asgd_apply_fold")


def make_asgd_apply_merge(
    gamma: float, batch_rate: float, n: int, num_workers: int,
    donate_model: bool = False,
):
    """jit (w, G (m, d), mask (m,), k) -> (w', k') -- ``m`` coalesced PUSH
    gradients applied in ONE device dispatch, **bit-identical** to running
    :func:`make_asgd_apply` serially over the masked slots.

    Unlike :func:`make_asgd_apply_fold` (the in-process updater's fold
    over a tuple of handles, held to the serial path within a tolerance),
    this folds the slots of a stacked ``G`` through a ``lax.scan`` whose
    body is the serial apply expression verbatim -- same per-element operation sequence, so the DCN merge
    queue's fused apply can be asserted equal to the serial path bit for
    bit.  One compile per (m, d) shape; the PS pads short batches to its
    merge bound so only one shape ever exists.

    ``donate_model=True`` additionally donates ``w``: XLA writes ``w'``
    into the dead input's buffer, so a steady-state drain allocates
    NOTHING (donation changes aliasing only, never values -- asserted
    bit-identical to the undonated kernel in tests/test_meshgrad.py).
    The caller owns the lifetime discipline: every retained copy of the
    model (snapshot stack, checkpoint capture, published pull snapshots)
    must be a HOST copy taken before the next donated apply, because the
    old device handle dies at dispatch -- see ``ParameterServer``'s
    drain, which only routes a drain through the donated kernel when the
    outgoing version is already host-published.

    Delay-adaptive damping (``parallel/controller.py``): a mask slot is
    the per-item step-DAMP factor, not just a keep bit -- 0 skips the
    slot exactly as before, 1.0 is the undamped apply (``1.0 * x`` is
    exact in f32, so the legacy path stays bit-identical), and a
    controller-damped push carries its bounded ``1/(1+tau)``-family
    factor here, scaling that item's effective step with no change to
    the clock/accept semantics (``k`` still advances by 1 per kept
    slot).  :func:`make_asgd_apply_damped` is the serial twin with the
    SAME expression, so the fused and serial paths agree bit for bit at
    every damp value.
    """
    par_recs = batch_rate * n / num_workers

    @functools.partial(
        jax.jit, donate_argnums=(0, 3) if donate_model else (3,)
    )
    def apply_merge(w, G, mask, k):
        def body(carry, xs):
            w, k = carry
            g, a = xs
            lr = gamma / jnp.sqrt(k / num_workers + 1.0)
            w2 = w - (a * (lr / par_recs)) * g
            keep = a > 0
            return (jnp.where(keep, w2, w), jnp.where(keep, k + 1.0, k)), None

        with jax.named_scope("apply"):
            (w, k), _ = jax.lax.scan(body, (w, k), (G, mask))
        return w, k

    return _prof.wrap_dispatch(apply_merge, "kernel.dispatch", "asgd_apply_merge")


def make_asgd_apply_damped(gamma: float, batch_rate: float, n: int,
                           num_workers: int):
    """jit (w, g, k, a) -> (w', k+1): :func:`make_asgd_apply` with a
    per-call step-DAMP scalar ``a`` (delay-adaptive step sizes per
    arXiv:1601.04033, actuated by ``parallel/controller.py``).

    The expression is VERBATIM the damped merge-kernel body
    (``w - (a * (lr/par_recs)) * g``), so the serial one-dispatch path
    and the fused drain produce bit-identical models at every damp
    value -- and at ``a == 1.0`` bit-identical to the undamped
    :func:`make_asgd_apply` (multiplication by 1.0 is exact in f32).
    Same donation discipline: ``g`` and ``k`` die here, ``w`` is a live
    model version.
    """
    par_recs = batch_rate * n / num_workers

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def apply(w, g, k, a):
        with jax.named_scope("apply"):
            lr = gamma / jnp.sqrt(k / num_workers + 1.0)
            return w - (a * (lr / par_recs)) * g, k + 1.0

    return _prof.wrap_dispatch(apply, "kernel.dispatch", "asgd_apply_damped")


def make_saga_apply_merge(
    gamma: float, batch_rate: float, n: int, num_workers: int,
    donate_model: bool = False,
):
    """jit (w, alpha_bar, G (m, d), mask (m,)) -> (w', alpha_bar') -- the
    ASAGA face of the merge-queue fused apply (``delta == g`` over DCN,
    see ``ParameterServer.__init__``), scanning the serial
    :func:`make_saga_apply` expression over the masked slots so the fused
    result is bit-identical to the one-dispatch-per-push path.

    ``donate_model=True`` donates ``w`` alongside the always-donated
    ``alpha_bar`` -- same zero-allocation drain and same caller-side
    lifetime discipline as :func:`make_asgd_apply_merge`.
    """
    par_recs = batch_rate * n / num_workers

    @functools.partial(
        jax.jit, donate_argnums=(0, 1) if donate_model else (1,)
    )
    def apply_merge(w, alpha_bar, G, mask):
        def body(carry, xs):
            w, ab = carry
            g, a = xs
            w2 = w - (gamma / par_recs) * g - gamma * ab
            ab2 = ab + g / n
            keep = a > 0
            return (jnp.where(keep, w2, w), jnp.where(keep, ab2, ab)), None

        with jax.named_scope("apply"):
            (w, alpha_bar), _ = jax.lax.scan(
                body, (w, alpha_bar), (G, mask)
            )
        return w, alpha_bar

    return _prof.wrap_dispatch(apply_merge, "kernel.dispatch", "saga_apply_merge")


# ------------------------------------------------------------- mesh steps
# Multi-chip worker compute plane (ISSUE 11 / ROADMAP item 1): a DCN
# worker whose host has N chips computes its mini-batch gradient
# batch-parallel over a local ``dp`` mesh (parallel/mesh.py::make_mesh)
# instead of on one device.  Decomposition per arXiv:1505.04956
# (Hogwild-style data parallelism): each device holds a static row block
# of the worker's shard (placed ONCE via pad_and_shard, resident in HBM
# for the whole run), computes the partial gradient of its rows, and a
# ``lax.psum`` over ``dp`` reduces the partials locally -- the worker
# still emits ONE fused gradient per step, so the PS wire protocol is
# untouched (one PUSH per cohort member, same payload shape).


def make_mesh_asgd_worker_step(
    batch_rate: float, mesh, loss: str = "least_squares", axis: str = "dp"
):
    """jit (Xs, ys, valid, w, key) -> (g_sum, new_key) over a ``dp`` mesh.

    ``Xs``/``ys``/``valid`` are the pad_and_shard placements of the
    worker's shard (rows split over ``axis``); ``w`` and ``key`` are
    replicated.  Sampling is device-count-invariant: every device draws
    the IDENTICAL full-length Bernoulli mask (replicated subkey, global
    padded shape) and slices its own row block, so the sampled row set
    is a function of (key, padded length) alone, not of how many chips
    the worker happens to have.  On an unpadded shard the draw is
    bit-identical to :func:`make_asgd_worker_step`'s dense mask.

    The per-device partial is the same masked ``grad_sum`` the
    single-device step runs on its rows; ``lax.psum`` folds the partials
    (on this rig's CPU backend the all-reduce is a sequential
    device-order fold -- the oracle tests/test_meshgrad.py pins bit-for-
    bit).
    """
    grad_sum = _grad_sum_for(loss)
    from jax.sharding import PartitionSpec as P

    from asyncframework_tpu.parallel.mesh import resolve_shard_map

    n_dev = mesh.shape[axis]

    @functools.partial(
        resolve_shard_map(),
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(None), P(None)),
        out_specs=(P(None), P(None)),
    )
    def _step(Xl, yl, vl, w, key):
        n_l = Xl.shape[0]  # static local block length
        p = jax.lax.axis_index(axis)
        with jax.named_scope("sample"):
            key2, sub = jax.random.split(key)
            # replicated full-length draw, then slice my block: the mask
            # is identical on every device and invariant to the mesh size
            mask_full = jax.random.bernoulli(
                sub, batch_rate, (n_l * n_dev,)
            )
            ml = jax.lax.dynamic_slice_in_dim(
                mask_full.astype(jnp.float32), p * n_l, n_l
            ) * vl
        g_local = grad_sum(Xl, yl, w, ml)
        return jax.lax.psum(g_local, axis), key2

    return jax.jit(_step)


def make_mesh_saga_dcn_worker_step(mesh, axis: str = "dp"):
    """jit (Xs, ys, w, idx, alpha_sel, n_valid) -> (g, diff_sel) -- the
    mesh face of :func:`make_saga_dcn_worker_step`.

    The PS samples row ids ``idx`` into the worker's shard and ships the
    current history scalars ``alpha_sel`` with the model (both
    replicated); the shard's rows live row-sharded over ``axis``.  Each
    sampled slot is OWNED by exactly one device (the one holding that
    row): the owner gathers its row locally, computes the candidate
    scalar ``diff_j = x_j . w - y_j`` and the slot's gradient
    contribution ``(diff_j - alpha_j) x_j``; non-owners contribute exact
    zeros.  Two psums assemble the full (cap,) candidate vector and the
    fused (d,) gradient -- the same values the single-device step
    produces, decomposed by row ownership.
    """
    from jax.sharding import PartitionSpec as P

    from asyncframework_tpu.parallel.mesh import resolve_shard_map

    @functools.partial(
        resolve_shard_map(),
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(None), P(None), P(None), P()),
        out_specs=(P(None), P(None)),
    )
    def _step(Xl, yl, w, idx, alpha_sel, n_valid):
        cap = idx.shape[0]
        n_l = Xl.shape[0]
        p = jax.lax.axis_index(axis)
        valid = jnp.arange(cap) < n_valid
        local = idx - p * n_l
        mine = valid & (local >= 0) & (local < n_l)
        li = jnp.clip(local, 0, n_l - 1)
        vm = mine.astype(jnp.float32)
        with jax.named_scope("gather"):
            Xs_ = Xl[li]  # (cap, d) LOCAL gather -- only my rows are real
        with jax.named_scope("residual"):
            diff_l = (mm_f32(Xs_, w) - yl[li]) * vm
        with jax.named_scope("grad"):
            g_l = Xs_.T @ ((diff_l - alpha_sel) * vm)
        # each slot has exactly one owner: the psums add zeros to the
        # owner's value (slot-exact) and fold the per-device gradient
        # partials (device-order, like the ASGD mesh step)
        g, diff = jax.lax.psum((g_l, diff_l), axis)
        return g, diff

    return jax.jit(_step)


# ------------------------------------------------------------------ sparse
def sparse_step_capacity(batch_rate: float, n_rows: int) -> int:
    """Static slot count for the compacted sparse step: E[count] + 6 sigma
    of the Bernoulli draw, lane-rounded and capped at the shard size.
    Overflow probability per step is ~1e-9; overflowing rows are dropped
    (the sample is fractionally smaller that step, nothing corrupts).
    """
    import math

    mean = batch_rate * n_rows
    sigma = math.sqrt(max(batch_rate * (1.0 - batch_rate) * n_rows, 0.0))
    cap = int(math.ceil(mean + 6.0 * sigma))
    cap = max(8, ((cap + 7) // 8) * 8)
    return min(cap, n_rows)


def sparse_walk_tile(batch_rate: float, d: int, n_rows: int, width: int):
    """``(R, C)``, the block the compacted step of a shard of ``n_rows``
    rows read ``width`` slots wide walks its packed sample in under a
    ``(d,)`` model, or ``None`` where it reads the sample whole
    (``gradients.walk_tile``: the chooser, from these shapes alone)."""
    return walk_tile(sparse_step_capacity(batch_rate, n_rows), width,
                     walk_accumulator_resident(d, n_rows, width))


def sparse_walked_slots(batch_rate: float, d: int, n_rows: int, width: int,
                        row_lengths=None) -> float:
    """The slots a compacted sparse step gathers and scatter-adds on a
    shard of ``n_rows`` rows read ``width`` slots wide, on average over the
    Bernoulli draw and from the host's integers alone.  Where the sample
    is read whole (:func:`sparse_walk_tile`: a shard stored in sublane
    tiles), capacity x ``width``; where it is WALKED, ``R`` x ``C`` for
    every chunk of every row tile up to the tile's longest row
    (``row_lengths``: the slots each row of the shard fills, in the order
    they are stored; ``None``: every row ``width``), the draw taken as
    every ``1 / batch_rate``-th row and the capacity's tail as unfilled.
    A count equal to capacity x ``width`` says the walk did not engage."""
    cap = sparse_step_capacity(batch_rate, n_rows)
    tile = sparse_walk_tile(batch_rate, d, n_rows, width)
    if tile is None:
        return float(cap * width)
    rows, chunk = tile
    lengths = (np.full(n_rows, width) if row_lengths is None
               else np.asarray(row_lengths))
    packed = np.arange(cap)
    drawn = np.minimum((packed / batch_rate).astype(np.int64), n_rows - 1)
    filled = np.where(packed < batch_rate * n_rows, lengths[drawn], 0)
    tiles = -(-cap // rows)
    longest = np.pad(filled, (0, tiles * rows - cap)).reshape(
        tiles, rows).max(axis=1)
    return float(rows * chunk * np.sum(-(-longest // chunk)))


def _sized_by_capacity(step, factory: str, batch_rate: float, d: int,
                       **static):
    """A compacted sparse step as the engine calls it, and its size.

    The step is built once a MACHINE: the jitted ``step`` goes behind
    :class:`program_store.LoadedByShape`, which loads the executable of a
    call's shape from the store beside the compile cache under a key that
    needs no trace (the ``factory``'s name, ``batch_rate``, ``d`` and its
    other ``static`` arguments, the operands, the versions, the source),
    and traces, compiles and stores it where there is none.
    These steps alone: a solver has one a shard SHAPE (eight over
    webspam's shards), each 0.5 to 1.2 s of tracing and lowering with the
    device idle, and tasks of 8 to 330 ms, beside which a call of the
    loaded executable costs the host what ``jit``'s does (v5e, PERF.md
    section 6, PR 57); the dense steps, the applies and the evaluations
    have one shape each and stay on ``jit``.
    ``step.counts()``: what this object loaded, built and failed to load.

    Its size, for who asks the step it runs:
    ``step.task_rows(n_rows)``, the rows its compaction holds
    (:func:`_counts_rows`), and what the two choosers say of the sample
    it packs from ``n_rows`` rows read ``width`` slots wide (the shard's
    live width where the step was built with one) against its ``(d,)``
    float32 model: ``step.gather_path(n_rows, width)``
    (``gradients.sparse_gather_path``), ``step.scatter_path(n_rows,
    width)`` (``gradients.sparse_scatter_path``: the same answer for a
    sample that is walked, as ASGD's of a shard stored in lane tiles is,
    and one read whole) and ``step.sorted_pairs(n_rows, width)``
    (``gradients.sparse_sorted_pairs``: the pairs that program sorts), the
    solvers' ``extras["sparse_gather_path"]``, ``["sparse_scatter_path"]``
    and ``["sorted_pairs_per_step_mean"]``."""
    step = LoadedByShape(step, factory,
                         dict(batch_rate=batch_rate, d=d, **static))

    def task_rows(n_rows):
        return sparse_step_capacity(batch_rate, n_rows)

    def gather_path(n_rows, width):
        return sparse_gather_path(
            jax.ShapeDtypeStruct((d,), jnp.float32),
            jax.ShapeDtypeStruct((task_rows(n_rows), width), jnp.int32),
        )

    step.gather_path = gather_path
    step.scatter_path = lambda n_rows, width: sparse_scatter_path(
        d, task_rows(n_rows) * width)
    step.sorted_pairs = lambda n_rows, width: sparse_sorted_pairs(
        d, task_rows(n_rows) * width)
    return _counts_rows(step, task_rows)


def _pack_rows(mask, cap: int):
    """``(idx, valid)`` of a boolean row mask packed to ``cap`` slots: the
    set rows' ids ascending, the first ``cap`` kept on overflow, 0 in the
    unfilled tail; ``valid`` (bool) marks the filled slots.  ``idx`` is to
    the bit what ``jnp.nonzero(mask, size=cap, fill_value=0)`` returns, by
    ONE single-operand sort of the keys ``row id if set else n_rows``.
    The keys of set rows are distinct, so the sort need not be stable (a
    stable one carries an iota as a second operand on the TPU).  On the
    v5e, 2,865,039 rows (PERF.md section 6, PR 33): 3.2 ms, 1.1 ns a row;
    ``jnp.nonzero``'s ``bincount(cumsum(mask))``, a scatter-add of as many
    ones, 27.0 ms.
    """
    n_rows = mask.shape[0]
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    keys = jax.lax.sort(jnp.where(mask, rows, n_rows), is_stable=False)[:cap]
    valid = keys < n_rows
    return jnp.where(valid, keys, 0), valid


def _sampled_rows(sub, batch_rate, n_rows: int, dtype):
    """The compacted sparse steps' sample: a Bernoulli(b) draw over the
    shard's rows packed to the static capacity
    (:func:`sparse_step_capacity`, :func:`_pack_rows`); ``valid`` comes
    back as 0/1 of ``dtype``.  ONE definition for the ASGD and the ASAGA
    core, and through them for the fused rounds: the same key samples the
    same rows everywhere."""
    cap = sparse_step_capacity(batch_rate, n_rows)
    with jax.named_scope("sample"):
        mask = jax.random.bernoulli(sub, batch_rate, (n_rows,))
    with jax.named_scope("compact"):
        idx, valid = _pack_rows(mask, cap)
    return idx, valid.astype(dtype)


def _live_width(stored: int, live_width) -> int:
    """The ELL columns a program built with ``live_width`` reads of a shard
    stored ``stored`` wide: ``None``, all of them; ONE integer, that many
    of every shard (a factory built by hand for one shape); a mapping
    ``{stored width: live width}``, each shard shape's own
    (``SparseShardedDataset.live_widths``, what :func:`worker_programs`
    builds with: the program is traced once a shape it is called with,
    and a width the mapping lacks is read whole)."""
    if isinstance(live_width, Mapping):
        live_width = live_width.get(stored)
    return stored if live_width is None else min(stored, live_width)


def _live_columns(cols, vals, live_width):
    """``(cols, vals)`` of a stored padded-ELL shard ``(n_p, K)`` at its
    LIVE width: the first ``live_width`` ELL columns; ``None``, or a shard
    stored no wider, is the shard as it is.

    ``data/sparse.py`` packs a row's values to the left, so the ELL columns
    from the live width on hold ``col=0, val=0`` in every row: a product of
    theirs is ``0.0 * w[0]`` added into ``g[0]``.  The v5e pays a gather, a
    sort and a scatter-add by the SLOT, so a step over ``(capacity,
    live_width)`` costs ``live_width / K`` of one over the stored width
    (kdd2012: 11 of 16; PERF.md section 6, PR 38).  Call it INSIDE the jit
    that reads the shard, and as a plain slice: the TPU stores the shard
    rows minor (``{0,1}``), so the first ``live_width`` columns are a
    contiguous prefix and the slice is a ``bitcast`` there, no copy
    (``tests/test_step_layout.py`` holds that in the compiled step).  A
    gather that names the prefix by its slice size instead (``(1,
    live_width)`` of the ``(n_p, K)`` operand) is NOT the same program: the
    compiler expands it into a loop of one ``dynamic-slice`` a sampled row
    (compiled for a described v5e, PR 38)."""
    live = _live_width(cols.shape[1], live_width)
    if live == cols.shape[1]:
        return cols, vals
    return cols[:, :live], vals[:, :live]


def _sparse_compacted_gradient(cols, vals, y, w, sub, batch_rate, grad_sum,
                               loss="least_squares", live_width=None):
    """Shared core of the compacted sparse step: Bernoulli(b) sample
    packed to static capacity (:func:`_sampled_rows`), only those rows
    gathered and scatter-added, in the order they are stored and at the
    shard's live width (:func:`_live_columns`: every gather, sort and
    scatter downstream sees ``(capacity, live_width)`` at the most).  A
    sample of a shard stored in lane tiles, whose rows are of unequal
    length, is WALKED (``gradients.walk_tile``, chosen from the shape):
    the model's gather takes it in ``(R, C)`` blocks, each row tile up to
    its last non-zero, and never sees the slots behind it nor the unfilled
    tail of the capacity; ``grad_sum`` is told so, and adds the products
    by a scatter-add a block or sorts them as ONE list, dead pairs last
    (``gradients.sparse_scatter_path``).  The rows'
    coefficient is ``m - y`` (least squares) or ``sigmoid(m) - y``
    (logistic) of the margin ``m = x . w``, f32 throughout.
    ONE definition, used by the engine worker step AND the fused rounds --
    the fused path's sampling-parity claim depends on these staying
    bit-identical."""
    if loss not in ("least_squares", "logistic"):
        raise ValueError(f"unknown loss {loss!r}")
    cols, vals = _live_columns(cols, vals, live_width)
    idx, valid = _sampled_rows(sub, batch_rate, y.shape[0], vals.dtype)
    with jax.named_scope("gather"):
        c_sel = cols[idx]
        v_sel = vals[idx] * valid[:, None]  # unfilled slots contribute 0
    walk = sample_walk(v_sel, sparse_walk_tile(  # None: it is read whole
        batch_rate, w.shape[0], y.shape[0], cols.shape[1]))
    with jax.named_scope("residual"):
        m = sparse_margins(c_sel, v_sel, w, walk)
        if loss == "least_squares":
            r = m - y[idx] * valid
        else:  # an unfilled slot's margin is 0: sigmoid(0) is not
            r = (jax.nn.sigmoid(m) - y[idx]) * valid
    return grad_sum(c_sel, v_sel, r, walk)


def make_sparse_asgd_worker_step(batch_rate: float, d: int,
                                 loss: str = "least_squares",
                                 live_width: "int | None" = None):
    """jit (cols, vals, y, w, key) -> (g_sum (d,), new_key).

    The sparse analog of :func:`make_asgd_worker_step` for padded-ELL shards
    (rcv1-class data), with **masked-row compaction**: a Bernoulli(b) sample
    touches only ~b of the shard's rows, so gathering/scattering the FULL
    (n_p, K) arrays wastes (1-b) of the work: the v5e pays by the SLOT,
    2.7 ns a gathered model value (eight an index,
    ``gradients.sparse_margins``; 6.9 one an index until PR 36) and 6.9 ns
    a scatter-added one (PERF.md section 6, PR 36: 11.6 ns a sampled
    slot in all, with the row gathers and the packing), so a step over all
    2,865,039 x 40 slots of a criteo shard would take 1.3 s where its
    sampled twentieth takes 0.068.  For the same reason the step reads the
    shard at its LIVE width: ``live_width`` is the dataset's
    (``SparseShardedDataset.live_widths``; ``None`` = the stored width), and
    the ELL columns beyond it, padding in every row, are never gathered,
    sorted or scatter-added (:func:`_live_columns`; kdd2012: 11 of 16
    slots a row, PERF.md section 6, PR 38).
    Instead the sampled row ids are packed into a
    static-capacity index vector (:func:`_pack_rows` -- static shapes,
    jit-stable), and only those rows' cols/vals are gathered and
    scatter-added: ~b of the traffic for the identical gradient.  The
    returned gradient is dense because the parameter server applies dense
    updates (the reference's driver-side axpy is dense too).
    """
    grad_sum = make_sparse_grad_sum(d)

    @jax.jit
    def step(cols, vals, y, w, key):
        key, sub = jax.random.split(key)
        g = _sparse_compacted_gradient(
            cols, vals, y, w, sub, batch_rate, grad_sum, loss, live_width
        )
        return g, key

    return _sized_by_capacity(step, "sparse_asgd_worker_step", batch_rate, d,
                              loss=loss, live_width=live_width)


def _sparse_saga_compacted(cols, vals, y, w, alpha, sub, batch_rate,
                           grad_sum, live_width=None):
    """Shared core of the compacted sparse ASAGA worker computation
    (the ASGD core's sample, :func:`_sampled_rows`; gather, candidate
    scalars, history-corrected gradient).
    ONE definition, used by the engine worker step AND the fused rounds --
    the fused path's sampling-parity claim depends on these staying
    bit-identical (same discipline as :func:`_sparse_compacted_gradient`,
    and the same live width: ``c_sel`` / ``v_sel`` come back
    ``(capacity, live_width)``).
    """
    cols, vals = _live_columns(cols, vals, live_width)
    idx, valid = _sampled_rows(sub, batch_rate, y.shape[0], vals.dtype)
    with jax.named_scope("gather"):
        c_sel = cols[idx]
        v_sel = vals[idx] * valid[:, None]  # unfilled slots contribute 0
    with jax.named_scope("residual"):
        diff_sel = sparse_margins(c_sel, v_sel, w) - y[idx] * valid
    g = grad_sum(c_sel, v_sel, diff_sel - alpha[idx])
    return g, diff_sel, idx, valid, c_sel, v_sel


def _sparse_saga_commit_expr(alpha, diff_sel, idx, valid):
    """The ScalarMap commit as a traceable expression (shared by the
    jitted engine commit and the fused scan): ``alpha[idx_j] <- diff_sel_j``
    for valid slots; padding slots scatter OUT OF BOUNDS and drop --
    routing them anywhere real would race a valid write at the same index.
    ``idx`` is ascending (:func:`_pack_rows` sorts the sampled row ids)
    with padding at the tail, so the scatter runs with
    ``indices_are_sorted``."""
    n = alpha.shape[0]
    tgt = jnp.where(valid > 0, idx, n)
    return alpha.at[tgt].set(diff_sel, indices_are_sorted=True, mode="drop")


def make_sparse_saga_worker_step(batch_rate: float, d: int,
                                 live_width: "int | None" = None):
    """jit (cols, vals, y, w, alpha, key) ->
    (g, diff_sel, idx, valid, c_sel, v_sel, new_key) -- COMPACTED.

    Sparse ASAGA worker computation with the same masked-row compaction as
    the ASGD step: the Bernoulli-sampled row ids pack into a static-capacity
    index vector and only those rows' cols/vals/history are touched (~b of
    the full-shard gather/scatter volume).  ``diff_sel`` are the candidate
    history scalars FOR THE SELECTED ROWS; ``idx``/``valid`` say where they
    go; ``c_sel``/``v_sel`` (validity-zeroed, ``(capacity, live_width)``:
    the shard is read at its live width as the ASGD step reads it) ride
    along so the updater's exact table delta needs no second row gather.
    """
    grad_sum = make_sparse_grad_sum(d)

    @jax.jit
    def step(cols, vals, y, w, alpha, key):
        key, sub = jax.random.split(key)
        g, diff_sel, idx, valid, c_sel, v_sel = _sparse_saga_compacted(
            cols, vals, y, w, alpha, sub, batch_rate, grad_sum, live_width
        )
        return g, diff_sel, idx, valid, c_sel, v_sel, key

    return _sized_by_capacity(step, "sparse_saga_worker_step", batch_rate, d,
                              live_width=live_width)


def make_sparse_saga_commit():
    """jit (alpha, diff_sel, idx, valid) -> alpha'; see
    :func:`_sparse_saga_commit_expr` for the semantics.  Its XLA module is
    ``jit_sparse_saga_commit_history``, beside the exact delta's
    ``jit_sparse_saga_table_delta``: what a device trace finds the padded-ELL
    history path by (the dense programs are ``jit_saga_commit_history`` and
    ``jit_saga_table_delta``)."""

    @jax.jit
    def sparse_saga_commit_history(alpha, diff_sel, idx, valid):
        return _sparse_saga_commit_expr(alpha, diff_sel, idx, valid)

    return sparse_saga_commit_history


def make_sparse_table_delta(d: int):
    """jit (c_sel, v_sel, diff_sel, alpha_cur, idx) -> exact table delta.

    The compacted analog of :func:`make_saga_table_delta`: the change the
    commit makes to the mean history gradient, computed against the CURRENT
    table slice (``alpha_cur[idx]``) at commit time -- see the dense
    variant's docstring for why dispatch-time history drifts.  ``c_sel``
    and ``v_sel`` are the step's own sample, ``(capacity, live_width)``
    already: the delta scatter-adds as many slots as the step did.
    """
    grad_sum = make_sparse_grad_sum(d)

    @jax.jit
    def sparse_saga_table_delta(c_sel, v_sel, diff_sel, alpha_cur, idx):
        with jax.named_scope("history.delta"):
            return grad_sum(c_sel, v_sel, diff_sel - alpha_cur[idx])

    return sparse_saga_table_delta


#: rows a block of the blocked sparse evaluation holds, and snapshots one
#: gather serves.  Eight snapshots are one sublane tile: the v5e keeps the
#: ``(8, d)`` table in VMEM, rows minor, and gathers all eight per index
#: into ``(8, K, rows)``, rows minor like the stored shard, so nothing is
#: relaid (a ninth snapshot pads the tile to 16 and the compiler then
#: copies the gathered block to another tiling: 2.0 GB of temporaries at
#: 18 snapshots, compiled for a described v5e).  On the chip (PERF.md
#: section 6, PR 32; one 2,865,039 x 40 shard, seconds a call of eight):
#: 65,536 rows 0.392 (3.4 ns a gathered slot: eight snapshots for half of
#: what ONE element-wise ``w[cols]`` pass costs, 0.826), 131,072 and
#: 262,144 rows 0.577, 16,384 and 32,768 rows 1.56; sixteen snapshots in
#: one call 1.16.  65,536 rows x 40 slots x 8 snapshots x 4 B = 84 MB a
#: gathered block, which the compiler keeps in VMEM (11 MB of temporaries
#: in HBM; at 262,144 rows 336 MB of them): the size is what the v5e's 128
#: MiB of VMEM holds.  The step's own eight-wide gather
#: (``gradients.sparse_margins``, PR 36) is another program, with a block
#: of its own: its table is the ONE model as ``(8, d / 8)``, which the
#: compiler stores eight-minor, where this ``(8, d)`` table is too large for
#: that and stays rows major.  Where the table cannot be in VMEM at all
#: (kdd2012's d = 54,686,452: 1.75 GB; PERF.md section 6, PR 37; one
#: 4,676,222 x 16 shard) the block does not matter, 1.086 s a call at
#: 65,536 rows and 1.077 at 8,192 (14.4 ns a slot, eight values 219 MB
#: apart an index), and a table packed sixteen columns of eight snapshots
#: a 128-lane row, one 512 B read an index, is slower (1.82 to 2.05): the
#: form and the block stay.
#: A block is also at most ``SPARSE_EVAL_BLOCK_SLOTS`` slots, the slots of
#: those 65,536 rows at criteo's stored width: rows thousands of slots
#: wide (webspam: 1,664 to 16,384) make a block of 1,568 to 160 rows, not
#: a shard at once (21,875 x 16,384 x 8 snapshots x 4 B = 11.5 GB gathered).
SPARSE_EVAL_BLOCK_ROWS = 65_536
SPARSE_EVAL_BLOCK_SLOTS = SPARSE_EVAL_BLOCK_ROWS * 40
SPARSE_EVAL_SNAPSHOTS = 8


def make_sparse_trajectory_loss_eval(loss: str = "least_squares",
                                     live_width: "int | None" = None):
    """jit (cols, vals, y, W (S,d)) -> (S,) per-snapshot loss sums, in ONE
    pass over the shard in row blocks, at the shard's live width
    (``live_width``: the dataset's one or its mapping by stored width,
    :func:`_live_width`; ``None`` = the stored width; a block
    is ``(rows, live_width)`` of the shard, so the ELL columns that hold
    padding in every row are not gathered: :func:`_live_columns`).

    Per block of ``SPARSE_EVAL_BLOCK_ROWS`` rows (fewer where the rows are
    wide: ``SPARSE_EVAL_BLOCK_SLOTS``), ``W[:, cols_block]`` is
    gathered once for up to ``SPARSE_EVAL_SNAPSHOTS`` snapshots (more are
    taken eight at a time inside the block), the margins ``(S, rows)``
    are the f32 sum over the slots, and the block adds ``sum (m - y)^2``
    or ``sum log(1 + e^m) - y m`` (the stable ``logaddexp`` form).  The
    last block is clamped to the shard's end and the rows it shares with
    the block before are masked, so a ragged shard compiles nothing else.
    The shard is read where it lies: a shard whose stored width is no
    whole number of 128-lane tiles (40, 16) is stored rows minor and its
    transposed block is a ``bitcast``; one that is (a wide shard,
    ``data/sparse.py: _round_up``) is stored row-major and its block is
    taken as it is.  No copy of ``cols`` and ``vals`` to the other layout
    is made (that copy was 1,697 B a shard row, which decided the ``n`` a
    chip could hold: PERF.md section 6, PR 30).

    ``eval_shard.snapshots_per_call``: what the engine stacks a call's
    ``W`` to, so that one executable serves every trajectory length;
    ``eval_shard.blocks(n_rows, K)`` and ``eval_shard.block_rows(n_rows,
    K)``: the row blocks of one call over a shard stored ``K`` wide and
    the rows of one (without ``K``: what the row bound alone gives);
    ``eval_shard.width(K)``: the ELL columns it reads of such a shard.
    """
    if loss not in ("least_squares", "logistic"):
        raise ValueError(f"unknown loss {loss!r}")
    tile = SPARSE_EVAL_SNAPSHOTS

    def width(stored):
        return _live_width(stored, live_width)

    def _row_blocks(n_rows, stored):
        most = SPARSE_EVAL_BLOCK_ROWS
        if stored is not None:  # whole sublane tiles of the wide rows
            by_slots = SPARSE_EVAL_BLOCK_SLOTS // width(stored)
            most = min(most, max(8, by_slots - by_slots % 8))
        return row_blocks(n_rows, most)

    def block_rows(n_rows, stored=None):
        return _row_blocks(n_rows, stored)[0]

    def blocks(n_rows, stored=None):
        return _row_blocks(n_rows, stored)[1]

    @jax.jit
    def eval_shard(cols, vals, y, W):
        n_rows, n_snap = y.shape[0], W.shape[0]
        rows, n_blocks = _row_blocks(n_rows, cols.shape[1])
        # slots minor in a block where the device stores the shard so
        slots_minor = cols.shape[1] % 128 == 0
        cols, vals = _live_columns(cols, vals, live_width)

        def one_block(i, acc):
            start, at = clamped_block(i, rows, n_rows)
            fresh = (at + jnp.arange(rows) >= start).astype(jnp.float32)
            cb = jax.lax.dynamic_slice_in_dim(cols, at, rows)
            vb = jax.lax.dynamic_slice_in_dim(vals, at, rows)
            if not slots_minor:  # (K, rows): how a narrow shard is stored
                cb, vb = cb.T, vb.T
            yb = jax.lax.dynamic_slice_in_dim(y, at, rows)
            sums = []
            for lo in range(0, n_snap, tile):
                with jax.named_scope("gather"):
                    # (<= tile, K, rows), or (<= tile, rows, K)
                    picked = W[lo:lo + tile][:, cb]
                m = jnp.sum(picked * vb[None].astype(jnp.float32),
                            axis=2 if slots_minor else 1)
                if loss == "least_squares":
                    per_row = jnp.square(m - yb)
                else:
                    per_row = jnp.logaddexp(0.0, m) - yb * m
                sums.append(jnp.sum(per_row * fresh, axis=1))
            return acc + jnp.concatenate(sums)

        return jax.lax.fori_loop(
            0, n_blocks, one_block, jnp.zeros(n_snap, jnp.float32)
        )

    eval_shard.snapshots_per_call = tile
    eval_shard.blocks = blocks
    eval_shard.block_rows = block_rows
    eval_shard.width = width
    return eval_shard


def make_fused_asgd_rounds(
    gamma: float,
    batch_rate: float,
    n: int,
    shards,
    loss: str = "least_squares",
    rounds_per_call: int = 16,
    sparse_d: "int | None" = None,
    live_width: "int | None" = None,
):
    """jit (w, k, keys (nw,2)) -> (w', k', keys', W_snap (R, d)) -- R full
    cohort rounds with ZERO host involvement (the device-resident accept
    loop, VERDICT r3 item 2).

    Semantics: at ``taw = inf`` with a full-wave cohort, the async engine's
    accept path reduces to "the whole cohort reads one model version; its
    gradients are applied in order with the ``gamma/sqrt(k/P+1)`` schedule"
    (``SparkASGDThread.scala:154-189`` with the tau filter never firing).
    That is a pure function of (w, k, keys), so R rounds fuse into one
    ``lax.scan`` -- the host's ~1 ms/update dispatch bound (BASELINE.md
    round 3) disappears; per-update cost becomes device compute.  The
    engine path stays the general case (finite taw, stragglers,
    speculation, fault tolerance cannot live inside a scan); this is the
    recipe-matched fast path for the reference's own headline runs, which
    all use ``taw = inf`` (``README.md:64``).

    ``shards``: list of (X, y) dense -- or, with ``sparse_d`` set, of
    (cols, vals, y) padded-ELL -- device arrays, all resident on the SAME
    device (the PS chip); per-worker PRNG chains ride in ``keys`` exactly
    as the engine keeps them, so sampling parity per worker is preserved.
    ``live_width``: the width the sparse shards are read at, as
    :func:`make_sparse_asgd_worker_step` takes it.
    """
    grad_sum = _grad_sum_for(loss)
    nw = len(shards)
    par_recs = batch_rate * n / nw
    sp_grad_sum = None
    if sparse_d is not None:
        if loss != "least_squares":
            raise ValueError(
                "sparse fused rounds support least_squares only (the "
                "compacted residual is least-squares); got " + loss
            )
        sp_grad_sum = make_sparse_grad_sum(sparse_d)

    def one_gradient(shard, w, key):
        # the SAME cores the engine worker steps run
        if sparse_d is not None:
            key, sub = jax.random.split(key)
            cols, vals, y = shard
            g = _sparse_compacted_gradient(
                cols, vals, y, w, sub, batch_rate, sp_grad_sum,
                live_width=live_width,
            )
            return g, key
        X, y = shard
        return _dense_sampled_gradient(X, y, w, key, batch_rate, grad_sum)

    def round_fn(carry, _x):
        w, k, keys = carry
        gs = []
        new_keys = []
        for i, shard in enumerate(shards):  # static unroll over workers
            g, nk = one_gradient(shard, w, keys[i])
            gs.append(g)
            new_keys.append(nk)
        with jax.named_scope("apply"):
            G = jnp.stack(gs)
            kk = k + jnp.arange(nw, dtype=jnp.float32)
            lr = gamma / jnp.sqrt(kk / nw + 1.0)
            w2 = w - (lr / par_recs) @ G
        return (w2, k + float(nw), jnp.stack(new_keys)), w2

    @jax.jit
    def run_rounds(w, k, keys):
        (w2, k2, keys2), W_snap = jax.lax.scan(
            round_fn, (w, k, keys), None, length=rounds_per_call
        )
        return w2, k2, keys2, W_snap

    return run_rounds


def make_fused_saga_rounds(
    gamma: float,
    batch_rate: float,
    n: int,
    shards,
    rounds_per_call: int = 16,
    sparse_d: "int | None" = None,
    live_width: "int | None" = None,
):
    """jit (w, ab, alphas, keys) -> (w', ab', alphas', keys', W_snap) --
    R full ASAGA cohort rounds fused on one device (the ASAGA face of the
    device-resident accept loop; see :func:`make_fused_asgd_rounds` for
    the taw=inf semantics argument).

    Per round: every worker computes its history-corrected gradient
    ``g_i = X_i^T (mask_i * (diff_i - alpha_i))`` against the round-start
    model and its OWN (current) history slice; the accepts then fold
    sequentially -- ``w <- w - gamma*(g_j/parRecs + ab); ab <- ab + g_j/N``
    (``SparkASAGAThread.scala:210-213``) -- and each worker's candidate
    scalars commit into its slice.  ``delta == g`` is exact here for the
    same reason as the DCN PS: slices are worker-disjoint and one wave
    carries one result per worker, so the alpha a gradient was computed
    against IS the alpha at commit.  Least-squares only (the scalar
    history compression requires it, like the solver).

    ``sparse_d``: padded-ELL shards as (cols, vals, y) tuples -- the
    worker computation mirrors the engine's compacted sparse SAGA step
    (sampled rows gathered; candidate scalars committed by a scatter
    whose padding slots drop out of bounds; see
    make_sparse_saga_worker_step / make_sparse_saga_commit), at the same
    ``live_width``.
    """
    nw = len(shards)
    par_recs = batch_rate * n / nw
    sp_grad_sum = None
    if sparse_d is not None:
        sp_grad_sum = make_sparse_grad_sum(sparse_d)

    def one_sparse(shard, w, alpha, key):
        # the SAME compacted core + commit the engine worker step runs
        cols, vals, y = shard
        key, sub = jax.random.split(key)
        g, diff_sel, idx, valid, _c, _v = _sparse_saga_compacted(
            cols, vals, y, w, alpha, sub, batch_rate, sp_grad_sum,
            live_width,
        )
        alpha2 = _sparse_saga_commit_expr(alpha, diff_sel, idx, valid)
        return g, alpha2, key

    def round_fn(carry, _x):
        w, ab, alphas, keys = carry
        gs = []
        new_alphas = []
        new_keys = []
        for i, shard in enumerate(shards):  # static unroll over workers
            if sparse_d is not None:
                g, a2, key = one_sparse(shard, w, alphas[i], keys[i])
                gs.append(g)
                new_alphas.append(a2)
                new_keys.append(key)
                continue
            X, y = shard
            key, sub = jax.random.split(keys[i])
            mask = jax.random.bernoulli(
                sub, batch_rate, (X.shape[0],)
            ).astype(jnp.float32)
            g, diff = dense_masked_grad(
                X, y, w, mask, alpha=alphas[i], batch_rate=batch_rate)
            gs.append(g)
            # commit the wave's candidate scalars into the slice
            new_alphas.append(jnp.where(mask > 0, diff, alphas[i]))
            new_keys.append(key)
        # sequential accept fold (ab advances between the nw applies)
        w2, ab2 = w, ab
        with jax.named_scope("apply"):
            for g in gs:
                w2 = w2 - (gamma / par_recs) * g - gamma * ab2
                ab2 = ab2 + g / n
        return (w2, ab2, tuple(new_alphas), jnp.stack(new_keys)), w2

    @jax.jit
    def run_rounds(w, ab, alphas, keys):
        (w2, ab2, alphas2, keys2), W_snap = jax.lax.scan(
            round_fn, (w, ab, tuple(alphas), keys), None,
            length=rounds_per_call,
        )
        return w2, ab2, alphas2, keys2, W_snap

    return run_rounds


def make_saga_dcn_worker_step():
    """jit (X, y, w, idx, alpha_sel, n_valid) -> (g, diff_sel).

    The DCN-ASAGA worker computation (``SparkASAGAThread.scala:280-294``,
    ``sampledMap``): the PS owns the scalar-history table and SAMPLES for the
    worker, shipping padded row ids ``idx`` and their current history scalars
    ``alpha_sel`` with the model; the worker gathers only those rows,
    computes candidate scalars ``diff_sel = x_i . w - y_i`` and the
    history-corrected gradient ``g = sum_i (diff_i - alpha_i) x_i``, and
    ships both back.  Padding slots (``>= n_valid``) contribute zero.
    Static shapes: ``idx``/``alpha_sel`` are capacity-padded by the PS
    (:func:`sparse_step_capacity`), so one executable serves every round.
    """

    @jax.jit
    def step(X, y, w, idx, alpha_sel, n_valid):
        cap = idx.shape[0]
        valid = (jnp.arange(cap) < n_valid).astype(jnp.float32)
        with jax.named_scope("gather"):
            Xs = X[idx]
        with jax.named_scope("residual"):
            diff = (mm_f32(Xs, w) - y[idx]) * valid
        with jax.named_scope("grad"):
            g = Xs.T @ ((diff - alpha_sel) * valid)
        return g, diff

    return _prof.wrap_dispatch(step, "kernel.dispatch", "saga_dcn_worker_step")


def make_saga_dcn_sparse_worker_step(d: int):
    """jit (cols, vals, y, w, idx, alpha_sel, n_valid) -> (g, diff_sel).

    Sparse (padded-ELL) variant of :func:`make_saga_dcn_worker_step` for
    rcv1-class shards: the PS-sampled row ids gather only those rows'
    cols/vals, and the history-corrected gradient scatter-adds into a dense
    (d,) vector (the PS applies dense updates).  Padding rows are zeroed
    through ``v_sel`` so they contribute nothing.
    """
    grad_sum = make_sparse_grad_sum(d)

    @jax.jit
    def step(cols, vals, y, w, idx, alpha_sel, n_valid):
        cap = idx.shape[0]
        valid = (jnp.arange(cap) < n_valid).astype(vals.dtype)
        with jax.named_scope("gather"):
            c_sel = cols[idx]
            v_sel = vals[idx] * valid[:, None]
        with jax.named_scope("residual"):
            diff = (sparse_margins(c_sel, v_sel, w) - y[idx]) * valid
        # invalid rows have v_sel == 0, so their (diff - alpha) is inert
        g = grad_sum(c_sel, v_sel, diff - alpha_sel)
        return g, diff

    return step


@functools.partial(jax.jit, donate_argnums=(0,))
def add_grads(a, b):
    """Associative combine for the sync drain (comOp parity: vector add).

    The running accumulator ``a`` is donated: the drain's ``acc`` is dead the
    moment the next partial arrives, so the sum is built in one buffer.
    """
    return a + b


def make_trajectory_loss_eval(loss: str = "least_squares"):
    """jit (X, y, W_stack (S,d)) -> (S,) per-snapshot loss sums over a shard."""

    @jax.jit
    def eval_shard(X, y, W):
        R = mm_f32(X, W.T)  # (n, S); bf16 shards stay bf16 in the matmul
        if loss == "least_squares":
            E = R - y[:, None]
            return jnp.sum(E * E, axis=0)
        elif loss == "logistic":
            return jnp.sum(
                jnp.logaddexp(0.0, R) - y[:, None] * R, axis=0
            )
        else:
            raise ValueError(f"unknown loss {loss!r}")

    return _prof.wrap_dispatch(eval_shard, "kernel.dispatch", "trajectory_loss_eval")


def make_predict_step(loss: str = "least_squares"):
    """jit (X (n,d) f32, w (d,) f32) -> (n,) f32 predictions -- the serving
    tier's PREDICT kernel (serving/replica.py).

    least_squares serves the raw regression score ``X @ w``; logistic
    serves the positive-class probability ``sigmoid(X @ w)``.  One jitted
    executable per (loss, batch shape); replicas bucket batch sizes to
    powers of two so a mixed request stream compiles O(log n) variants,
    not one per request.
    """
    if loss not in ("least_squares", "logistic"):
        raise ValueError(f"unknown loss {loss!r}")

    @jax.jit
    def predict(X, w):
        z = mm_f32(X, w)
        if loss == "logistic":
            return jax.nn.sigmoid(z)
        return z

    return _prof.wrap_dispatch(predict, "kernel.dispatch", "predict_step")


# ------------------------------------------------- a dataset's programs
@dataclasses.dataclass(frozen=True)
class WorkerPrograms:
    """What :func:`worker_programs` built for one dataset: the programs the
    engine's solvers run on its shards, and what they cost.  Every program
    takes ``*shard.operands`` ahead of the model; nothing that holds this
    record asks how a shard is stored."""

    step: Callable
    evaluate: Callable
    #: ``(gamma, n=, shards=, rounds_per_call=)``: the fused rounds over
    #: the shards' operands (``make_fused_asgd_rounds`` / ``_saga_rounds``)
    fused_rounds: Callable
    #: which programs these are and what they walk, for every result's
    #: ``extras``
    extras: Mapping[str, object]
    #: by worker: the width its shard is read at (``task.dispatch``'s
    #: ``width=``; ``None`` on a dense shard); the non-zeros its step
    #: samples, the slots it gathers for them and the (column, product)
    #: pairs it sorts to add them into ``g``, on average (``()`` where
    #: the step reads whole rows of a dense shard)
    widths: Tuple[Optional[int], ...]
    step_nonzeros: Tuple[float, ...]
    step_walked: Tuple[float, ...]
    step_sorted: Tuple[float, ...]
    #: ``(shard)`` -> the counted flops of one step on it (utils/flops.py)
    task_flops: Callable
    #: ``(shard)`` -> what one evaluation call over it adds to a run's
    #: ``extras``: ``eval_blocks``, the gathers made and, over padded ELL,
    #: ``eval_slots`` (the blocks as STORED, a clamped last block counted
    #: whole) and ``eval_live_slots`` (at the width the evaluation reads)
    eval_account: Callable
    #: the model-sized rows one evaluation call stacks (``None``: every
    #: snapshot of the run), and the bytes of temporaries one fleet of
    #: steps in flight holds beyond the planner's headroom
    eval_stack_rows: Optional[int] = None
    workspace_bytes: int = 0
    #: ``()`` -> what a padded-ELL step's store did over this record's
    #: life, for a result's ``extras``: the shapes it loaded, built and
    #: failed to load (``None``: a step that stays on ``jit``)
    step_programs: Optional[Callable] = None
    # ``history=True``: ASAGA's own.  ``compacted`` says which PAYLOAD the
    # step returns, ``(diff, idx, valid, c_sel, v_sel)`` or ``(diff,
    # mask)``; ``table_delta`` takes it as the accept path spells it out,
    # and so does ``commit``, the compacted payload's (a ``(diff, mask)``
    # one is committed by ``saga_commit_history``, ONE jitted function);
    # ``table_mean_grad(*shard.operands[:-1], v)`` is ``X^T v`` over a
    # whole shard
    compacted: bool = False
    commit: Optional[Callable] = None
    table_delta: Optional[Callable] = None
    table_mean_grad: Optional[Callable] = None


def _padded_ell_account(shards, step, evaluate, batch_rate, d, live,
                        history=False):
    """A padded-ELL dataset's part of :class:`WorkerPrograms`.  A step's
    size: the rows its compaction holds, the slots of them as they are
    STORED (capacity x ELL width) and the slots it gathers and
    scatter-adds (capacity x the width read: equal where nothing was left
    out), each the LARGEST over the workers' shards (shards of one shape:
    every step's), and which program gathers the model and which adds the
    products into ``g`` (at every width read, where they differ); what the
    shards hold of the device and of the data, and how many step shapes
    they make.

    The account is the ASGD step's and ASAGA's alike (``history``): both
    pack one sample (:func:`_sampled_rows`) and read it at the live width,
    so ``live_slots_per_step`` is what either gathers and scatter-adds.
    They part in two places.  ASAGA's step reads its packed sample WHOLE
    (:func:`_sparse_saga_compacted` hands ``sparse_margins`` no walk), so
    what it walks is capacity x the width read also where ASGD's step
    would walk row tiles; and its result carries the packed sample to the
    updater: ``history_payload_bytes``, the largest ``(diff_sel, idx,
    valid, c_sel, v_sel)`` a result of this dataset holds beside ``g``."""
    stored = [s.shape[1] for s in shards]
    caps = [step.task_rows(s.size) for s in shards]
    widths = [_live_width(k, live) for k in stored]
    # the capacity x the width read, or what ASGD's ragged walk takes
    walked = [
        float(c * lw) if history else
        sparse_walked_slots(batch_rate, d, s.size, lw, s.row_lengths)
        for s, c, lw in zip(shards, caps, widths)]
    rows = max(s.size for s in shards)
    own = {}
    if history:
        # diff_sel (f32), idx (int32) and valid (the values' dtype) a
        # packed row; its columns and values a slot
        own["history_payload_bytes"] = max(
            c * (8 + s.vals.dtype.itemsize
                 + lw * (s.cols.dtype.itemsize + s.vals.dtype.itemsize))
            for s, c, lw in zip(shards, caps, widths))

    def eval_account(shard):
        n_rows, k = shard.shape
        n_blocks = evaluate.blocks(n_rows, k)
        walked_rows = n_blocks * evaluate.block_rows(n_rows, k)
        return {"eval_blocks": n_blocks, "eval_slots": walked_rows * k,
                "eval_live_slots": walked_rows * evaluate.width(k)}

    return dict(
        extras={
            "sparse_step_capacity": max(caps),
            "sampled_slots_per_step": max(
                c * k for c, k in zip(caps, stored)),
            "sparse_live_width": max(widths),
            "live_slots_per_step": max(
                c * lw for c, lw in zip(caps, widths)),
            "sparse_gather_path": "+".join(sorted({
                step.gather_path(rows, lw) for lw in set(widths)})),
            "sparse_scatter_path": "+".join(sorted({
                step.scatter_path(s.size, lw)
                for s, lw in zip(shards, widths)})),
            "sparse_width_min": min(widths),
            "sparse_width_max": max(widths),
            "sparse_step_shapes": len({(s.shape, s.device) for s in shards}),
            "sparse_stored_slots": sum(
                s.size * k for s, k in zip(shards, stored)),
            "sparse_nonzero_slots": sum(s.nnz for s in shards),
            # the largest share of its capacity x STORED width a
            # worker's step walks: 1.0 says the walk did not engage
            "walked_slots_share_max": max(
                wk / (c * k) for wk, c, k in zip(walked, caps, stored)),
            **own,
        },
        widths=tuple(widths),
        step_nonzeros=tuple(batch_rate * s.nnz for s in shards),
        step_walked=tuple(walked),
        step_sorted=tuple(
            float(step.sorted_pairs(s.size, lw))
            for s, lw in zip(shards, widths)),
        task_flops=lambda shard: _flops.sparse_task_flops(
            step.task_rows(shard.size), shard.shape[1]),
        eval_account=eval_account,
        eval_stack_rows=evaluate.snapshots_per_call,
        # each step's packed sample at its shard's width, columns and
        # values, the model values gathered for them, and the pair of
        # keys and products its sum sorts (five f32-sized arrays of
        # capacity x width; compiled for a described v5e, a 21,875 x
        # 16,384 webspam shard's one-shot step held 338 MB, PR 39; the
        # 16,406 x 16,384 one's holds 130 MB, 8.0 B a slot, since its
        # walked sample is one sorted list, PR 54: the sort works in the
        # sample's own buffers).  Nothing at 11 to 39 slots a row
        # (criteo: 116 MB a step; the pairs its sum by sorted segments
        # sorts lie in VMEM, PR 52); a sixth of a GB a step at thousands
        workspace_bytes=sum(20 * c * k for c, k in zip(caps, stored)),
        step_programs=lambda: {
            f"step_programs_{what}": n for what, n in step.counts().items()},
    )


def worker_programs(ds, batch_rate: float, loss: str = "least_squares",
                    history: bool = False) -> WorkerPrograms:
    """The programs of the engine's solvers over dataset ``ds``, built
    ONCE from the factories above, with what they cost: the ONE place
    that asks how ``ds`` stores its shards.  ``history``: ASAGA's step,
    with its commit and table delta, in place of ASGD's.

    A padded-ELL dataset's programs read each shard at the width its
    shape gives (``ds.checked_live_widths()``, which refuses a value
    beyond it): one step and one evaluation for every shard SHAPE (jit
    traces them by the arrays they are called with), shared by the shards
    that have it.  A dense dataset's shards have one width and dtype, so
    shard 0 and the draw's rate say which program the step is."""
    d = ds.d
    shards = [ds.shard(w) for w in range(ds.num_workers)]
    padded_ell = bool(getattr(ds, "is_sparse", False))
    live = ds.checked_live_widths() if padded_ell else None
    read = {"sparse_d": d, "live_width": live} if padded_ell else {}
    own = {}
    if history and padded_ell:
        step = make_sparse_saga_worker_step(batch_rate, d, live_width=live)
        own = dict(compacted=True, commit=make_sparse_saga_commit(),
                   table_delta=make_sparse_table_delta(d),
                   table_mean_grad=make_sparse_grad_sum(d))
    elif history:
        step = make_saga_worker_step(batch_rate)
        table_delta = make_saga_table_delta()

        def table_mean_grad(X, v):
            # the table delta's own executable, the whole slice as ``diff``
            return table_delta(X, v, jnp.ones(v.shape, jnp.float32),
                               jnp.zeros(v.shape, jnp.float32))

        own = dict(table_delta=table_delta, table_mean_grad=table_mean_grad)
    elif padded_ell:
        step = make_sparse_asgd_worker_step(
            batch_rate, d, loss, live_width=live)
    else:
        step = make_asgd_worker_step(batch_rate, loss)
    if history:
        fused_rounds = functools.partial(
            make_fused_saga_rounds, batch_rate=batch_rate, **read)
    else:
        fused_rounds = functools.partial(
            make_fused_asgd_rounds, batch_rate=batch_rate, loss=loss, **read)
    if padded_ell:
        evaluate = make_sparse_trajectory_loss_eval(loss, live_width=live)
        account = _padded_ell_account(
            shards, step, evaluate, batch_rate, d, live, history)
    else:
        evaluate = make_trajectory_loss_eval(loss)
        path = dense_step_path(shards[0].operands[0], batch_rate)
        account = dict(
            extras={
                "dense_step_path": path,
                # the share of a shard's lane tiles a step fetches,
                # expected over the draw (host arithmetic, no device
                # read); 1.0 where the step reads the whole shard
                "dense_tiles_read_share": dense_tiles_share(
                    batch_rate if path == "onepass_tiles" else None),
            },
            widths=(None,) * len(shards), step_nonzeros=(), step_walked=(),
            step_sorted=(),
            task_flops=lambda shard: _flops.dense_task_flops(
                step.task_rows(shard.size), shard.shape[1]),
            eval_account=lambda shard: {"eval_blocks": 1},
        )
    return WorkerPrograms(step=step, evaluate=evaluate,
                          fused_rounds=fused_rounds, **account, **own)


class DcnPrograms(NamedTuple):
    """What :func:`dcn_worker_programs` built for one worker process."""

    step: Callable
    evaluate: Callable
    #: a step over padded-ELL rows touches few columns: its gradient may
    #: ship sparse-encoded
    sparse_gradients: bool
    #: the mesh steps (``make_mesh_*_worker_step``) take dense rows only
    meshable: bool


def dcn_worker_programs(shards, d: int, batch_rate: float,
                        loss: str = "least_squares",
                        history: bool = False) -> DcnPrograms:
    """The programs of a DCN worker process (``parallel/ps_dcn.py``),
    which is handed shards and no dataset: how they are stored is read off
    what they are called with (three arrays: padded ELL).  Both programs
    read a shard at its stored width.  ``history``: the DCN-ASAGA step,
    whose sample the PS draws."""
    padded_ell = any(len(s.operands) == 3 for s in shards)
    if history:
        step = (make_saga_dcn_sparse_worker_step(d) if padded_ell
                else make_saga_dcn_worker_step())
    else:
        step = (make_sparse_asgd_worker_step(batch_rate, d) if padded_ell
                else make_asgd_worker_step(batch_rate, loss))
    evaluate = (make_sparse_trajectory_loss_eval() if padded_ell
                else make_trajectory_loss_eval(loss))
    return DcnPrograms(step, evaluate, sparse_gradients=padded_ell,
                       meshable=not padded_ell)


# (ASAGA's fold stands at the END of this file: a program's compile-cache
# key holds the line numbers of its source, so a function put among the
# others would send every executable defined below it back to the compiler
# on every machine, once: ROADMAP Speed 5(b))
def make_saga_apply_fold(
    gamma: float, batch_rate: float, n: int, num_workers: int
):
    """jit (w, alpha_bar, gs, deltas, m) -> (w', alpha_bar') -- the first
    ``m`` accepts of an ASAGA drain applied in ONE dispatch.

    ``gs`` and ``deltas`` are tuples of FIXED length (the engine's updater
    pads a short drain with one cached zero handle to ``num_workers``) and
    how many of their slots count is data (``m``, a device f32 scalar), so
    there is one executable whatever the drain's size, as
    :func:`make_asgd_apply_fold`.  Exactness: the serial accept path is
    ``w <- w - (gamma / parRecs) g_j - gamma ab; ab <- ab + delta_j / N``, a
    recurrence in ``(w, ab)`` whose step ``j`` reads ``ab`` as step
    ``j - 1`` left it and nothing else of the run.  The fold IS that
    recurrence: it calls what :func:`make_saga_apply` builds (looked up
    when the fold is built, so whatever stands in for the serial apply
    stands in here too: ``benchmark/check_saga.py --round-delta``), slot
    after slot in the serial order inside one program, and a slot past
    ``m`` selects both carries as they were.  What it may not do is move a
    step's ``delta`` in front of another step's ``w``: ``delta_j`` is
    computed by the updater against the table AS COMMIT ``j`` FINDS IT,
    before this dispatch, so the table's mean after the dispatch is
    ``ab'`` exactly as after ``m`` serial applies.  Where an accept reused
    its step's ``g`` for the delta the SAME handle rides in both tuples,
    so nothing in them is donated (nor may the padding's one buffer be,
    twice); ``alpha_bar`` is donated as in the serial apply, ``w`` never
    (an old handle is a model version).
    """
    apply_one = make_saga_apply(gamma, batch_rate, n, num_workers,
                                donate_g=False)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def apply_fold(w, alpha_bar, gs, deltas, m):
        live = jnp.arange(len(gs), dtype=jnp.float32) < m
        for keep, g, delta in zip(live, gs, deltas):
            w2, ab2 = apply_one(w, alpha_bar, g, delta)
            w = jnp.where(keep, w2, w)
            alpha_bar = jnp.where(keep, ab2, alpha_bar)
        return w, alpha_bar

    return _prof.wrap_dispatch(apply_fold, "kernel.dispatch", "saga_apply_fold")
