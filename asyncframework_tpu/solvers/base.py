"""Common solver configuration and result types.

``SolverConfig`` carries the reference drivers' 13 positional knobs
(``SparkASGDThread.scala:28-48``: path/file/d/N are data-loading concerns
handled by the data layer; the remaining 9 algorithmic knobs appear here
under their long names) plus TPU-build extensions (loss kind, device update
mode, calibration override).
"""

from __future__ import annotations

import math
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from asyncframework_tpu.checkpoint import CheckpointManager
from asyncframework_tpu.data.sharded import ShardedDataset


class DeadWorkerError(RuntimeError):
    """A synchronous drain can never complete: a cohort worker's executor
    is dead and nothing will replace it.  Carries the per-worker liveness
    diagnostic (who is dead, last-heartbeat ages, who already reported)."""


def dead_worker_diagnostic(pool, dead: Dict[int, float],
                           collected: Optional[set] = None) -> str:
    """Per-worker liveness table for the fail-fast abort message."""
    collected = collected or set()
    lines = [
        "synchronous drain cannot complete: "
        f"executor(s) {sorted(dead)} dead with no replacement"
    ]
    for wid, ex in sorted(pool.executors.items()):
        age = ex._clock.now_ms() - ex.last_heartbeat_ms
        lines.append(
            f"  wid {wid:3d}: {'DEAD' if not ex.alive else 'alive':5s} "
            f"last-heartbeat {age:8.0f}ms ago  busy={ex.busy!s:5s} "
            f"reported={'yes' if wid in collected else 'no'}"
        )
    return "\n".join(lines)


def collect_checked(ctx, waiter, timeout_s: float, pool=None,
                    cohort=None, dead_grace_s: float = 1.0,
                    collected: Optional[set] = None):
    """Blocking collect that surfaces a job abort instead of hanging --
    and, when given the executor ``pool``, fails FAST with a per-worker
    liveness diagnostic when a cohort executor dies and stays dead past
    ``dead_grace_s`` (nobody will ever deliver its result), instead of
    sitting out the full ``timeout_s``.  With the heartbeat monitor
    running, a killed executor is replaced within the grace window and
    its entry here self-clears; with monitoring off, this is the only
    thing standing between a SIGKILLed worker and a silent full-timeout
    hang of the synchronous barrier."""
    deadline = time.monotonic() + timeout_s
    dead_since: Dict[int, float] = {}
    while True:
        if waiter.failed is not None:
            raise RuntimeError("job aborted during drain") from waiter.failed
        try:
            return ctx.collect_all(timeout=0.1)
        except queue.Empty:
            now = time.monotonic()
            if pool is not None and not pool.closed:
                watch = cohort if cohort is not None else list(pool.executors)
                for wid in watch:
                    ex = pool.executors.get(wid)
                    if (ex is not None and not ex.alive
                            and not ex.shutdown_requested):
                        first = dead_since.setdefault(wid, now)
                        if now - first > dead_grace_s:
                            raise DeadWorkerError(dead_worker_diagnostic(
                                pool, dead_since, collected
                            ))
                    else:
                        # replaced (heartbeat path) or healthy again
                        dead_since.pop(wid, None)
            if now > deadline:
                raise TimeoutError("sync drain timed out")


#: an iteration budget that bounds nothing (what ``taw`` calls unbounded):
#: such a run ends at its deadline, and how many snapshots it keeps is not
#: known before it starts
UNBOUNDED_ITERATIONS = 2**31 - 1


def planned_snapshots(cfg: "SolverConfig") -> int:
    """The snapshots a run of ``cfg`` keeps, counted before it starts: the
    model at ``w = 0``, after update ``j * printer_freq + 1`` for every such
    update the iteration budget allows, and the final model.  Under a
    budget that bounds nothing only the two every run keeps are known."""
    if cfg.num_iterations >= UNBOUNDED_ITERATIONS:
        return 2
    return 2 + -(-cfg.num_iterations // max(1, cfg.printer_freq))


def planned_model_copies(cfg: "SolverConfig",
                         eval_stack_rows: Optional[int] = None) -> int:
    """The model-sized device buffers (``d`` f32 each) a run of ``cfg``
    may hold at once ON ONE CHIP, beside its shards: the live model; one
    result a worker, computed and not yet applied (a result is a DENSE ``d``-vector
    whatever its batch touched; behind an updater that has stalled up to
    two fleets more can queue, which the planner's headroom is for, not
    this count); one pinned model version a worker (a task
    holds the version it was handed until its result is back); the
    snapshots (:func:`planned_snapshots`); one evaluation call's stack of
    them (``eval_stack_rows``, the dataset's programs' own: eight rows
    over padded ELL; ``None``, all of them, over a dense shard); and
    the versioned store's ring where workers read stale versions.  What
    ``TrainResult.extras["model_copies_peak"]`` counts in a run, by the
    HANDLE.  Over several chips the count holds for EVERY chip: each holds
    a replica of the live model, a buffer of every pinned version and of
    every snapshot (a version is one handle with a buffer a chip,
    ``engine_loop.ModelReplicas``) and receives every result, so
    :func:`check_hbm_plan` charges the whole count to each device, the
    one with the most shard bytes included (until the model lived on
    every chip only the driver's chip held it all, and the plan was
    pessimistic for the others)."""
    snapshots = planned_snapshots(cfg)
    ring = cfg.max_live_versions if cfg.stale_read_offset is not None else 0
    stack = snapshots if eval_stack_rows is None else eval_stack_rows
    return 1 + 2 * cfg.num_workers + snapshots + stack + ring


def check_hbm_plan(X, cfg: "SolverConfig", devices, history_table: bool,
                   programs=None) -> None:
    """Consult the HBM planner before committing to a run (VERDICT item 10):
    host arrays are planned from shape BEFORE placement (they are sharded
    dense: ``programs`` is None); a pre-built dataset has its actual
    residency measured and comes with its ``programs``
    (``ops.steps.worker_programs``), which say what an evaluation call
    stacks and what a fleet of steps holds in temporaries; the engine's
    model-sized state is :func:`planned_model_copies`, counted a chip (over
    several chips every one holds it), free at 3 kB a copy
    and a third of the chip at 219 MB.  Raises ``MemoryError``
    with the planner's accounting when the budget is oversubscribed."""
    from asyncframework_tpu.utils.hbm import plan_for_run

    num_devices = max(len(set(devices)), 1)
    target = (X.shape[0], X.shape[1]) if isinstance(X, np.ndarray) else X
    plan_for_run(
        target,
        cfg.num_workers,
        num_devices,
        history_table=history_table,
        model_versions=planned_model_copies(
            cfg, getattr(programs, "eval_stack_rows", None)),
        budget_bytes=cfg.hbm_budget_bytes,
        workspace_bytes=getattr(programs, "workspace_bytes", 0),
    ).require_fits()


def resolve_dataset(X, y, num_workers: int, devices):
    """Accept host arrays (sharded here) or a pre-built dataset
    (:class:`ShardedDataset` or
    :class:`~asyncframework_tpu.data.sparse.SparseShardedDataset`);
    validate consistency with the solver's setup."""
    from asyncframework_tpu.data.sparse import SparseShardedDataset

    if isinstance(X, (ShardedDataset, SparseShardedDataset)):
        if y is not None:
            raise ValueError(
                "y must be None when passing a pre-built dataset "
                "(its labels are already resident on device)"
            )
        if X.num_workers != num_workers:
            raise ValueError(
                f"dataset is sharded for {X.num_workers} workers but the "
                f"solver is configured for {num_workers}"
            )
        for wid in range(num_workers):
            expect = devices[wid % len(devices)]
            actual = X.shard(wid).device
            if actual != expect:
                raise ValueError(
                    f"shard {wid} lives on {actual} but the solver will "
                    f"dispatch worker {wid} to {expect}; rebuild the dataset "
                    f"with the solver's device list"
                )
        return X
    return ShardedDataset(X, y, num_workers, devices)


def run_fused_plan(make_runner, carry, total_rounds: int, nw: int,
                   printer_freq: int, w_of, chunk_cap: int = 16):
    """Shared chunk/warm-up/snapshot/timing machinery of the fused
    device-resident solvers (ASGD.run_fused / ASAGA.run_fused) -- ONE
    definition so their benchmark numbers stay comparable.

    ``make_runner(length)`` builds a jitted callable ``carry -> (carry,
    W_snap)`` running ``length`` rounds; ``w_of(carry)`` extracts the model
    handle.  The full-chunk and remainder executables are BOTH warmed and
    **fenced** (``jax.block_until_ready``) before the clock starts --
    unfenced warm-up dispatches would still be executing at ``start_wall``
    and serialize the first timed chunk behind them, understating the
    fused rate.  Returns ``(carry, snapshots, start_wall, done_rounds)``;
    the caller fences the final model (``np.asarray``) before taking
    elapsed, as everywhere else.
    """
    import jax as _jax

    chunk = min(chunk_cap, total_rounds)
    full, rem = divmod(total_rounds, chunk)
    runner = make_runner(chunk)
    tail = make_runner(rem) if rem else None
    _jax.block_until_ready(runner(carry))
    if tail is not None:
        _jax.block_until_ready(tail(carry))
    start_wall = time.monotonic()
    snapshots: List[Tuple[float, object]] = [(0.0, w_of(carry))]
    snap_every = max(1, printer_freq // nw)
    done = 0
    plan = [(runner, chunk)] * full + ([(tail, rem)] if rem else [])
    for r, length in plan:
        carry, W_snap = r(carry)
        # chunk timestamps are dispatch-side; the caller's final fence
        # keeps elapsed honest
        t_ms = (time.monotonic() - start_wall) * 1e3
        for j in range(0, length, snap_every):
            snapshots.append((t_ms, W_snap[j]))
        done += length
    return carry, snapshots, start_wall, done


class SolverCheckpointer:
    """Shared checkpoint plumbing for the async solvers.

    Owns the manager, the compatibility metadata, the save-cadence decision,
    and the restore-with-validation step, so ASGD and ASAGA differ only in
    *which* state fields they save (ASAGA adds the history table).
    """

    def __init__(self, cfg: "SolverConfig", solver: str, d: int, n: int):
        self.cfg = cfg
        self.meta = {
            "solver": solver, "num_workers": cfg.num_workers, "d": d, "n": n
        }
        self.mgr = (
            CheckpointManager(cfg.checkpoint_dir, cfg.checkpoint_keep)
            if cfg.checkpoint_dir
            else None
        )

    @property
    def enabled(self) -> bool:
        return self.mgr is not None

    def restore(self) -> Optional[Dict]:
        """Latest checkpoint, validated against this run; None = cold start."""
        if self.mgr is None:
            return None
        ck = self.mgr.restore_latest_or_none()
        if ck is not None:
            validate_resume(ck.get("meta", {}), **self.meta)
        return ck

    def should_save(self, k: int) -> bool:
        return (
            self.mgr is not None
            and self.cfg.checkpoint_freq > 0
            and k % self.cfg.checkpoint_freq == 0
        )

    def should_save_range(self, k_old: int, k_new: int) -> bool:
        """True when any k in (k_old, k_new] hits the cadence -- a batched
        drain may jump OVER a checkpoint boundary and must still save."""
        freq = self.cfg.checkpoint_freq
        return (
            self.mgr is not None
            and freq > 0
            and k_new // freq > k_old // freq
        )

    def save(self, k: int, **state) -> None:
        self.mgr.save(k, {**state, "k": k, "meta": self.meta})


def validate_resume(meta: Dict, **expect) -> None:
    """Fail fast when a checkpoint does not match the resuming run.

    A checkpoint written under a different worker count / dataset shape /
    solver would otherwise crash deep in the training loop (missing worker
    ids, wrong history-slice sizes) or silently resume the wrong model.
    """
    for key, want in expect.items():
        got = meta.get(key)
        if got != want:
            raise ValueError(
                f"checkpoint incompatible with this run: {key}={got!r} "
                f"in checkpoint but {want!r} configured"
            )


@dataclass
class SolverConfig:
    num_workers: int = 8          # [num partitions]
    num_iterations: int = 1000    # [num iterations] (accepted updates / rounds)
    gamma: float = 0.1            # [step size]
    taw: int = 2**31 - 1          # [taw] staleness bound
    batch_rate: float = 0.1       # [batch rate] Bernoulli b
    bucket_ratio: float = 0.5     # [bucket ratio] cohort threshold
    printer_freq: int = 100       # [printer freq] trajectory snapshot period
    coeff: float = 0.0            # [coeff] delay intensity; -1 = cloud mode
    seed: int = 42                # [seed]
    loss: str = "least_squares"
    # TPU-build extensions
    calibration_iters: Optional[int] = None  # default 100 * num_workers
    collect_timeout_s: float = 0.05
    run_timeout_s: float = 600.0
    # NO READER (PR 31).  ASGD's updater folds whatever is queued when it
    # wakes into ONE device dispatch and reads no knob for it: on the v5e
    # a fold over a tuple of handles costs 0.34 ms of host time at any
    # drain size, serial applies 0.23 ms each, the stacked form this field
    # once selected 9.7 ms (PERF.md section 6, PR 31).  The field, the
    # key async.drain.batch (conf.py) and its cli.py mapping wait for
    # tests/benchmark/test_bench_harness.py's `cfg.drain_batch == 1` to
    # go (a benchmark PR's), then for a simplicity PR (ROADMAP Design 2).
    drain_batch: int = 1
    # DCN data plane (parallel/ps_dcn.py).  pull_mode: None = resolve from
    # conf async.pull.mode ('full' ships the whole model per PULL,
    # byte-identical legacy wire; 'delta' negotiates NOT_MODIFIED /
    # byte-exact XOR delta / full per pull).  push_merge: None = resolve
    # from conf async.push.merge (max pushes the PS coalesces into one
    # fused device apply at lock acquisition; 1 = classic serial path).
    pull_mode: Optional[str] = None
    push_merge: Optional[int] = None
    # push_codec: None = resolve from conf async.codec.push ('off' ships
    # raw f32 gradients, byte-identical legacy wire; 'fp16'/'int8'
    # quantize dense ASGD pushes with per-worker error-feedback residual
    # accumulation -- net/wirecodec.py; ASAGA and sparse-encoded pushes
    # always ship exact).
    push_codec: Optional[str] = None
    # pipeline_depth: None = resolve from conf async.pipeline.depth
    # (0 = the classic serial worker loop, byte- and step-identical;
    # >= 1 = prefetched pulls on a second connection + a bounded
    # in-flight push sender with at most this many unacked pushes).
    pipeline_depth: Optional[int] = None
    # mesh_devices: None = resolve from conf async.mesh.devices (0 = the
    # classic single-device worker gradient step, byte- and step-
    # identical; >= 2 = each DCN worker computes its mini-batch gradient
    # batch-parallel over a local dp mesh of this many chips -- shard
    # rows resident in HBM across the run, per-device partials psum-
    # reduced locally, ONE fused gradient per PUSH, wire unchanged).
    # Clamped to the rig's device count; degrades to the serial path
    # (logged) when fewer than 2 devices result or the shard is sparse.
    mesh_devices: Optional[int] = None
    # checkpoint/resume (SURVEY.md section 5: a capability the reference lacks)
    checkpoint_dir: Optional[str] = None  # None = checkpointing off
    checkpoint_freq: int = 0              # accepted updates between saves; 0 = off
    checkpoint_keep: int = 3
    # observability (EventLoggingListener / MetricsSystem parity; None = off)
    # live dashboard (SparkUI.scala:39 parity): HTTP port serving run state
    # DURING the run; 0 = ephemeral (metrics/live.py); None = off
    ui_port: Optional[int] = None
    event_log: Optional[str] = None       # JSONL (.gz ok) event log path
    metrics_csv: Optional[str] = None     # CsvSink path
    metrics_jsonl: Optional[str] = None   # JsonlSink path
    metrics_period_s: float = 1.0
    # distributed tracing (metrics/trace.py): per-update sampling rate for
    # lifecycle spans (compute / merge.queue / merge.apply here; the DCN
    # path adds the wire stages).  None (default) = OFF for the in-process
    # engine -- its updater thread is the measured hot path, so tracing it
    # is explicit opt-in (--trace-sample / --conf async.trace.sample); the
    # async.trace.sample conf default (1/64) governs the DCN plane, whose
    # stages are network-dominated.
    trace_sample: Optional[float] = None
    # convergence telemetry (metrics/timeseries.py): every Nth update per
    # logical DCN worker evaluates its shard's mean loss + grad norm and
    # piggybacks the sample on the next PUSH header (``cv``) for the PS's
    # loss-vs-wallclock / loss-vs-version curves.  None = resolve from
    # conf async.convergence.sample (default 0 = off: one extra jitted
    # eval per sample, and byte-identity suites compare exact wires);
    # async-cluster flips it to 16.  In-process solvers fold their
    # post-hoc trajectory instead -- this knob is DCN-worker-side only.
    conv_sample: Optional[int] = None
    # failure detection / elastic recovery (HeartbeatReceiver parity)
    heartbeat: bool = True                # liveness monitoring during the run
    heartbeat_timeout_ms: float = 2000.0
    heartbeat_interval_s: float = 0.25
    max_slot_failures: int = 2            # repeated deaths => re-home the shard
    # speculative execution (TaskSetManager.checkSpeculatableTasks parity)
    speculation: bool = False
    speculation_quantile: float = 0.75
    speculation_multiplier: float = 1.5
    speculation_min_ms: float = 100.0
    # dynamic executor allocation (ExecutorAllocationManager.scala:82
    # parity): sibling host threads added to backlogged slots, retired idle
    dynamic_allocation: bool = False
    allocation_max_extra: int = 1
    allocation_backlog_threshold: int = 2
    allocation_idle_timeout_s: float = 1.0
    # stale-read experiment (ASYNCbroadcast.value(index) parity): workers
    # read model version (latest - offset) from a VersionedModelStore
    stale_read_offset: Optional[int] = None
    max_live_versions: int = 4
    # HBM budget consulted before placement; None = query the device
    hbm_budget_bytes: Optional[int] = None

    def effective_calibration_iters(self) -> int:
        if self.calibration_iters is not None:
            return self.calibration_iters
        return 100 * self.num_workers

    @property
    def bucket_threshold(self) -> int:
        return math.floor(self.num_workers * self.bucket_ratio)


@dataclass
class TrainResult:
    """What a driver run produces (the reference prints these; we return them).

    ``trajectory`` is the optVars analog evaluated post-hoc in one pass:
    ``(wall_ms_since_start, objective)`` where objective is the mean loss over
    the full dataset.
    """

    final_w: np.ndarray
    trajectory: List[Tuple[float, float]]
    elapsed_s: float
    accepted: int = 0
    dropped: int = 0
    rounds: int = 0
    max_staleness: int = 0
    avg_delay_ms: float = 0.0
    updates_per_sec: float = 0.0
    # counted worker-gradient flops (utils/flops.py model; excludes the
    # post-hoc trajectory evaluation) -- the MFU numerator
    total_flops: float = 0.0
    #: per worker, the milliseconds between a result of its and the submit
    #: that took it again (``Occupancy``: a traced engine run; else empty)
    waiting_time_ms: Dict[int, float] = field(default_factory=dict)
    extras: Dict[str, object] = field(default_factory=dict)
    #: accepted updates behind every ``trajectory`` entry (0 for ``w = 0``,
    #: ``accepted`` for the final model); empty where a run mode does not
    #: record it
    snapshot_updates: List[int] = field(default_factory=list)
    #: staleness (model versions) -> count, over EVERY result the server
    #: merged: sums to ``accepted + dropped``
    staleness_hist: Dict[int, int] = field(default_factory=dict)

    @property
    def final_objective(self) -> float:
        return self.trajectory[-1][1] if self.trajectory else float("nan")


class WaitingTimeTable:
    """When each worker's task was submitted, and from that the task time
    the delay calibrator reads.

    Parity: ``SubmitJobTime`` (``SparkASGDThread.scala:112-115,328-335``).
    The reference's ``WaitingTime`` (a worker's idle gaps) is kept where
    the gap begins, at the worker's result:
    ``solvers/instrumentation.py: Occupancy``, which fills
    ``TrainResult.waiting_time_ms`` in a traced run.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.submit_ms: Dict[int, float] = {}

    def on_submit(self, worker_ids, now_ms: float) -> None:
        with self._lock:
            for wid in worker_ids:
                self.submit_ms[wid] = now_ms

    def on_finish(self, worker_id: int, now_ms: float) -> float:
        """(finish - submit), for delay calibration."""
        with self._lock:
            return now_ms - self.submit_ms.get(worker_id, now_ms)


class DelayCalibrator:
    """Average-delay measurement over the warm-up phase.

    Parity: ``culTime``/``culCount`` accumulation while ``k < 100*numPart``
    and the one-shot ``avgDelay = culTime/culCount``
    (``SparkASGDThread.scala:174-183,244-249``).
    """

    def __init__(self, calibration_iters: int):
        self._iters = calibration_iters
        self._cul_time = 0.0
        self._cul_count = 0
        self._lock = threading.Lock()
        self.avg_delay_ms = 0.0
        self.calibrated = False

    def record(self, k: int, task_ms: float) -> None:
        with self._lock:
            if k < self._iters:
                self._cul_time += task_ms
                self._cul_count += 1

    def maybe_finalize(self, k: int) -> bool:
        """Returns True the single time calibration completes."""
        with self._lock:
            if not self.calibrated and k > self._iters and self._cul_count > 0:
                self.avg_delay_ms = self._cul_time / self._cul_count
                self.calibrated = True
                return True
            return False
