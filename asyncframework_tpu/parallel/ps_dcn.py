"""Asynchronous parameter server across OS processes (the DCN channel).

Parity: the reference's whole point is async gradient flow from REMOTE
workers to the driver -- executor processes push task results over Netty
RPC to the driver's result queue
(``CoarseGrainedSchedulerBackend.scala:239-307``,
``CoarseGrainedExecutorBackend.scala:92``), where the updater thread applies
the tau-filter and gamma-schedule.  This module is that capability for the
TPU build: a **parameter-server process** owning the model on its device,
and **worker processes** owning data shards on theirs, joined by a thin
length-prefixed TCP protocol (the Netty-RPC analog; deliberately NOT
``jax.distributed`` collectives -- XLA collectives are lockstep SPMD, and
bounded-staleness asynchrony is precisely the regime where lockstep is
wrong.  Spark's channel is an RPC mesh for the same reason).

Semantics preserved from the single-process engine (solvers/asgd.py):

- logical clock = number of merged gradients; a model handed to a worker is
  stamped with the clock at send time; staleness at merge = clock - stamp;
  accept iff ``staleness <= taw`` else drop (worker is re-served either way)
  -- ``SparkASGDThread.scala:169,199-202``.
- accept applies ``w -= gamma/sqrt(k/P+1)/parRecs * g`` on the PS device via
  the SAME jitted ``make_asgd_apply`` executable the single-process updater
  uses.
- partial-barrier cohorts: with ``bucket_ratio > 0`` the PS releases PULL
  requests in waves -- it holds arriving pulls until
  ``floor(P * bucket_ratio)`` workers are simultaneously waiting, then
  serves all of them the same model version (``ASYNCbarrier`` +
  ``bucketRatio`` wait loop, ``SparkASGDThread.scala:230-234,282-283``).
- straggler injection: workers apply the DelayModel locally after the PS
  finishes calibration and broadcasts the measured average task time
  (``SparkASGDThread.scala:121-138,244-249``).

Wire protocol (one JSON header line + optional raw f32/npz payload, length
prefixed): PULL -> MODEL(k, w) | PUSH(ts, g) -> ACK(accepted) |
EVAL(W stack) -> LOSSES | DONE.  The PS cannot evaluate the loss trajectory
itself (it holds no data), so at end-of-run each worker scores the snapshot
stack against its shards and the PS sums -- the distributed analog of
``optVars`` evaluation (``SparkASGDThread.scala:386-401``).

Extensions past the ASGD-dense core:

- **ASAGA** (``algo="asaga"``): the PS owns the per-sample scalar-history
  table and the sampling (``ScalarMap`` + ``sampledMap``,
  ``SparkASAGAThread.scala:114,280-294``).  PULL carries the worker's shard
  size; MODEL ships capacity-padded ``(idx, alpha[idx])`` with the model;
  PUSH returns the gradient plus candidate scalars, which the PS commits
  only on accept (the driver-controlled ScalarMap merge) before the
  three-term update ``w -= gamma*(g/parRecs + alpha_bar)``,
  ``alpha_bar += g/N`` (``:210-213``).
- **Sparse gradients** (``enc="sparse"``): rcv1-class pushes ship
  ``(idx u32, val f32)`` pairs when that beats the dense ``d*4`` bytes; the
  PS scatters into dense before its (dense) apply.  Workers decide per push
  -- a near-dense gradient goes dense.

Data-plane throughput overhaul (version-cached replies, delta pulls,
vectored framing, batched apply):

- **Version-cached encoded replies**: the PS serializes the model ONCE per
  version (host array + payload bytes + CRC); an entire cohort pull of an
  unchanged version is a dict lookup plus a vectored socket write (the
  backing array is float32 -- the old per-pull ``astype(...).tobytes()``
  copy is gone).
- **Version-gated delta pulls** (``async.pull.mode=delta``): workers send
  ``have=<ts>``; the PS answers NOT_MODIFIED (zero model payload -- common
  under wave gating and straggler re-pulls), a byte-exact XOR sparse delta
  against a recent cached version (``net/wiredelta.py``,
  ``async.pull.delta.versions``), or the full model, whichever is
  smallest.  Every non-full reply carries the current version's CRC32; a
  client-side mismatch or basis-cache miss falls back to a full pull --
  the path can degrade to the legacy wire, never to a wrong model.  A
  pull WITHOUT ``have`` gets the legacy reply, byte-identical.
- **Batched gradient apply** (``async.push.merge``): pushes pending at
  model-lock acquisition coalesce into ONE fused device apply
  (``ops/steps.make_*_apply_merge`` -- a ``lax.scan`` over the serial
  apply expression, bit-identical to one-dispatch-per-push), with
  per-push accept/reject, dedup, and trace spans preserved per item.

Pipelined update loop (``async.pipeline.depth``):

- **Lock-free PULL serving**: the PS publishes a per-version
  :class:`_ModelSnap` ``(ts, host array, payload bytes, CRC)`` via atomic
  reference swap; ``_handle_pull`` serves full/NOT_MODIFIED/delta replies
  from the published snapshot without ever touching the model lock (only
  the wave gate and small bookkeeping locks remain on the pull path), so
  a cohort pull never queues behind a merge drain and vice versa.  The
  debug lock watchdog (``net/lockwatch.py``, ``async.debug.lockwatch``)
  asserts the claim at the frame choke points.
- **Prefetched pulls + decoupled pushes** (worker side, depth >= 1): a
  prefetch thread on a SECOND PSClient connection pulls model v(k+1)
  while step k computes (delta-mode ``have=`` pulls make an unchanged
  version nearly free), and pushes are handed to a bounded in-flight
  sender so the next compute starts before the push ACK returns.
  Staleness stays bounded: the PS's taw admission prices the extra
  in-flight steps, and a taw REJECTION makes the worker discard its
  prefetched model and re-pull fresh (counted as a stale-prefetch
  discard).  Exactly-once push semantics ride the session/dedup
  machinery unchanged; adoption orders and RELEASED/DONE work on both
  connections.  Depth 0 (the default outside ``async-cluster``) is the
  classic serial loop, byte- and step-identical.
"""

from __future__ import annotations

import io
import json
import os
import socket
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from asyncframework_tpu.metrics import flightrec as _flight
from asyncframework_tpu.metrics import profiler as _prof
from asyncframework_tpu.metrics import trace as _trace
from asyncframework_tpu.net import ClientSession, DedupWindow, RetryPolicy
from asyncframework_tpu.net import frame as _frame
from asyncframework_tpu.net import shmring as _shmring
from asyncframework_tpu.net import wirecodec, wiredelta
from asyncframework_tpu.parallel import supervisor as supervisor_mod
from asyncframework_tpu.parallel.supervisor import ElasticSupervisor

# ------------------------------------------------------------------ framing
# The framing moved to net/frame.py (one choke point for the whole control
# + data plane, with fault-injection hooks); these aliases keep the
# historical import site alive for everything that learned it here.
_send_msg = _frame.send_msg
_recv_exact = _frame.recv_exact
_recv_msg = _frame.recv_msg


# ------------------------------------------------- pipeline counters
# Process-global pipelined-loop totals (live UI "pipeline" section).  The
# worker loops accumulate locally (one _PipelineStats per worker process
# run) and ship deltas on PUSH/BYE headers; the PS folds them here -- so
# the counters land in the process that serves the dashboard whether the
# workers are threads in this process or real OS processes across a DCN.
_pl_lock = threading.Lock()
_pl_totals: Dict[str, int] = {}


def pipeline_totals() -> Dict[str, int]:
    """Pipelined update-loop counters: prefetch_hits (model was already
    waiting when the loop asked), prefetch_waits (the loop blocked on the
    prefetch), stale_discards (prefetched model thrown away after a taw
    rejection), pushes_async (pushes sent by the decoupled sender),
    push_errors (pushes whose whole retry budget was spent),
    inflight_max (max unacked pushes observed)."""
    with _pl_lock:
        return dict(_pl_totals)


def reset_pipeline_totals() -> None:
    """Zero the process-global pipeline counters (per-run isolation; see
    ``asyncframework_tpu.metrics.reset_totals``)."""
    with _pl_lock:
        _pl_totals.clear()


def _pl_fold(delta: Dict[str, int]) -> None:
    """Fold a wire-shipped counter delta; ``inflight_max`` is a high-water
    mark (max-merged), everything else a monotone count."""
    if not delta:
        return
    with _pl_lock:
        for k, v in delta.items():
            try:
                v = int(v)
            except (TypeError, ValueError):
                continue
            if k == "inflight_max":
                if v > _pl_totals.get(k, 0):
                    _pl_totals[k] = v
            else:
                _pl_totals[k] = _pl_totals.get(k, 0) + v


def _cv_fold(wire, clock: int = 0,
             wall_ms: Optional[float] = None) -> None:
    """Fold piggybacked convergence samples (the ``cv`` PUSH/BYE header
    entry: ``[[version, loss, grad_norm], ...]``) into the process-global
    :class:`~asyncframework_tpu.metrics.timeseries.ConvergenceHistory`,
    stamped with the PS run clock's wallclock and the staleness the PS
    observes (merge clock minus the sample's model version).  Dedup'd
    PUSH retries never reach the handlers, so a sample folds exactly
    once -- the span/pipeline-counter discipline."""
    if not wire:
        return
    from asyncframework_tpu.metrics import timeseries as _ts

    conv = _ts.convergence()
    now_ms = wall_ms if wall_ms is not None else time.time() * 1e3
    for item in wire:
        try:
            version = int(item[0])
            loss = item[1]
            gnorm = item[2] if len(item) > 2 else None
        except (TypeError, ValueError, IndexError):
            continue  # junk from the wire must not kill the handler
        conv.add(now_ms, version, loss=loss, grad_norm=gnorm,
                 staleness=max(0, clock - version) if clock else None)


class _PipelineStats:
    """Per-worker-process pipeline counters, shipped to the PS as deltas
    on PUSH headers (``pl``) and on BYE -- the same piggyback discipline
    as trace spans, so the PS-side live UI sees them even when the worker
    is a separate OS process.  A delta taken for a push that terminally
    fails is merged back so the counts ride the next attempt."""

    __slots__ = ("_lock", "_counts", "_shipped_inflight_max")

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._shipped_inflight_max = 0

    def bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + n

    def high_water(self, key: str, v: int) -> None:
        with self._lock:
            if v > self._counts.get(key, 0):
                self._counts[key] = v

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def take_wire(self) -> Dict[str, int]:
        """Unshipped counter delta (empty dict = nothing to ship, no
        header field, no wire bytes)."""
        with self._lock:
            out = {k: v for k, v in self._counts.items()
                   if k != "inflight_max" and v}
            hw = self._counts.get("inflight_max", 0)
            if hw > self._shipped_inflight_max:
                out["inflight_max"] = hw
                self._shipped_inflight_max = hw
            for k in out:
                if k != "inflight_max":
                    self._counts[k] = 0
            return out

    def merge_back(self, delta: Dict[str, int]) -> None:
        with self._lock:
            for k, v in delta.items():
                if k == "inflight_max":
                    continue  # the high-water mark survives locally
                self._counts[k] = self._counts.get(k, 0) + v


class _ModelSnap:
    """One published model version: the host float32 array, its serialized
    payload bytes, and the CRC32 integrity stamp -- immutable once built,
    swapped in by atomic reference assignment so ``_handle_pull`` can
    serve any reply shape without the model lock."""

    __slots__ = ("ts", "w_host", "wire", "crc", "gen")

    def __init__(self, ts: int, w_host: np.ndarray, wire: bytes, crc: int,
                 gen: int):
        self.ts = ts
        self.w_host = w_host
        self.wire = wire
        self.crc = crc
        #: model GENERATION the build basis carried (bumped on every
        #: accepted push): the send-time clock re-stamp in _handle_pull
        #: is allowed only while the generation is unchanged -- dropped
        #: pushes tick the clock but not the generation, accepted ones
        #: tick both, so gen equality proves "same bytes, newer clock"
        self.gen = gen


class WaitDone:
    """Result of :meth:`ParameterServer.wait_done`: truthy iff the run
    finished; otherwise carries the per-worker progress diagnostic (old
    callers that only truth-test keep working, new callers can print WHY
    the run did not finish)."""

    __slots__ = ("done", "diagnostic")

    def __init__(self, done: bool, diagnostic: Optional[str]):
        self.done = bool(done)
        self.diagnostic = diagnostic

    def __bool__(self) -> bool:
        return self.done

    def __repr__(self) -> str:
        return "WaitDone(done)" if self.done else (
            f"WaitDone(not done)\n{self.diagnostic}"
        )

    def __str__(self) -> str:
        return "done" if self.done else (self.diagnostic or "not done")


class _PendingPush:
    """One decoded PUSH waiting in the PS merge queue.

    The handler thread decodes the payload OUTSIDE the model lock, enqueues
    this record, and whoever holds the lock next drains every pending push
    into one fused device apply (``_drain_merge_locked``) -- per-push
    accept/reject, dedup, calibration, and trace bookkeeping all happen
    per item in FIFO order, exactly as the serial path did; only the
    device dispatch is coalesced."""

    __slots__ = ("wid", "ts", "g_host", "diff", "header", "payload_len",
                 "tc", "t_queue0", "done", "ack", "accepted", "staleness",
                 "task_ms", "t_apply0", "t_done", "k_at_merge",
                 "do_snapshot", "damp")

    def __init__(self, wid: int, ts: int, g_host, diff, header: dict,
                 payload_len: int, tc, t_queue0: float):
        self.wid, self.ts = wid, ts
        self.g_host, self.diff = g_host, diff
        self.header, self.payload_len = header, payload_len
        self.tc, self.t_queue0 = tc, t_queue0
        self.done = False
        self.ack: dict = {}
        self.accepted = False
        self.staleness = 0
        self.task_ms = 0.0
        self.t_apply0 = 0.0
        self.t_done = 0.0
        self.k_at_merge = 0
        self.do_snapshot = False
        # delay-adaptive step-DAMP factor, decided per item at drain
        # time from the installed CTRL law (1.0 = undamped, the only
        # value with control off -- bit-identical legacy apply)
        self.damp = 1.0


# ----------------------------------------------------------------- PS side
class ParameterServer:
    """Driver-side PS: accept worker connections, run the updater semantics.

    One handler thread per worker connection (the reference's RPC dispatcher
    threads); the model/clock live behind one lock (single-writer updater
    discipline -- the TPU build's answer to the reference's benign races,
    SURVEY.md section 5).
    """

    def __init__(self, cfg, d: int, n: int, device=None, host: str = "0.0.0.0",
                 port: int = 0, algo: str = "asgd",
                 checkpoint_path: Optional[str] = None,
                 supervisor: Optional[ElasticSupervisor] = None,
                 bus=None, shard_map=None, shard_index: int = 0,
                 epoch: Optional[int] = None, shard_epochs=None,
                 standby: bool = False):
        import jax
        import jax.numpy as jnp

        from asyncframework_tpu.ops import steps

        if algo not in ("asgd", "asaga"):
            raise ValueError(f"unknown PS algo {algo!r}")
        self.cfg = cfg
        self.d, self.n = d, n
        self.algo = algo
        # fencing epoch (async.fence.enabled): 0 = fencing off, the
        # byte-identical legacy wire (no ep header keys anywhere).  > 0 =
        # this server incarnation's minted epoch; every PULL/PUSH/
        # SUBSCRIBE stamped with a DIFFERENT epoch is answered
        # REJECT_FENCED (admission in _fence_reject), so a deposed client
        # replaying buffered pushes -- or any op routed at a deposed
        # incarnation of this range -- can never double-apply against the
        # current owner's state.  Restoring from checkpoint bumps past
        # the persisted epoch (every incarnation is a new epoch), and a
        # controller (shardgroup.ShardGroup) passes an explicit epoch
        # that already counts its lease-expiry fences.
        if epoch is None:
            from asyncframework_tpu.conf import FENCE_ENABLED
            from asyncframework_tpu.conf import global_conf as _gc

            epoch = 1 if _gc().get(FENCE_ENABLED) else 0
        self.epoch = int(epoch)
        #: per-shard epochs of the whole group (index-aligned with
        #: shard_map); installed by SETMAP / the launcher so WELCOME can
        #: hand workers the full epoch vector next to the map
        self.shard_epochs = ([int(e) for e in shard_epochs]
                             if shard_epochs else None)
        #: highest foreign epoch seen ABOVE ours: once a client proves a
        #: successor exists for this range, this incarnation is a zombie
        #: and refuses every stamped op (even same-epoch ones) -- "never
        #: mutate or serve a range it no longer owns"
        self._fenced_above = 0
        self.fenced_rejects = 0
        # sharded PS group (parallel/shardgroup.py): when this server is one
        # range of a shard group, ``shard_map`` is the group's wire map
        # (per-shard [host, port, lo, hi]) and ``shard_index`` names this
        # server's range.  The map is what HELLO's WELCOME reply hands
        # workers so they resolve the group with no side channel; it may
        # also be installed after construction (SETMAP, or attribute
        # assignment before start).  None/0 = the classic single PS --
        # WELCOME omits the key and the wire stays byte-identical.
        self.shard_map = [list(e) for e in shard_map] if shard_map else None
        self.shard_index = int(shard_index)
        # hot-standby replication (parallel/replication.py, ISSUE 13).
        # standby=True: this server is a WARM STANDBY -- it refuses the
        # training plane (PULL/PUSH answer ERR; it is not in the shard
        # map), applies its primary's replicated merge batches
        # (REPL_SYNC bootstrap + REPL_APPEND stream) through the same
        # jitted kernel, and serves SUBSCRIBE/SHARDMAP reads from the
        # mirrored snapshot (staleness priced by replication lag).  A
        # PROMOTE order flips it to range primary under the minted
        # epoch.  standby_map names every shard's standby endpoint
        # ([host, port] | None per range, installed via SETMAP or the
        # launcher); a PRIMARY whose own entry is set runs a
        # ReplicationStream (self.repl) to it.
        self._standby = bool(standby)
        self.standby_map: Optional[List] = None
        self.repl = None
        self.promoted = False
        self.checkpoint_path = checkpoint_path
        self.resumed_from_k: Optional[int] = None
        self.device = device if device is not None else jax.devices()[0]
        self._w = jax.device_put(jnp.zeros(d, jnp.float32), self.device)
        self._k_dev = jax.device_put(jnp.float32(0.0), self.device)
        zw = jax.device_put(jnp.zeros(d, jnp.float32), self.device)
        zg = jax.device_put(jnp.zeros(d, jnp.float32), self.device)
        if algo == "asaga":
            # ScalarMap semantics (SparkASAGAThread.scala:114,280-294): the
            # PS owns the per-sample history table AND the sampling -- it
            # draws each worker's Bernoulli(b) rows, ships (idx, alpha[idx])
            # with the model, and commits returned scalars only on accept.
            # delta == g is EXACT here (unlike the single-process engine,
            # which recomputes the delta -- see make_saga_table_delta): a
            # worker's samples live in its own shard, no other worker can
            # touch those table entries, and the per-connection pull->push
            # protocol serializes the worker against its own commits, so the
            # alpha the gradient was built against IS the alpha at commit.
            # donate_g=False: the same device buffer is passed as g and delta.
            self._apply = steps.make_saga_apply(
                cfg.gamma, cfg.batch_rate, n, cfg.num_workers, donate_g=False
            )
            self._ab = jax.device_put(jnp.zeros(d, jnp.float32), self.device)
            self._table: Dict[int, np.ndarray] = {}   # wid -> shard scalars
            self._rngs: Dict[int, np.random.Generator] = {}
            self._pending_idx: Dict[int, np.ndarray] = {}  # outstanding pull
            # guards table/rng structure + contents against the checkpoint
            # writer's iteration (lock order: _lock -> _saga_lock); pulls
            # hold it WITHOUT _lock so sampling never queues the apply path
            self._saga_lock = threading.Lock()
            zab = jax.device_put(jnp.zeros(d, jnp.float32), self.device)
            self._apply(zw, zab, zg, zg)
        else:
            self._apply = steps.make_asgd_apply(
                cfg.gamma, cfg.batch_rate, n, cfg.num_workers
            )
            # warm the accept path before the clock starts (first-iteration
            # blocking parity) -- donated dummies, never live state
            zk = jax.device_put(jnp.float32(0.0), self.device)
            self._apply(zw, zg, zk)

        # debug lock watchdog (net/lockwatch.py, async.debug.lockwatch):
        # the model lock becomes a watched lock -- any socket send/recv
        # under it raises at the frame choke point, continuously checking
        # the lock-free PULL-serving claim in chaos/soak runs.  The other
        # contended PS locks ride named_lock too, feeding the lock-order
        # race detector acquisition edges (a cycle among ps.model /
        # ps.stats / ps.versions / supervisor.members is a potential
        # deadlock caught at the first nested hold, not in production).
        from asyncframework_tpu.net import lockwatch as _lockwatch

        self._lock = _lockwatch.named_lock("ps.model")
        # ---- data plane: version-cached encoded PULL replies + deltas.
        # One readback AND one encode per model version, published as an
        # immutable _ModelSnap (host float32 array + serialized payload
        # bytes + CRC) via ATOMIC REFERENCE SWAP: _handle_pull serves
        # full/NOT_MODIFIED/delta replies from the published snapshot
        # without touching the model lock -- a whole cohort pull of an
        # unchanged version is an attribute read + a socket write, and
        # PULL serving never queues behind a merge drain.  An accepted
        # push clears the reference; the next pull rebuilds (readback +
        # encode happen OUTSIDE the model lock, under _snap_build_lock so
        # a cohort triggers one build, not P).  _w_versions keeps recent
        # versions' host arrays (bounded, version-age eviction, its own
        # small lock) so a worker pulling with ``have=<ts>`` can be
        # served a byte-exact XOR delta (net/wiredelta.py).
        self._snap: Optional[_ModelSnap] = None
        # the build BASIS: (clock, device array) captured atomically at
        # the end of every applying drain (O(1) tuple write under the
        # lock the drain already holds).  A snapshot rebuild reads this
        # reference instead of taking the model lock -- the pull path
        # stays off the model lock even while rebuilding, so a merge
        # convoy (continuous decoupled pushes keep handlers cycling the
        # lock) cannot add its queueing delay to pull latency.
        # model generation: +1 per ACCEPTED push (under the model lock,
        # BEFORE its clock tick).  Snapshot re-stamping and publishing
        # key off it -- see _ModelSnap.gen / _model_snap.
        self._model_gen = 0
        self._snap_basis: Tuple[int, object, int] = (0, self._w, 0)
        self._snap_build_lock = _lockwatch.named_lock("ps.snap_build")
        self._versions_lock = _lockwatch.named_lock("ps.versions")
        # pull-path bookkeeping (reply-shape counters, pull timestamps,
        # last-contact) keeps its own lock: read-modify-write safety
        # without ever touching the model lock from the pull path
        self._stats_lock = _lockwatch.named_lock("ps.stats")
        from collections import OrderedDict as _OD2
        from asyncframework_tpu.conf import (
            PULL_DELTA_VERSIONS,
            PUSH_MERGE,
            global_conf as _gconf,
        )

        self._w_versions: "_OD2[int, np.ndarray]" = _OD2()
        # an un-overridden cache depth auto-scales with the worker count: a
        # worker's basis is typically ~P versions old by its next pull (P
        # peers each merged once in between, plus clock ticks from drops),
        # so a cache shallower than that never hits.  Cost is host RAM
        # only: depth * d * 4 bytes of version arrays.
        if _gconf().contains(PULL_DELTA_VERSIONS.key):
            self._delta_versions = max(
                0, int(_gconf().get(PULL_DELTA_VERSIONS))
            )
        else:
            self._delta_versions = max(
                int(_gconf().get(PULL_DELTA_VERSIONS)),
                4 * cfg.num_workers + 2,
            )
        # the version cache is only maintained once a delta-capable client
        # shows up (first pull carrying ``have``): a full-mode deployment
        # pays zero cache RAM and zero per-pull cache work
        self._delta_clients_seen = False
        # pull-reply shape counters (bench/tests: the "zero payload bytes
        # per unchanged-version pull" claim is read off these)
        self.pull_replies: Dict[str, int] = {"full": 0, "nm": 0,
                                             "xdelta": 0}
        self.pull_model_bytes = 0  # model-part payload bytes sent via PULL
        # serving plane (asyncframework_tpu/serving/): SUBSCRIBE reply
        # shapes + bytes, counted apart from PULL so the training data
        # plane's bench numbers stay clean of read traffic
        self.subscribe_replies: Dict[str, int] = {"full": 0, "nm": 0,
                                                  "xdelta": 0}
        self.subscribe_model_bytes = 0
        # relaycast root offer path (asyncframework_tpu/relaycast/): a
        # SUBSCRIBE whose header carries ``rport`` registers the
        # subscriber as a direct relay child (the shared ChildRegistry:
        # bounded by async.relay.fanout with LRU eviction, so a deep
        # node that root-subscribed once cannot squat a slot a planned
        # direct child keeps renewing), and a lazy offer thread
        # announces each new version via RELAY_OFFER so depth-1 nodes
        # fetch event-driven instead of poll-bounded.  Offers are
        # advisory: a lost one costs nothing (the child's refresh loop
        # still polls).
        from asyncframework_tpu.conf import RELAY_FANOUT as _RF

        self._relay_fanout = max(1, int(_gconf().get(_RF)))
        self._relay_registry = None  # built with the first rport seen
        self._relay_lock = threading.Lock()
        self._relay_thread: Optional[threading.Thread] = None
        self._relay_offered = -1  # newest clock already offered
        self.relay_offers = 0
        # version birth times (bounded): ts -> run-clock ms at which that
        # model version was PUBLISHED by an applying drain.  Feeds the
        # freshness-lag-in-ms answer on SUBSCRIBE replies: the age of a
        # served version is "how long ago did a NEWER version appear",
        # which is 0 while the served version is still current (dropped
        # pushes tick the clock without changing the model, and leave no
        # entry here -- correctly aging nothing).
        self._born_lock = threading.Lock()
        from collections import OrderedDict as _ODB

        self._ver_born: "_ODB[int, float]" = _ODB()
        # ---- data plane: batched gradient apply (merge queue).  All
        # pushes pending at lock acquisition coalesce into ONE fused
        # device apply (ops/steps.make_*_apply_merge -- bit-identical to
        # the serial order); per-push semantics stay per item.
        merge = getattr(cfg, "push_merge", None)
        self._merge_max = max(1, int(merge if merge is not None
                                     else _gconf().get(PUSH_MERGE)))
        from collections import deque as _deque

        self._merge_q: "_deque[_PendingPush]" = _deque()
        self._apply_merge = None
        # drain-time scratch (single writer under _lock; device_put copies
        # host->device eagerly, so reusing the buffers across drains is
        # safe and keeps the lock hold free of O(m*d) allocations)
        self._merge_G: Optional[np.ndarray] = None
        self._merge_mask: Optional[np.ndarray] = None
        if self._merge_max > 1:
            self._merge_G = np.empty((self._merge_max, d), np.float32)
            self._merge_mask = np.empty(self._merge_max, np.float32)
            zG = jax.device_put(
                jnp.zeros((self._merge_max, d), jnp.float32), self.device
            )
            zm = jax.device_put(
                jnp.zeros(self._merge_max, jnp.float32), self.device
            )
            # donate_model: the fused drain writes w' into the dead
            # input's buffer -- zero steady-state allocation.  The drain
            # only routes a batch through this kernel when the outgoing
            # version is already HOST-published (its _ModelSnap exists),
            # so nothing can ever need the donated device buffer again;
            # otherwise it falls back to the serial per-item applies
            # (asserted bit-identical).  Warm dummies are donated too --
            # zw/zk2/zab2 are dead after this call by construction.
            if algo == "asaga":
                self._apply_merge = steps.make_saga_apply_merge(
                    cfg.gamma, cfg.batch_rate, n, cfg.num_workers,
                    donate_model=True,
                )
                zab2 = jax.device_put(jnp.zeros(d, jnp.float32), self.device)
                self._apply_merge(zw, zab2, zG, zm)
            else:
                self._apply_merge = steps.make_asgd_apply_merge(
                    cfg.gamma, cfg.batch_rate, n, cfg.num_workers,
                    donate_model=True,
                )
                zk2 = jax.device_put(jnp.float32(0.0), self.device)
                self._apply_merge(zw, zG, zm, zk2)
        self.merge_batches = 0    # fused drains that applied >= 1 push
        self.merge_merged = 0     # pushes applied through fused drains
        self.merge_batch_max = 0  # largest single fused batch
        self._clock = 0          # merged gradients (ASYNCcontext.CurrentTime)
        self._k = 0              # accepted updates
        self.accepted = 0
        self.dropped = 0
        self.push_bytes = 0      # wire payload bytes received via PUSH
        self.max_staleness = 0
        self._snapshots: List[Tuple[float, object]] = []
        self._t0: Optional[float] = None
        self._done = threading.Event()
        # calibration (SparkASGDThread.scala:174-183)
        self._cal_ms = 0.0
        self._cal_n = 0
        self.avg_delay_ms = 0.0
        self._pull_times: Dict[int, float] = {}
        # cohort wave gate (ASYNCbarrier + bucketRatio)
        self._wave_cv = threading.Condition()
        self._waiting: List[int] = []
        self._wave_id = 0

        # elastic membership (parallel/supervisor.py); None = the classic
        # fixed-membership PS (old callers see no behavior change)
        self.supervisor = supervisor
        # per-worker ledgers, tracked unconditionally: they feed wait_done's
        # progress diagnostic AND the acceptance coverage assert (every
        # shard's samples contributed), and they survive a PS restart
        self._last_contact: Dict[int, float] = {}
        self.pushes_by_wid: Dict[int, int] = {}
        self.accepted_by_wid: Dict[int, int] = {}
        # per-worker straggler stats (cluster observer input surface):
        # merge-time facts (staleness, push inter-arrival EWMA) land at
        # drain, latency facts (compute / push.rtt EWMAs) land when this
        # worker's piggybacked spans fold.  Own lock: span folds run on
        # connection handler threads, outside the model lock by design.
        self._wstats_lock = threading.Lock()
        self._wstats: Dict[int, Dict[str, float]] = {}
        self.membership_rejects = 0  # pushes from deposed shard servers
        # exactly-once-applied PUSH: a retried (sid, seq) re-sends the
        # cached ACK instead of merging the gradient twice (net/session.py).
        # Constructed BEFORE a restore so a checkpointed window lands here
        # -- that is what keeps retries exactly-once ACROSS a kill -9 +
        # restart, not just across a lost reply.
        from asyncframework_tpu.conf import NET_DEDUP_WINDOW, global_conf

        self._dedup = DedupWindow(window=global_conf().get(NET_DEDUP_WINDOW))

        # adaptive control plane (parallel/controller.py): the installed
        # CTRL payload (None = control off, byte-identical legacy wire
        # everywhere) + its parsed effective values.  Installed by the
        # local AsyncController (primary), by SETMAP (shard secondaries
        # and promoted standbys -- decisions SURVIVE promotion because
        # the group re-announces its stored ctrl), and served to workers
        # on WELCOME and on PULL replies whose ``cs`` stamp is stale.
        # _ctrl_lock guards the swap; the drain reads the parsed fields
        # via one attribute read each (GIL-atomic reference swaps).
        self._ctrl_lock = threading.Lock()
        self.ctrl: Optional[dict] = None
        self._ctrl_b = 0            # cohort override (0 = conf value)
        self._ctrl_merge = 0        # effective merge budget (0 = conf)
        self._ctrl_damp: Optional[Tuple[float, float, float]] = None
        self._ctrl_wdamp: Dict[int, float] = {}
        self.ctrl_stale_rejects = 0  # stale (ep, seq) installs refused
        self._apply_damped = None    # built on first damped install

        # distributed tracing (metrics/trace.py): server-side spans for
        # traced updates (the frame carried a ``tc`` header) plus spans
        # piggybacked on PUSH/BYE are folded into the process-global
        # aggregator and -- when a ListenerBus is given -- posted as
        # TraceSpan events (-> event log -> live UI -> history server), so
        # a worker's spans survive its death.
        self.bus = bus
        self._trace_agg = _trace.aggregator()
        self.trace_spans = 0  # spans folded (own + piggybacked)
        # folds happen on per-connection handler threads, outside _lock by
        # design (telemetry must not queue the apply path) -- the counter
        # needs its own lock like every other process counter.  Piggyback
        # folds dedup by span_id (bounded LRU) -- see _fold_wire_spans.
        self._trace_lock = threading.Lock()
        from collections import OrderedDict as _OD

        self._seen_span_ids: "_OD[str, None]" = _OD()

        self._elapsed_offset_ms = 0.0  # wall already spent before a resume
        # a STANDBY never boot-restores: its state arrives over the wire
        # (REPL_SYNC) at the epoch its primary streams, and a stale
        # checkpoint restore here would mint an epoch ABOVE the stream's
        # and wrongly fence it out.  The path is still kept: once
        # promoted, this server checkpoints its range there.
        if (checkpoint_path and os.path.exists(checkpoint_path)
                and not self._standby):
            self._restore(checkpoint_path)

        self._srv = socket.create_server((host, port))
        self._srv.settimeout(0.2)
        self.port = self._srv.getsockname()[1]
        self._threads: List[threading.Thread] = []
        self._accept_thread: Optional[threading.Thread] = None
        self._ckpt_thread: Optional[threading.Thread] = None
        self._ckpt_trigger = threading.Event()
        self._eval_results: Dict[int, np.ndarray] = {}
        # every process token that ever said HELLO: lets the launcher-side
        # role tell a worker that is still BOOTING (chip init, data
        # generation and compile take tens of seconds on a cold chip) from
        # one that came and died
        self.hello_procs: set = set()
        self._eval_cv = threading.Condition()
        self._stop = threading.Event()

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "ParameterServer":
        self._t0 = time.monotonic() - self._elapsed_offset_ms / 1e3
        with self._lock:
            if self.resumed_from_k is None:
                self._snapshots.append((0.0, np.array(self._w, np.float32)))
            if self._k >= self.cfg.num_iterations:
                self._done.set()  # checkpoint was already past the finish
                if self.supervisor is not None:
                    self.supervisor.freeze()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="ps-accept", daemon=True
        )
        self._accept_thread.start()
        if self.checkpoint_path:
            # async checkpoint writer: the push handler only SIGNALS the
            # cadence; serialization happens under the lock on this thread
            # and the disk write happens off every worker's request path
            self._ckpt_thread = threading.Thread(
                target=self._ckpt_loop, name="ps-checkpoint", daemon=True
            )
            self._ckpt_thread.start()
        if self.supervisor is not None:
            self.supervisor.start()
        # continuous telemetry (metrics/timeseries.py): this PS's core
        # scalars become the ``ps.*`` time series every sampler tick --
        # the updates/s-floor SLO (rate(ps.accepted)) and the adaptive
        # controller's input surface.  Last registration wins, matching
        # "the live PS owns the dashboard"; stop() unhooks only itself.
        from asyncframework_tpu.metrics import timeseries as _ts

        self._ts_source = self._telemetry_source
        _ts.register_source("ps", self._ts_source)
        # per-worker stats on /api/status (``ps_workers`` section): the
        # cluster observer's straggler scoring reads it -- same
        # last-registration-wins + identity-gated-unregister discipline
        # as the ``ps`` series source
        from asyncframework_tpu.metrics import live as _live

        self._workers_section = self.worker_stats
        _live.register_status_section("ps_workers", self._workers_section)
        _ts.ensure_started()
        return self

    def worker_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-worker straggler inputs (JSON-able; the ``ps_workers``
        /api/status section): accepted/dropped counts, last observed
        staleness, push inter-arrival EWMA, and -- when this worker's
        spans fold here -- compute and push-RTT EWMAs."""
        with self._wstats_lock:
            return {str(w): dict(st) for w, st in self._wstats.items()}

    _EWMA_A = 0.3  # per-worker EWMA weight (a few pushes to converge)

    def _wstat_merge(self, wid: int, staleness: int,
                     accepted: bool) -> None:
        """Merge-time per-worker facts; called at drain (model lock
        held) -- a dict update, same cost class as accepted_by_wid."""
        now_ms = time.monotonic() * 1e3
        with self._wstats_lock:
            st = self._wstats.setdefault(int(wid), {})
            st["accepted"] = st.get("accepted", 0) + int(accepted)
            st["dropped"] = st.get("dropped", 0) + int(not accepted)
            st["staleness"] = int(staleness)
            last = st.get("last_seen_ms")
            if last is not None and now_ms > last:
                iv = now_ms - last
                prev = st.get("interval_ms")
                st["interval_ms"] = round(
                    iv if prev is None
                    else self._EWMA_A * iv + (1 - self._EWMA_A) * prev, 3)
            st["last_seen_ms"] = now_ms

    def _wstat_span(self, span: "_trace.Span") -> None:
        """Latency facts from a folded span (compute / push.rtt).

        Only updates entries :meth:`_wstat_merge` already created: spans
        fold at PUSH receive (handler threads), merges at drain -- a
        span-only entry would carry a one-sample EWMA with no
        ``accepted`` count, bypassing the observer's warm-up guard and
        flagging a booting worker on its very first sample."""
        if span.worker_id is None or span.dur_ms is None:
            return
        if span.stage == _trace.COMPUTE:
            key = "compute_ms"
        elif span.stage == _trace.PUSH_RTT:
            key = "rtt_ms"
        else:
            return
        with self._wstats_lock:
            st = self._wstats.get(int(span.worker_id))
            if st is None:
                return
            prev = st.get(key)
            st[key] = round(
                span.dur_ms if prev is None
                else self._EWMA_A * span.dur_ms
                + (1 - self._EWMA_A) * prev, 3)

    def _telemetry_source(self) -> Dict[str, float]:
        """Flat scalars the time-series sampler records as ``ps.<key>``
        (lock-free reads of ints: a tick may see a torn multi-field view,
        but each individual series stays monotone/correct)."""
        out = {
            "clock": self._clock,
            "k": self._k,
            "accepted": self.accepted,
            "dropped": self.dropped,
            "push_bytes": self.push_bytes,
            "max_staleness": self.max_staleness,
            # merge-queue backlog at this instant: the observer prices
            # it against the push rate (queue growing faster than the
            # drain = the apply plane is the bottleneck)
            "queue_depth": len(self._merge_q),
            "done": int(self._done.is_set()),
        }
        repl = self.repl
        if repl is not None:
            # the standby's replication lag in merge units -- the
            # ps.standby_lag series the default standby_lag SLO rule
            # watches (read staleness on the standby is priced by it)
            out["standby_lag"] = float(repl.lag_versions())
            out["standby_synced"] = 1.0 if repl.synced else 0.0
        if self._standby:
            out["standby"] = 1.0
        return out

    # ---------------------------------------------------------- checkpointing
    def _checkpoint_state(self) -> dict:
        """Snapshot everything a restarted PS needs, caller holds the lock.
        ``_pending_idx`` is deliberately NOT saved: in-flight pulls die with
        the process, and a post-restart push referencing one is dropped
        (stale by construction)."""
        meta = {
            "algo": self.algo,
            "clock": self._clock,
            "k": self._k,
            "accepted": self.accepted,
            "dropped": self.dropped,
            "push_bytes": self.push_bytes,
            "max_staleness": self.max_staleness,
            "cal_ms": self._cal_ms,
            "cal_n": self._cal_n,
            "avg_delay_ms": self.avg_delay_ms,
            "elapsed_ms": self._now_ms() if self._t0 is not None else 0.0,
            "snap_times": [t for (t, _w) in self._snapshots],
            # session dedup windows ride the checkpoint: a PUSH applied in
            # this life and retried against the NEXT life must be answered
            # from cache, not merged again.  Captured under the same lock
            # as the model, so window and weights can never disagree about
            # which pushes are "in".
            "dedup": self._dedup.state(),
            "pushes_by_wid": {
                str(w): c for w, c in self.pushes_by_wid.items()
            },
            "accepted_by_wid": {
                str(w): c for w, c in self.accepted_by_wid.items()
            },
            "membership_rejects": self.membership_rejects,
            # fencing: the epoch rides the checkpoint so a restart can
            # never come back BELOW a fence (the restore bumps past it),
            # and the reject count survives incarnations for the
            # acceptance assertions / metrics
            "epoch": self.epoch,
            "fenced_rejects": self.fenced_rejects,
        }
        # owned copies, never device-buffer views: a later donated drain
        # overwrites the model buffer in place
        arrays = {"w": np.array(self._w, np.float32)}
        if self._snapshots:
            arrays["snap_stack"] = np.stack(
                [np.asarray(w) for (_t, w) in self._snapshots]
            )
        if self.algo == "asaga":
            arrays["ab"] = np.array(self._ab, np.float32)
            with self._saga_lock:  # consistent table + RNG capture
                for wid, table in self._table.items():
                    arrays[f"table_{wid}"] = table.copy()
                meta["rng_states"] = {
                    str(wid): rng.bit_generator.state
                    for wid, rng in self._rngs.items()
                }
        return {"meta": meta, "arrays": arrays}

    def save_checkpoint(self) -> None:
        """Atomic on-disk PS checkpoint (Master.scala:41 recovery semantics
        applied to the run itself, per SURVEY section 7 stage 5: model +
        history table + RNG + clock).  Serialize under the lock, write
        outside it."""
        if not self.checkpoint_path:
            return
        with self._lock:
            state = self._checkpoint_state()
        buf = io.BytesIO()
        np.savez(buf, __meta__=json.dumps(state["meta"]), **state["arrays"])
        tmp = self.checkpoint_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(buf.getvalue())
        # fsync file + rename + fsync directory: the save survives host
        # power loss, not just process death (checkpoint.durable_replace)
        from asyncframework_tpu.checkpoint import durable_replace

        durable_replace(tmp, self.checkpoint_path)

    def _ckpt_loop(self) -> None:
        while not self._stop.is_set():
            if not self._ckpt_trigger.wait(timeout=0.2):
                continue
            self._ckpt_trigger.clear()
            try:
                self.save_checkpoint()
            except Exception:  # noqa: BLE001 - the writer must outlive
                # any one failed save (disk hiccup, transient device
                # fault): a dead checkpoint thread would silently void
                # the restart guarantees for the rest of the run
                pass

    def _restore(self, path: str) -> None:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"]))
            if meta["algo"] != self.algo:
                raise ValueError(
                    f"checkpoint algo {meta['algo']!r} != PS algo "
                    f"{self.algo!r}"
                )
            self._install_state(z, meta)
            if self.epoch > 0:
                # every incarnation is a NEW epoch: a restart from this
                # checkpoint must dominate anything the previous life
                # stamped or accepted (a controller-passed epoch that
                # already counts more fences wins via max)
                self.epoch = max(self.epoch,
                                 int(meta.get("epoch", 0)) + 1)
            self.fenced_rejects = int(meta.get("fenced_rejects", 0))
        self.resumed_from_k = self._k
        supervisor_mod.bump_total("ps_resumes")

    def _install_state(self, z, meta: dict) -> None:
        """Install a checkpoint image's model + bookkeeping (shared by
        the boot-time restore and the standby's REPL_SYNC applier).
        Deliberately does NOT touch the fencing epoch or the fenced-
        reject counter: incarnation identity belongs to the caller --
        a restore bumps past the persisted epoch, a standby sync keeps
        the epoch its stream runs at."""
        import jax

        # generation bump FIRST: a lock-free reader mid-build (a live
        # standby keeps serving SUBSCRIBE through a re-sync) must fail
        # its publish guard, or it would cache the PRE-install snapshot
        # after the install and serve it until the next accepted apply
        # happened to bump the generation.  The _snap clear comes LAST,
        # after every other field, so a reader that re-reads the basis
        # builds the NEW state.  (The guard's compare-then-store is not
        # atomic -- the residual preemption window is the same one the
        # drain path has always had, and the next invalidation clears
        # it.)
        self._model_gen += 1
        self._w = jax.device_put(z["w"], self.device)
        self._w_versions.clear()
        with self._born_lock:
            self._ver_born.clear()  # prior-life ages are meaningless
        self._snap_basis = (int(meta["clock"]), self._w,
                            self._model_gen)
        self._clock = int(meta["clock"])
        self._k = int(meta["k"])
        # the DEVICE step counter must follow k: the ASGD step-size
        # schedule reads it (gamma/sqrt(k/P+1)), so leaving it at this
        # life's old value would replay the installed state's future
        # updates at the wrong step sizes -- a silent divergence between
        # a mirror and its primary (and, before this, between a
        # restarted shard and the run it resumed)
        import jax.numpy as jnp

        self._k_dev = jax.device_put(jnp.float32(self._k), self.device)
        self.accepted = int(meta["accepted"])
        self.dropped = int(meta["dropped"])
        self.push_bytes = int(meta["push_bytes"])
        self.max_staleness = int(meta["max_staleness"])
        self._cal_ms = float(meta["cal_ms"])
        self._cal_n = int(meta["cal_n"])
        self.avg_delay_ms = float(meta["avg_delay_ms"])
        self._elapsed_offset_ms = float(meta["elapsed_ms"])
        if "snap_stack" in z:
            stack = z["snap_stack"]
            self._snapshots = [
                (t, stack[i].copy())
                for i, t in enumerate(meta["snap_times"])
            ]
        else:
            self._snapshots = []
        if self.algo == "asaga":
            self._ab = jax.device_put(z["ab"], self.device)
            self._table = {
                int(k.split("_", 1)[1]): z[k].copy()
                for k in z.files if k.startswith("table_")
            }
            for wid_s, state in meta.get("rng_states", {}).items():
                rng = np.random.default_rng()
                rng.bit_generator.state = state
                self._rngs[int(wid_s)] = rng
        self._dedup.load_state(meta.get("dedup"))
        self.pushes_by_wid = {
            int(w): int(c)
            for w, c in meta.get("pushes_by_wid", {}).items()
        }
        self.accepted_by_wid = {
            int(w): int(c)
            for w, c in meta.get("accepted_by_wid", {}).items()
        }
        self.membership_rejects = int(meta.get("membership_rejects", 0))

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(
                target=self._serve_conn, args=(conn,),
                name=f"ps-conn-{conn.fileno()}", daemon=True
            )
            t.start()
            # reap on append: a long-running elastic PS accepts a fresh
            # connection per worker reconnect/retry -- without pruning,
            # finished handler threads accumulate for the life of the
            # process (one Thread object + name per connection ever made)
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def _now_ms(self) -> float:
        return (time.monotonic() - self._t0) * 1e3

    # -------------------------------------------------------------- tracing
    def _bus_time_ms(self) -> float:
        return self._now_ms() if self._t0 is not None else 0.0

    def _fold_span(self, span: "_trace.Span") -> None:
        """One span into the aggregator + (when attached) the event bus."""
        with self._trace_lock:
            self.trace_spans += 1
        self._trace_agg.add(span)
        self._wstat_span(span)
        if self.bus is not None:
            self.bus.post(_trace.span_event(span, self._bus_time_ms()))

    def _fold_wire_spans(self, wire_spans) -> None:
        """Spans piggybacked on a worker's PUSH/BYE header.

        Deduped by span_id: the (sid, seq) window covers same-stamp
        retries, but a push that was DELIVERED and then spent its whole
        retry budget re-queues its piggyback onto the next push under a
        fresh stamp -- without this, exactly the fault windows tracing
        exists to explain would double-count their spans."""
        if not wire_spans:
            return
        for d in wire_spans:
            try:
                span = _trace.Span.from_wire(d)
                with self._trace_lock:
                    if span.span_id in self._seen_span_ids:
                        continue
                    self._seen_span_ids[span.span_id] = None
                    while len(self._seen_span_ids) > 8192:
                        self._seen_span_ids.popitem(last=False)
                self._fold_span(span)
            except Exception:  # noqa: BLE001 - junk from the wire must not
                pass           # kill the connection handler

    # ------------------------------------------------------------- protocol
    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while not self._stop.is_set():
                header, payload = _recv_msg(conn)
                op = header["op"]
                # PULL_SAGA/PUSH_SAGA are the same handlers under their own
                # verbs so fault schedules (net/faults.py) can target the
                # ASAGA stream without also counting ASGD ops
                if op in ("PULL", "PULL_SAGA"):
                    if self._standby:
                        # a standby is a READ replica: SUBSCRIBE serves
                        # from its mirrored snapshot, but the training
                        # plane (wave gate, membership, merges) belongs
                        # to the range primary alone -- it is not in
                        # the shard map, and a client that lands here
                        # anyway must re-resolve, not train against a
                        # mirror
                        _send_msg(conn, {"op": "ERR", "msg": "standby"})
                        continue
                    if self._fence_reject(conn, header):
                        continue
                    self._handle_pull(conn, header)
                elif op == "SUBSCRIBE":
                    # serving-tier snapshot subscription: a read-only,
                    # wave-gate-free pull that keeps answering after DONE
                    # (standbys serve it too -- the read-replica face of
                    # hot-standby replication, staleness priced by lag)
                    if self._fence_reject(conn, header):
                        continue
                    self._handle_subscribe(conn, header)
                elif op in ("PUSH", "PUSH_SAGA"):
                    if self._standby:
                        _send_msg(conn, {"op": "ERR", "msg": "standby"})
                        continue
                    cached = self._dedup.check(header)
                    if cached is not None:
                        # duplicate of an already-applied push (the ACK was
                        # lost on the wire): re-send it, merge nothing.
                        # Dedup wins over the fence check: an op this
                        # incarnation ALREADY applied must re-answer its
                        # cached verdict, not invent a new one.
                        _send_msg(conn, cached[0])
                    elif not self._fence_reject(conn, header, record=True):
                        self._handle_push(conn, header, payload)
                elif op == "HELLO":
                    # a worker process introducing itself (elastic plane):
                    # proc token + logical worker ids + pid/host (+ the
                    # pid's /proc start time, pid-reuse protection)
                    self.hello_procs.add(str(header.get("proc")))
                    if self.supervisor is not None:
                        self.supervisor.register(
                            str(header.get("proc")),
                            [int(w) for w in header.get("wids", [])],
                            pid=header.get("pid"),
                            host=header.get("host"),
                            pid_start=header.get("pstart"),
                            mport=header.get("mport"),
                        )
                    welcome = {"op": "WELCOME",
                               "elastic": self.supervisor is not None}
                    if self.shard_map:
                        # the shard-map handshake: workers/replicas resolve
                        # the group here and fan every PULL/PUSH out per
                        # range (shardgroup.ShardedPSClient).  Key absent
                        # on an unsharded PS -- byte-identical legacy wire.
                        welcome["shards"] = self.shard_map
                        if self.shard_epochs:
                            welcome["epochs"] = self.shard_epochs
                    if self.epoch:
                        welcome["epoch"] = self.epoch
                    if self.ctrl is not None:
                        # adaptive control plane: a joining worker gets
                        # the current CTRL payload next to the map and
                        # epoch vector (absent with control off --
                        # byte-identical legacy wire)
                        welcome["ctrl"] = self.ctrl
                    _send_msg(conn, welcome)
                elif op == "SHARDMAP":
                    # shard-map query (group members, liveness probes,
                    # serving replicas): the classic single PS answers an
                    # empty list -- "no group here"
                    reply = {"op": "SHARDMAP",
                             "shards": self.shard_map or []}
                    if self.epoch:
                        reply["epoch"] = self.epoch
                        reply["fenced_rejects"] = self.fenced_rejects
                    if self.shard_epochs:
                        reply["epochs"] = self.shard_epochs
                    if self.standby_map:
                        # discovery surface for the read path: serving
                        # replicas / relaycast roots may subscribe to a
                        # range's standby instead of its primary
                        reply["standbys"] = self.standby_map
                    if self._standby:
                        reply["standby"] = True
                    if self.ctrl is not None:
                        reply["ctrl"] = self.ctrl
                    _send_msg(conn, reply)
                elif op == "SETMAP":
                    # group controller installing the assembled map on a
                    # freshly-spawned shard child (it cannot know its
                    # peers' ephemeral ports before they announce)
                    wire = header.get("shards") or None
                    self.shard_map = ([list(e) for e in wire]
                                      if wire else None)
                    if "index" in header:
                        self.shard_index = int(header["index"])
                    if header.get("epochs"):
                        # the controller's epoch vector (post-fence
                        # re-installs ride this too, so WELCOME hands new
                        # workers current epochs, not boot-time ones)
                        self.shard_epochs = [int(e)
                                             for e in header["epochs"]]
                    if "standbys" in header:
                        # the controller's standby endpoints: a primary
                        # whose own entry is set (re)targets its
                        # replication stream here -- promotion re-homes
                        # a NEW standby behind the promoted primary via
                        # the same install
                        self.set_standby_map(header.get("standbys"))
                    if "ctrl" in header:
                        # adaptive-control decisions ride SETMAP next to
                        # the map/epochs/standbys: shard secondaries
                        # damp/serve under the SAME decision the primary
                        # applies, and a promoted standby re-learns the
                        # current CTRL from the group's re-announce
                        # (monotone install; a deposed controller's
                        # stale stamp is refused)
                        self.set_control(header.get("ctrl"))
                    _send_msg(conn, {"op": "ACK"})
                elif op in ("REPL_APPEND", "REPL_SYNC"):
                    # primary->standby replication stream (parallel/
                    # replication.py).  Only a standby applies it, and
                    # the fence admission below is THE promotion-safety
                    # gate: a deposed primary's post-promotion appends
                    # carry its stale epoch and bounce REJECT_FENCED --
                    # including against the PROMOTED (ex-standby)
                    # server itself, whose minted epoch now dominates,
                    # which is how the zombie learns it was deposed.
                    if self._standby:
                        ep = header.get("ep")
                        if ep is not None and int(ep) > self.epoch:
                            # adopt-forward: the stream's source is
                            # authoritative for its standby (a primary
                            # relaunched from checkpoint streams at its
                            # bumped epoch); a STALE stamp still fails
                            # the admission below
                            self.epoch = int(ep)
                    if self._fence_reject(conn, header):
                        continue
                    if not self._standby:
                        _send_msg(conn, {"op": "ERR",
                                         "msg": "not a standby"})
                        continue
                    if op == "REPL_SYNC":
                        self._handle_repl_sync(conn, payload)
                    else:
                        self._handle_repl_append(conn, header, payload)
                elif op == "PROMOTE":
                    # controller order: this standby becomes its range's
                    # primary under the minted epoch (idempotent by
                    # monotone epoch compare)
                    self._handle_promote(conn, header)
                elif op == "FINISH":
                    # group-wide DONE broadcast: a secondary shard serves
                    # its range with an unbounded iteration budget and
                    # learns run completion only from the primary's DONE,
                    # fanned out here (worker BYE and the group controller
                    # both send it; idempotent by construction)
                    self._done.set()
                    if self.supervisor is not None:
                        self.supervisor.freeze()
                    with self._wave_cv:
                        self._wave_cv.notify_all()
                    _send_msg(conn, {"op": "ACK"})
                elif op == "SNAPSHOTS":
                    # only meaningful once the run is done; the stack is
                    # consistent either way (lock-copied)
                    times, W = self.snapshot_stack()
                    _send_msg(
                        conn,
                        {"op": "SNAPSHOTS", "times": times,
                         "shape": list(W.shape)},
                        np.ascontiguousarray(W, np.float32).tobytes(),
                    )
                elif op == "EVAL_RESULT":
                    arr = np.frombuffer(payload, np.float64).copy()
                    with self._eval_cv:
                        self._eval_results[int(header["wid"])] = arr
                        self._eval_cv.notify_all()
                    _send_msg(conn, {"op": "ACK"})
                elif op == "BYE":
                    # a departing worker's last completed spans (push.rtt
                    # of its final traced update has no later PUSH to ride)
                    # and its final pipeline-counter / convergence deltas
                    self._fold_wire_spans(header.get("spans"))
                    _pl_fold(header.get("pl"))
                    _cv_fold(header.get("cv"), clock=self._clock,
                             wall_ms=self._bus_time_ms())
                    _send_msg(conn, {"op": "ACK"})
                    return
                elif op == "SHM_OPEN":
                    # transport upgrade (net/shmring.py): attach to the
                    # colocated client's ring segments and keep serving
                    # the SAME framed protocol over them.  Everything
                    # above the transport -- dedup, fencing, CRC fields
                    # -- runs unchanged; only the byte path underneath
                    # _recv_msg/_send_msg moves.  A refused attach
                    # answered ERR and this TCP conversation continues.
                    upgraded = _shmring.serve_attach(conn, header)
                    if upgraded is not None:
                        conn = upgraded
                else:
                    _send_msg(conn, {"op": "ERR", "msg": f"bad op {op}"})
        except (ConnectionError, OSError):
            return
        finally:
            conn.close()

    def _fence_reject(self, conn: socket.socket, header: dict,
                      record: bool = False) -> bool:
        """Epoch-fencing admission (async.fence.enabled): True when the
        op was answered REJECT_FENCED and must not be served.

        Rules (``ep`` = the op's stamped epoch, ``self.epoch`` = this
        incarnation's minted one):

        - fencing off (``self.epoch == 0``) or unstamped op (legacy
          client): serve -- the wire stays byte-identical and old
          clients keep their old semantics;
        - ``ep < self.epoch``: the CLIENT is deposed (it pulled its view
          from a fenced incarnation) -- reject, tell it the current
          epoch so it re-resolves and continues;
        - ``ep > self.epoch``: a successor exists, so THIS server is the
          zombie -- remember the foreign epoch and reject; from here on
          every stamped op is refused (a zombie must neither mutate nor
          serve its old range, even to same-epoch stragglers);
        - ``ep == self.epoch`` and not deposed: serve.

        The reply carries the highest epoch this server knows, so a
        fenced client self-heals: it adopts the epoch and its next op
        (stamped fresh) is admitted by the current owner.  Fenced PUSH
        verdicts are recorded in the dedup window (``record=True``) so a
        retry of the same stamp re-answers the fence instead of racing a
        fresh admission."""
        if not self.epoch:
            return False
        ep = header.get("ep")
        if ep is None:
            return False
        ep = int(ep)
        if ep > self.epoch:
            # lock-free int write: monotone max under the GIL; a racing
            # reader sees either value, both of which fence correctly
            if ep > self._fenced_above:
                self._fenced_above = ep
        elif ep == self.epoch and self._fenced_above <= self.epoch:
            return False
        rej = {"op": "REJECT_FENCED",
               "epoch": max(self.epoch, self._fenced_above)}
        with self._stats_lock:
            self.fenced_rejects += 1
        supervisor_mod.bump_total("fenced_rejects")
        if record:
            # PUSH: fold the piggybacked telemetry BEFORE rejecting --
            # the 'fold before any drop path' invariant (_handle_push).
            # Spans/counters/convergence samples around a failover are
            # exactly the telemetry the fence window must not eat, and
            # dedup-replayed fenced stamps never reach here (the cached
            # verdict answers them), so nothing double-folds.
            self._fold_wire_spans(header.get("spans"))
            _pl_fold(header.get("pl"))
            _cv_fold(header.get("cv"), clock=self._clock,
                     wall_ms=self._bus_time_ms())
            self._dedup.record(header, rej)
        _send_msg(conn, rej)
        return True

    def note_fenced_above(self, ep: int) -> None:
        """Fold a foreign successor epoch observed OUT of band (the
        replication stream's REJECT_FENCED reply): from here on every
        stamped op is refused, exactly as if a client had proven the
        successor -- which drives workers to re-resolve onto it."""
        ep = int(ep)
        if ep > self._fenced_above:
            self._fenced_above = ep

    # ------------------------------------------------- adaptive control
    def set_control(self, wire: Optional[dict]) -> bool:
        """Install a CTRL payload (parallel/controller.py decisions).

        Monotone by (epoch, seq) -- fence-stamped: a deposed
        controller's decision (stamped with a pre-promotion epoch below
        an already-installed one) is refused and counted, exactly like
        a zombie's write.  ``None`` clears control entirely (back to
        the byte-identical legacy path).  Returns True when installed.
        """
        from asyncframework_tpu.parallel.controller import ctrl_seq

        if wire is not None and self.algo == "asgd":
            damp = wire.get("damp")
            if damp and float(damp[0]) > 0:
                # build + warm the damped serial kernel BEFORE the law
                # is published: a single-item drain between install and
                # compile would otherwise fall through to the undamped
                # kernel while a contended (fused) drain damps -- the
                # applied step must never depend on queue contention
                self._ensure_apply_damped()
        with self._ctrl_lock:
            if wire is None:
                self.ctrl = None
                self._ctrl_b = 0
                self._ctrl_merge = 0
                self._ctrl_damp = None
                self._ctrl_wdamp = {}
                return True
            new, cur = ctrl_seq(wire), ctrl_seq(self.ctrl)
            if new == cur:
                # idempotent re-delivery (the group re-announces its
                # stored ctrl on every SETMAP sweep): not a fence event
                return False
            if new < cur:
                self.ctrl_stale_rejects += 1
                return False
            self.ctrl = dict(wire)
            self._ctrl_b = max(0, int(wire.get("b", 0) or 0))
            self._ctrl_merge = max(0, int(wire.get("merge", 0) or 0))
            damp = wire.get("damp")
            if damp and self.algo == "asgd":
                # [coeff, floor, free]: the bounded 1/(1+tau)-family
                # law the drain applies per accepted push.  ASAGA is
                # excluded by design: damping the gradient term alone
                # would break its alpha_bar == mean(table) invariant
                # (same exactness stance as the codec exclusion).
                c, fl, fr = (float(damp[0]), float(damp[1]),
                             float(damp[2]))
                self._ctrl_damp = (c, fl, fr) if c > 0 else None
            else:
                self._ctrl_damp = None
            wd = wire.get("wdamp") or {}
            try:
                self._ctrl_wdamp = {int(w): float(f)
                                    for w, f in wd.items()}
            except (TypeError, ValueError):
                self._ctrl_wdamp = {}
        return True

    def _ensure_apply_damped(self) -> None:
        """Build + warm the damped serial apply kernel once (ASGD only;
        called OFF the model lock -- from set_control before the law
        publishes, and from the replication receive path before a
        damped append takes the lock).  A benign double-build under a
        race compiles the identical function twice."""
        if self._apply_damped is not None or self.algo != "asgd":
            return
        from asyncframework_tpu.ops import steps as _steps
        import jax as _jax
        import jax.numpy as _jnp

        apply_damped = _steps.make_asgd_apply_damped(
            self.cfg.gamma, self.cfg.batch_rate, self.n,
            self.cfg.num_workers)
        zw = _jax.device_put(_jnp.zeros(self.d, _jnp.float32),
                             self.device)
        zg = _jax.device_put(_jnp.zeros(self.d, _jnp.float32),
                             self.device)
        zk = _jax.device_put(_jnp.float32(0.0), self.device)
        apply_damped(zw, zg, zk, np.float32(1.0))
        self._apply_damped = apply_damped

    def _item_damp(self, wid: int, staleness: int) -> float:
        """The per-item step-DAMP factor under the installed CTRL law:
        1/(1 + c*(tau - free)) past the free slack, floored, times the
        per-worker extra factor for observer-flagged stragglers.  1.0
        (exact) whenever control is off or the push is fresh enough."""
        law = self._ctrl_damp
        if law is None:
            return 1.0
        c, floor_, free = law
        damp = 1.0
        over = float(staleness) - free
        if over > 0.0:
            damp = max(floor_, 1.0 / (1.0 + c * over))
        wd = self._ctrl_wdamp.get(wid)
        if wd is not None:
            damp = max(floor_, damp * wd)
        # an ACCEPTED item's damp must stay strictly positive: the merge
        # kernel's keep bit is ``mask > 0``, and a zero factor (possible
        # only with a hand-crafted CTRL floor of 0) would silently turn
        # an accepted push into a dropped one
        return float(max(damp, 1e-6))

    def control_signals(self) -> Dict[str, float]:
        """PS-local scalars the adaptive controller reads each tick
        (lock-free int reads, same stance as ``_telemetry_source``)."""
        return {
            "clock": float(self._clock),
            "accepted": float(self.accepted),
            "dropped": float(self.dropped),
            "queue_depth": float(len(self._merge_q)),
            "max_staleness": float(self.max_staleness),
            "avg_delay_ms": float(self.avg_delay_ms),
            "done": float(self._done.is_set()),
        }

    # ----------------------------------------------- hot-standby replication
    def attach_standby(self, host: str, port: int) -> None:
        """(Re)point this PRIMARY's replication stream at its warm
        standby (parallel/replication.py).  Idempotent per endpoint.
        ASGD-only, like the sharded plane it serves: ASAGA's per-sample
        history table is not streamed."""
        if self.algo != "asgd":
            raise ValueError("standby replication is ASGD-only")
        if self._standby:
            raise ValueError("a standby does not stream to a standby")
        from asyncframework_tpu.parallel.replication import (
            ReplicationStream,
        )

        cur = self.repl
        if (cur is not None and not cur.fenced
                and (cur.host, cur.port) == (host, int(port))):
            return
        if cur is not None:
            cur.stop()
        self.repl = ReplicationStream(self, host, int(port))

    def set_standby_map(self, wire) -> None:
        """Install the group's standby endpoints (``[host, port]`` |
        None per range, SETMAP/launcher-supplied) and reconcile this
        server's own stream: a primary whose entry is set streams to
        it; an entry gone stops the stream."""
        self.standby_map = ([list(e) if e else None for e in wire]
                            if wire else None)
        if self._standby:
            return
        mine = None
        if (self.standby_map
                and self.shard_index < len(self.standby_map)):
            mine = self.standby_map[self.shard_index]
        if mine:
            self.attach_standby(str(mine[0]), int(mine[1]))
        elif self.repl is not None:
            self.repl.stop()
            self.repl = None

    def _handle_repl_sync(self, conn: socket.socket,
                          payload: bytes) -> None:
        """Standby side of REPL_SYNC: install the primary's checkpoint
        image as this mirror's state.  Idempotent -- re-installing the
        same image converges to the same state; a newer image simply
        supersedes.  The epoch is NOT taken from the image: the stream's
        ``ep`` stamp (adopt-forward in the dispatch) is the incarnation
        authority."""
        from asyncframework_tpu.parallel import replication as _repl

        try:
            with np.load(io.BytesIO(payload), allow_pickle=False) as z:
                meta = json.loads(str(z["__meta__"]))
                if meta["algo"] != self.algo:
                    raise ValueError(
                        f"sync algo {meta['algo']!r} != {self.algo!r}")
                with self._lock:
                    self._install_state(z, meta)
                    clock = self._clock
        except (ValueError, KeyError, OSError) as e:
            _send_msg(conn, {"op": "ERR", "msg": f"bad sync: {e}"})
            return
        if self._t0 is not None:
            # align this process's run clock with the primary's elapsed
            # wall, so mirrored version births / snapshot times price
            # freshness on the primary's timeline, not this process's
            self._t0 = time.monotonic() - self._elapsed_offset_ms / 1e3
        _repl.bump("sync_installs")
        _send_msg(conn, {"op": "ACK", "clock": clock})

    def _handle_repl_append(self, conn: socket.socket, header: dict,
                            payload: bytes) -> None:
        """Standby side of REPL_APPEND: apply one replicated merge batch
        exactly as the primary judged it -- same accept verdicts through
        the same jitted kernel in the same order, same ``(sid, seq)``
        dedup records (so a promoted standby re-answers replayed worker
        pushes from the REPLICATED window, never by re-applying), same
        snapshot cadence (the promoted trajectory continues seamlessly).

        Idempotence is the clock compare: a batch entirely at-or-below
        the applied clock is a duplicate delivery and re-ACKs; a batch
        starting exactly AT the clock applies; anything else is a gap --
        refused with ``resync`` so the stream re-bootstraps.  Never
        applied twice, never applied out of order."""
        import jax

        from asyncframework_tpu.parallel import replication as _repl

        if self.algo != "asgd":
            _send_msg(conn, {"op": "ERR", "msg": "replication is "
                                                 "ASGD-only"})
            return
        items = header.get("items") or []
        pre = int(header.get("pre", -1))
        cal = header.get("cal")
        if any(len(it) > 7 and float(it[7]) != 1.0 for it in items):
            # delay-adaptive damped items in this batch: compile the
            # damped kernel BEFORE taking the model lock (one-time)
            self._ensure_apply_damped()
        with self._lock:
            if pre + len(items) <= self._clock:
                reply = {"op": "ACK", "clock": self._clock, "dup": True}
            elif pre != self._clock:
                _repl.bump("resyncs_requested")
                reply = {"op": "ERR", "resync": True,
                         "clock": self._clock}
            else:
                off = 0
                for it in items:
                    wid, ts = int(it[0]), int(it[1])
                    acc = bool(it[2])
                    sid, seq, ack = it[3], it[4], it[5]
                    st = int(it[6])
                    # per-item step-DAMP (absent on a pre-damping
                    # primary's stream: 1.0 = the exact legacy apply)
                    damp = float(it[7]) if len(it) > 7 else 1.0
                    if sid is not None:
                        self._dedup.record({"sid": sid, "seq": seq},
                                           dict(ack))
                    self.pushes_by_wid[wid] = (
                        self.pushes_by_wid.get(wid, 0) + 1)
                    if st > self.max_staleness:
                        self.max_staleness = st
                    if acc:
                        g = np.frombuffer(
                            payload[off:off + 4 * self.d], np.float32)
                        off += 4 * self.d
                        # same unpublish-before-tick discipline as the
                        # drain: lock-free SUBSCRIBE readers must never
                        # pair a new clock with old bytes
                        self._model_gen += 1
                        self._snap = None
                        g_dev = jax.device_put(g, self.device)
                        if damp != 1.0 and self._apply_damped is not None:
                            # the primary damped this push: the mirror
                            # applies the IDENTICAL expression (serial
                            # damped kernel == damped merge body, bit
                            # for bit) so its state stays the primary's
                            self._w, self._k_dev = self._apply_damped(
                                self._w, g_dev, self._k_dev,
                                np.float32(damp))
                        else:
                            self._w, self._k_dev = self._apply(
                                self._w, g_dev, self._k_dev)
                        self._k += 1
                        self.accepted += 1
                        self.accepted_by_wid[wid] = (
                            self.accepted_by_wid.get(wid, 0) + 1)
                        if self._k % self.cfg.printer_freq == 0:
                            # the primary's snapshot cadence, mirrored:
                            # an owned host copy, never a buffer view
                            self._snapshots.append((
                                self._now_ms()
                                if self._t0 is not None else 0.0,
                                np.array(self._w, np.float32),
                            ))
                        if self._k >= self.cfg.num_iterations:
                            self._done.set()
                    else:
                        self.dropped += 1
                    self._clock += 1
                if cal:
                    self._cal_ms = float(cal[0])
                    self._cal_n = int(cal[1])
                    self.avg_delay_ms = float(cal[2])
                self._snap_basis = (self._clock, self._w,
                                    self._model_gen)
                if self._t0 is not None:
                    with self._born_lock:
                        self._ver_born[self._clock] = self._now_ms()
                        while len(self._ver_born) > 1024:
                            self._ver_born.popitem(last=False)
                _repl.bump("appends_applied")
                _repl.bump("append_items", len(items))
                reply = {"op": "ACK", "clock": self._clock}
        # deliberately NO checkpoint trigger here: durability is the
        # PRIMARY's job (a dead mirror is respawned and re-synced,
        # nothing to restore), and a mirror writing the shard's durable
        # files would race the acting primary's checkpoint thread on a
        # shared path.  Once PROMOTED, this server checkpoints through
        # the normal push path.
        _send_msg(conn, reply)

    def _handle_promote(self, conn: socket.socket,
                        header: dict) -> None:
        """PROMOTE: this standby becomes its range's primary at the
        controller-minted epoch.  Idempotent by monotone compare; the
        deposed primary needs no teardown order -- its next stream
        append (or any worker op, once note_fenced_above folds the
        bounce back) is REJECT_FENCED by the epoch installed here."""
        from asyncframework_tpu.parallel import replication as _repl

        ep = int(header.get("epoch", 0) or 0)
        with self._lock:
            if self._standby and ep <= self.epoch:
                # a STALE order against a fresh mirror (a late operator
                # retry, a re-delivered PROMOTE after this standby was
                # respawned): flipping would orphan it from its
                # primary's stream -- refuse, loudly.  An already-
                # promoted server re-ACKs below (idempotent).
                stale_ep, cur_ep = ep, self.epoch
                was_standby = None
            else:
                if ep > self.epoch:
                    self.epoch = ep
                was_standby = self._standby
                self._standby = False
                # an already-promoted server re-ACKs a DUPLICATE order
                # (ep == epoch: same map, install idempotent by value)
                # but must NOT install a STALE one (ep < epoch: a late
                # re-delivery from before a LATER failover would regress
                # the map/epoch vector this server hands out)
                stale_order = ep < self.epoch
            clock, k = self._clock, self._k
        if was_standby is None:
            _send_msg(conn, {"op": "ERR",
                             "msg": f"stale promote: epoch {stale_ep} "
                                    f"<= standby epoch {cur_ep}"})
            return
        if not stale_order:
            wire = header.get("shards") or None
            if wire:
                self.shard_map = [list(e) for e in wire]
            if "index" in header:
                self.shard_index = int(header["index"])
            if header.get("epochs"):
                self.shard_epochs = [int(e) for e in header["epochs"]]
            if "standbys" in header:
                # the fresh standby spawned behind THIS promoted primary
                self.set_standby_map(header.get("standbys"))
        if was_standby:
            self.promoted = True
            _repl.bump("promotions")
        _send_msg(conn, {"op": "ACK", "clock": clock, "k": k,
                         "epoch": self.epoch})

    def _release_wave_locked(self) -> None:
        """Fire the partial barrier: everyone currently waiting rides this
        wave.  Caller holds ``_wave_cv``."""
        self._wave_id += 1
        self._waiting.clear()
        self._wave_cv.notify_all()

    def _cohort_threshold(self) -> int:
        """Partial-barrier ``b``, clamped to live membership: when the
        supervisor knows only L workers are alive, a wave of min(b, L)
        keeps flowing immediately instead of leaning on the starvation
        fallback every round (ASAP's membership-as-staleness stance).

        The adaptive controller's cohort override (CTRL ``b``) takes
        precedence over the configured ``bucket_threshold`` -- its
        decision already respects the declared tunable bounds, and a
        re-clamped wave is how one DELAYed worker stops gating every
        round -- but live membership still caps it."""
        b_ctrl = self._ctrl_b
        threshold = (b_ctrl if b_ctrl > 0
                     else max(self.cfg.bucket_threshold, 1))
        threshold = max(threshold, 1)
        if self.supervisor is not None:
            threshold = max(1, min(threshold,
                                   self.supervisor.live_worker_count()))
        return threshold

    def _model_snap(self) -> _ModelSnap:
        """The published snapshot of the current model version, built on
        demand.  The fast path is one attribute read -- no locks at all.
        A rebuild (first pull after an accepted push) reads the
        atomically-published build basis and does the O(d) readback +
        serialize + CRC without touching the model lock either;
        ``_snap_build_lock`` makes a cohort trigger one build, not P."""
        snap = self._snap
        if snap is not None:
            return snap
        with self._snap_build_lock:
            snap = self._snap
            if snap is not None:
                return snap
            # the basis reference is written atomically by the drain (a
            # tuple swap under the model lock); reading it here needs NO
            # lock at all -- the build's only waits are the device
            # readback and peer builders on _snap_build_lock
            basis = self._snap_basis
            ts, w_dev, gen = basis
            # device readback without any lock.  The fused drain DONATES
            # the model buffer (in-place apply), so two disciplines:
            # (1) w_host must be an owned COPY, never a view of device
            # memory (np.asarray of a CPU jax array aliases the buffer);
            # (2) a donated drain can invalidate the basis buffer between
            # our tuple read and the readback -- it redirects the basis
            # (to the outgoing version's host copy) BEFORE the donating
            # dispatch, so one re-read always lands on valid memory.
            try:
                w_host = np.array(w_dev, np.float32)
            except Exception:
                basis = self._snap_basis
                ts, w_dev, gen = basis
                w_host = np.array(w_dev, np.float32)
            wire = w_host.tobytes()
            snap = _ModelSnap(int(ts), w_host, wire, wiredelta.crc(wire),
                              int(gen))
            # publish only while the model GENERATION is unchanged: a
            # drain may be mid-apply right now (it bumped _model_gen in
            # its accept branch, but writes the new basis only at drain
            # end), and publishing a stale snap then would let the
            # send-time re-stamp below pair the new clock with old
            # bytes.  Serving the unpublished snap is still correct --
            # it is stamped with ITS ts and staleness is priced.
            if self._model_gen == gen:
                self._snap = snap
            return snap

    def _negotiated_model(self, have) -> Tuple[int, int, dict, bytes]:
        """The LOCK-FREE model-serving core shared by PULL and SUBSCRIBE:
        everything here reads the published :class:`_ModelSnap` (atomic
        reference) -- the model lock is never taken (net/lockwatch.py
        asserts it in debug runs), so serving never queues behind a merge
        drain and a drain never stalls behind a slow reader's socket.

        Returns ``(ts, clock, model_hdr, model_part)``: the send-time
        version stamp, the raw clock read, the negotiated reply header
        fields (empty for a legacy no-``have`` reply, byte-identical to
        the pre-delta wire), and the model payload bytes.  Encoding
        happens OUTSIDE any lock (the O(d) xor must not queue the apply
        path); the version caches pin every array/bytes object needed."""
        if have is not None:
            self._delta_clients_seen = True  # one-way flag, GIL-atomic
        snap = self._model_snap()
        ts, w_host, w_wire, w_crc = snap.ts, snap.w_host, snap.wire, snap.crc
        # the clock may have ticked past the snapshot on DROPPED pushes
        # (they advance the clock but not the model).  An accepted push
        # bumps the model GENERATION before its clock tick, so if the
        # generation still matches this snapshot's after an atomic clock
        # read, every tick in between was a drop -- same bytes, newer
        # version: stamp the current clock (send-time parity with the
        # serial path).  A lost race just serves snap.ts, which only
        # over-prices staleness, never mispairs version and bytes.
        cur = self._clock
        if cur != ts and self._model_gen == snap.gen:
            ts = cur
        basis = None
        if have is not None and self._delta_versions > 0:
            # recent-version cache for delta encoding, maintained only
            # once a delta client exists; ts is monotone, so insertion
            # order IS version age and eviction pops the oldest
            with self._versions_lock:
                if snap.ts not in self._w_versions:
                    self._w_versions[snap.ts] = w_host
                    while len(self._w_versions) > self._delta_versions:
                        self._w_versions.popitem(last=False)
                if ts != snap.ts and ts not in self._w_versions:
                    self._w_versions[ts] = w_host  # same bytes, newer ts
                    while len(self._w_versions) > self._delta_versions:
                        self._w_versions.popitem(last=False)
        if have is not None:
            if int(have) == ts:
                # exact-version match needs no cache: the basis IS the
                # current version, so this encodes to NOT_MODIFIED
                # (the reply CRC still guards a cross-PS-life clash)
                basis = w_host
            elif self._delta_versions > 0:
                with self._versions_lock:
                    basis = self._w_versions.get(int(have))
        model_hdr: dict = {}
        model_part: bytes = w_wire
        if have is not None:
            wenc, enc_payload, nnz = wiredelta.encode(
                w_host, basis, cur_bytes=w_wire
            )
            model_hdr = {"wenc": wenc, "crc": w_crc}
            if wenc == wiredelta.XDELTA:
                model_hdr["nnz"] = nnz
            model_part = enc_payload
            model_hdr["wlen"] = len(model_part)
        return ts, cur, model_hdr, model_part

    def _handle_pull(self, conn: socket.socket, header: dict) -> None:
        wid = int(header["wid"])
        proc = header.get("proc")
        if self._t0 is not None:
            with self._stats_lock:
                self._last_contact[wid] = self._now_ms()
        sup = self.supervisor
        if sup is not None:
            if not sup.owns(proc, wid):
                # a deposed surrogate (the real owner rejoined): stand down
                _send_msg(conn, {"op": "RELEASED"})
                return
            sup.touch(wid, proc)
            sup.ack_adoption(proc, wid)
        if self._done.is_set():
            _send_msg(conn, {"op": "DONE"})
            return
        # traced update: time spent in the partial-barrier wave gate below
        # is THE server-side pull latency (pull.wait).  Untraced pulls (no
        # tc header -- sampling off or unsampled update) do no trace work.
        tc = _trace.TraceContext.from_wire(header["tc"]) \
            if "tc" in header else None
        t_wait0 = _trace.now_ms() if tc is not None else 0.0
        STARVATION_S = 1.0  # degraded-cohort release when peers are gone
        with self._wave_cv:
            self._waiting.append(wid)
            my_wave = self._wave_id
            if len(self._waiting) >= self._cohort_threshold():
                # the partial barrier fires
                self._release_wave_locked()
            else:
                t_enter = time.monotonic()
                while (
                    my_wave == self._wave_id
                    and not self._done.is_set()
                    and not self._stop.is_set()
                ):
                    self._wave_cv.wait(timeout=0.05)
                    # membership may have shrunk while we waited: the
                    # clamped threshold can release this wave NOW
                    if (
                        my_wave == self._wave_id
                        and len(self._waiting) >= self._cohort_threshold()
                    ):
                        self._release_wave_locked()
                        break
                    # starvation fallback: when fewer than threshold workers
                    # are still alive the wave can never fill -- after a
                    # full second of waiting, release whoever is here as a
                    # degraded cohort (the reference's wait loop assumes
                    # workers come back; dead ones never do)
                    if (
                        my_wave == self._wave_id
                        and time.monotonic() - t_enter > STARVATION_S
                    ):
                        self._release_wave_locked()
                        break
        t_wait1 = _trace.now_ms() if tc is not None else 0.0
        if self._done.is_set():
            _send_msg(conn, {"op": "DONE"})
            return
        extra_hdr: dict = {}
        extra_payload = b""
        if self.algo == "asaga":
            # PS-side seeded sampling (the reference driver's sampledMap
            # draw): per-wid RNG chain, Bernoulli(b) over the worker's
            # shard rows, padded to the static step capacity.  Deliberately
            # OUTSIDE the global lock: per-wid state (rng/table/pending) is
            # only ever touched by this wid's connection thread (pull and
            # push are serialized per connection, and no push can arrive
            # before this MODEL is sent), and O(n_p) sampling must not
            # queue other workers' pulls or the push/apply hot path.
            from asyncframework_tpu.ops.steps import sparse_step_capacity

            n_p = int(header["n_p"])
            with self._saga_lock:  # vs the checkpoint writer's snapshot
                table = self._table.get(wid)
                if table is None or table.shape[0] != n_p:
                    table = np.zeros(n_p, np.float32)
                    self._table[wid] = table
                rng = self._rngs.get(wid)
                if rng is None:
                    rng = np.random.default_rng([self.cfg.seed, wid])
                    self._rngs[wid] = rng
                cap = sparse_step_capacity(self.cfg.batch_rate, n_p)
                idx = np.nonzero(rng.random(n_p) < self.cfg.batch_rate)[0]
                if idx.size > cap:  # ~1e-9/draw: drop the excess (parity
                    idx = idx[:cap]  # with the device steps' capacity rule)
                idx_pad = np.zeros(cap, np.uint32)
                idx_pad[: idx.size] = idx
                alpha_sel = table[idx_pad].astype(np.float32)
                self._pending_idx[wid] = idx.astype(np.int64)
            extra_hdr = {"cap": cap, "n_valid": int(idx.size)}
            extra_payload = idx_pad.tobytes() + alpha_sel.tobytes()
        have = header.get("have")
        ts, _clock, model_hdr, model_part = self._negotiated_model(have)
        with self._stats_lock:
            self._pull_times[wid] = self._now_ms()
            shape = model_hdr.get("wenc", "full")
            self.pull_replies[shape] = self.pull_replies.get(shape, 0) + 1
            self.pull_model_bytes += len(model_part)
        avg = self.avg_delay_ms
        if tc is not None:
            # exactly the wave-gate wait (barrier cost), not the model
            # readback; folded here because the served version ts is only
            # known under the lock
            self._fold_span(_trace.Span(
                stage=_trace.PULL_WAIT, trace_id=tc.trace_id,
                span_id=_trace._new_id(8), parent_id=tc.span_id,
                worker_id=wid, model_version=ts, start_ms=t_wait0,
                dur_ms=max(0.0, t_wait1 - t_wait0),
            ))
        if sup is not None:
            # adoption orders ride the PULL reply (no extra RTT, no side
            # channel): re-delivered until the adopter's first pull FOR the
            # orphan lands, so a lost reply cannot lose a shard
            orders = sup.orders_for(proc)
            if orders:
                extra_hdr["adopt"] = orders
        ctrl = self.ctrl
        if ctrl is not None:
            # adaptive-control decisions ride PULL replies the same way
            # adoption orders do: re-delivered until the client's ``cs``
            # stamp catches up with the decision's FULL (epoch, seq)
            # stamp -- a restarted controller under a minted epoch
            # starts seq over, and a bare-seq compare would strand
            # every surviving worker on the deposed decisions.  A lost
            # reply cannot lose a decision and a settled cluster pays
            # zero extra bytes per pull.  Absent with control off.
            cs = header.get("cs")
            if cs is None:
                stamp = (0, -1)
            elif isinstance(cs, (list, tuple)) and len(cs) == 2:
                stamp = (int(cs[0]), int(cs[1]))
            else:  # legacy bare-seq stamp: pair it with OUR epoch
                stamp = (int(ctrl.get("ep", 0) or 0), int(cs))
            from asyncframework_tpu.parallel.controller import ctrl_seq

            if stamp < ctrl_seq(ctrl):
                extra_hdr["ctrl"] = ctrl
        # vectored zero-copy framing: the cached model bytes and the ASAGA
        # extra payload go out as one kernel-gathered iovec -- the payload
        # is never copied into a fresh frame buffer
        if self.epoch:
            # fencing on: replies advertise the current epoch so a
            # client that joined before a fence converges without a
            # REJECT_FENCED round trip (absent with fencing off --
            # byte-identical legacy wire)
            extra_hdr["ep"] = self.epoch
        _frame.send_msg_vectored(
            conn,
            {"op": "MODEL", "ts": ts, "avg_delay_ms": avg,
             "calibrated":
                 self._cal_n >= self.cfg.effective_calibration_iters(),
             **model_hdr, **extra_hdr},
            (model_part, extra_payload) if extra_payload
            else (model_part,),
        )

    def _version_age_ms(self, ts: int, clock: int) -> float:
        """Freshness age of model version ``ts``: ms since the first NEWER
        version was published (0 while ``ts`` is still the current model).
        Bounded scan of the birth ring -- entries are clock-ascending, so
        the first key past ``ts`` is the moment ``ts`` stopped being the
        latest; an evicted birth (very stale subscriber) under-reports
        rather than guessing."""
        if ts >= clock or self._t0 is None:
            return 0.0
        now = self._now_ms()
        with self._born_lock:
            for v, born in self._ver_born.items():
                if v > ts:
                    return max(0.0, now - born)
        return 0.0

    def _register_relay_child(self, host: str, port: int) -> None:
        """Record a relaycast direct child (SUBSCRIBE carried ``rport``)
        and lazily start the offer thread.  The shared ChildRegistry
        (relaycast/offers.py) bounds the set at the tree fanout with
        LRU eviction: direct children renew their slot on every
        subscribe, so a stale registrant (a deep node that re-homed
        here once) is displaced, never a live one."""
        start = False
        with self._relay_lock:
            if self._relay_registry is None:
                from asyncframework_tpu.relaycast.offers import (
                    ChildRegistry,
                )

                self._relay_registry = ChildRegistry(self._relay_fanout)
            if self._relay_thread is None:
                from asyncframework_tpu.utils.threads import guarded

                self._relay_thread = threading.Thread(
                    target=guarded(self._relay_offer_loop,
                                   "ps-relay-offer"),
                    name="ps-relay-offer", daemon=True,
                )
                start = True
        self._relay_registry.register(host, port)
        if start:
            self._relay_thread.start()

    def _relay_offer_loop(self) -> None:
        """The root offer path: watch the merge clock and announce each
        new published version (RELAY_OFFER: ts + CRC + epoch) to the
        registered direct children via the shared ChildRegistry fan-out.
        Entirely off the hot path -- the snapshot build it may trigger
        is the same one the next pull would pay, and sends happen
        outside every lock with short timeouts."""
        while not self._stop.is_set():
            self._stop.wait(0.02)
            clock = self._clock
            if clock == self._relay_offered:
                continue
            registry = self._relay_registry
            if registry is None or not registry.children():
                self._relay_offered = clock
                continue
            snap = self._model_snap()
            hdr = {"op": "RELAY_OFFER", "ts": snap.ts, "crc": snap.crc}
            if self.epoch:
                hdr["ep"] = self.epoch
            self.relay_offers += registry.offer(hdr)
            self._relay_offered = clock

    def _handle_subscribe(self, conn: socket.socket, header: dict) -> None:
        """Serving-tier snapshot subscription (serving/replica.py).

        Same ``have=``-negotiated NOT_MODIFIED / XDELTA / FULL reply
        shapes as PULL -- the replica cache-invalidation protocol IS the
        delta-pull protocol -- but deliberately WITHOUT the partial-
        barrier wave gate (a read must never wait for a training cohort
        to fill), without membership/ownership discipline (replicas are
        not shard servers), and still answering after DONE (training
        finishing must not take the read path down).  Entirely lock-free
        on the model lock, like ``_handle_pull``.  The reply additionally
        carries the PS merge clock, the accepted-update count, the served
        version's age in ms, and the done flag, so replicas can price
        their own freshness lag in versions AND ms."""
        rp = header.get("rport")
        if rp is not None:
            # relaycast: the subscriber runs a relay node on this port --
            # register it for the root offer path
            try:
                peer = conn.getpeername()[0]
            except OSError:
                peer = None
            if peer is not None:
                self._register_relay_child(peer, int(rp))
        have = header.get("have")
        ts, cur, model_hdr, model_part = self._negotiated_model(have)
        shape = model_hdr.get("wenc", "full")
        with self._stats_lock:
            self.subscribe_replies[shape] = (
                self.subscribe_replies.get(shape, 0) + 1
            )
            self.subscribe_model_bytes += len(model_part)
        if self.epoch:
            model_hdr["ep"] = self.epoch
        _frame.send_msg_vectored(
            conn,
            {"op": "MODEL", "ts": ts, "clock": cur, "k": self._k,
             "done": self._done.is_set(),
             "age_ms": round(self._version_age_ms(ts, cur), 3),
             **model_hdr},
            (model_part,),
        )

    def _handle_push(self, conn: socket.socket, header: dict,
                     payload: bytes) -> None:
        wid = int(header["wid"])
        ts = int(header["ts"])
        proc = header.get("proc")
        # completed client-side spans ride the PUSH header (the piggyback
        # that makes spans survive worker death); fold them before any
        # drop path so a membership-stale push still delivers its telemetry
        self._fold_wire_spans(header.get("spans"))
        # pipelined-loop counter deltas piggyback the same way (only
        # present when the worker runs the pipelined loop): dedup'd
        # retries never reach this handler, so a delta folds exactly once
        _pl_fold(header.get("pl"))
        # convergence samples (conf-gated, async.convergence.sample):
        # (version, loss, grad_norm) tuples fold into the loss-vs-wallclock
        # / loss-vs-version curves, stamped with THIS PS's run clock and
        # the staleness it observes right now
        _cv_fold(header.get("cv"), clock=self._clock,
                 wall_ms=self._bus_time_ms())
        tc = _trace.TraceContext.from_wire(header["tc"]) \
            if "tc" in header else None
        t_queue0 = _trace.now_ms() if tc is not None else 0.0
        sup = self.supervisor
        if sup is not None and not sup.owns(proc, wid):
            # membership-stale push: the shard was re-homed (rejoin deposed
            # this surrogate) -- drop it like any other too-stale gradient,
            # but do not tick the merge clock (nothing was considered)
            with self._lock:
                self.membership_rejects += 1
                ack = {"op": "ACK", "accepted": False, "released": True,
                       "done": self._done.is_set()}
                self._dedup.record(header, ack)
            _send_msg(conn, ack)
            return
        if sup is not None:
            sup.touch(wid, proc)
        diff = None
        if header.get("gq") is not None:
            # quantized gradient (net/wirecodec.py, async.codec.push):
            # fp16/int8 payload back to dense f32.  The worker's error-
            # feedback accumulator already folded this push's
            # quantization residual into its NEXT gradient, so the
            # server applies the dequantized value as-is -- stateless
            # here by design.  ASAGA never quantizes (exact history
            # scalars), so diff stays None.
            try:
                g_host = wirecodec.decode_grad(header, payload, self.d)
            except ValueError as e:
                _send_msg(conn, {"op": "ERR",
                                 "msg": f"bad quantized push: {e}"})
                return
        elif header.get("enc") == "sparse":
            # (idx, val) pair gradient (rcv1-class): scatter into dense on
            # host -- the PS's apply path is dense either way
            nnz = int(header["nnz"])
            idx_g = np.frombuffer(payload[: 4 * nnz], np.uint32)
            val_g = np.frombuffer(payload[4 * nnz: 8 * nnz], np.float32)
            g_host = np.zeros(self.d, np.float32)
            g_host[idx_g] = val_g
            if self.algo == "asaga":
                diff = np.frombuffer(payload[8 * nnz:], np.float32)
        else:
            raw = np.frombuffer(payload, np.float32)
            if self.algo == "asaga":
                g_host, diff = raw[: self.d], raw[self.d:]
            else:
                g_host = raw
        # merge queue: the payload was decoded OUTSIDE the lock; whoever
        # holds the model lock next coalesces every pending push into one
        # fused device apply.  Per-push accept/reject, dedup, clock, and
        # calibration bookkeeping stay per item (FIFO), exactly as the
        # serial path ordered them -- only the device dispatch is batched.
        item = _PendingPush(wid, ts, g_host, diff, header, len(payload),
                            tc, t_queue0)
        self._merge_q.append(item)
        with self._lock:
            while not item.done:
                self._drain_merge_locked()
        # pre-warm the pull snapshot for the version this drain produced,
        # OFF the model lock, on this (push) thread: the next cohort pull
        # finds it published and pays zero build latency.  A no-op when a
        # peer already built it; worst case under heavy churn the build
        # races a newer drain and is skipped at publish (CRC-gated
        # fallback keeps even the raciest interleaving degrade-to-full,
        # never wrong).
        if item.accepted:
            self._model_snap()
        if tc is not None:
            # staleness in TIME (ASAP's quantity): age of the model basis
            # this gradient was computed on = now - that version's pull.
            # merge.queue covers decode + wait for the single-writer model
            # lock; merge.apply covers the drain this push rode (tau
            # filter + fused apply dispatch) under the lock.
            self._fold_span(_trace.Span(
                stage=_trace.MERGE_QUEUE, trace_id=tc.trace_id,
                span_id=_trace._new_id(8), parent_id=tc.span_id,
                worker_id=wid, model_version=ts, start_ms=t_queue0,
                dur_ms=max(0.0, item.t_apply0 - t_queue0),
            ))
            self._fold_span(_trace.Span(
                stage=_trace.MERGE_APPLY, trace_id=tc.trace_id,
                span_id=_trace._new_id(8), parent_id=tc.span_id,
                worker_id=wid, model_version=ts, start_ms=item.t_apply0,
                dur_ms=max(0.0, item.t_done - item.t_apply0),
                staleness=int(item.staleness),
                staleness_ms=float(item.task_ms),
                accepted=bool(item.accepted),
            ))
        if self.bus is not None:
            from asyncframework_tpu.metrics.bus import GradientMerged

            self.bus.post(GradientMerged(
                self._bus_time_ms(), worker_id=wid,
                staleness=int(item.staleness),
                accepted=bool(item.accepted),
                iteration=item.k_at_merge,
            ))
        with self._wave_cv:
            self._wave_cv.notify_all()  # a wave may now meet its threshold
        _send_msg(conn, item.ack)
        if item.do_snapshot:
            # printer_freq cadence: signal the async checkpoint thread --
            # nobody's next message waits behind the disk write
            self._ckpt_trigger.set()

    @_prof.zoned("merge.drain")
    def _drain_merge_locked(self) -> None:
        """Caller holds ``_lock``.  Drain up to ``_merge_max`` pending
        pushes in FIFO order -- per-push accept/reject, dedup, clock, and
        calibration bookkeeping identical to the serial path -- then run
        ONE fused device apply for all accepted gradients
        (``ops/steps.make_*_apply_merge``, bit-identical to the serial
        apply order).  A push landing on the printer_freq snapshot
        boundary closes its batch so the host copy below pins exactly
        that version."""
        import jax

        drained: List[_PendingPush] = []
        batch: List[Tuple[_PendingPush, Optional[np.ndarray]]] = []
        # replication stream (parallel/replication.py): the standby
        # applies from exactly this clock, so capture it before any
        # item ticks it
        pre_clock = self._clock
        # donation guard, captured BEFORE any accept mutates gen/_snap:
        # the fused kernel donates the model buffer (in-place apply), so
        # it may only run when the OUTGOING version already exists as a
        # host-side _ModelSnap -- then no rebuild, checkpoint, or delta
        # encode can ever need the donated device buffer again.  The
        # accepted-push pre-warm (_model_snap right after each drain)
        # makes this the overwhelmingly common case.
        prev_snap = self._snap
        prev_gen = self._model_gen
        # adaptive control: the EFFECTIVE merge budget moves within
        # [1, _merge_max] (the compiled kernel bound; padding makes any
        # smaller batch exact).  0 = no override = the configured bound.
        budget = self._ctrl_merge or self._merge_max
        budget = max(1, min(budget, self._merge_max))
        while self._merge_q and len(drained) < budget:
            item = self._merge_q.popleft()
            drained.append(item)
            item.t_apply0 = _trace.now_ms() if item.tc is not None else 0.0
            self.push_bytes += item.payload_len
            if self._t0 is not None:
                self._last_contact[item.wid] = self._now_ms()
            self.pushes_by_wid[item.wid] = (
                self.pushes_by_wid.get(item.wid, 0) + 1
            )
            staleness = self._clock - item.ts
            self.max_staleness = max(self.max_staleness, staleness)
            task_ms = self._now_ms() - self._pull_times.get(
                item.wid, self._now_ms()
            )
            if self._cal_n < self.cfg.effective_calibration_iters():
                self._cal_ms += task_ms
                self._cal_n += 1
                if self._cal_n >= self.cfg.effective_calibration_iters():
                    self.avg_delay_ms = self._cal_ms / max(self._cal_n, 1)
            idx = None
            if self.algo == "asaga":
                # ASAGA's filter quirk: accept iff k - staleness <= taw
                # (SparkASAGAThread.scala:184; the ASGD driver tests
                # staleness <= taw).  A push whose pull-time sample the PS
                # no longer holds (restart) cannot commit -- drop it.
                idx = self._pending_idx.pop(item.wid, None)
                accepted = (
                    self._k - staleness <= self.cfg.taw
                    and self._k < self.cfg.num_iterations
                    and idx is not None
                )
            else:
                accepted = (
                    staleness <= self.cfg.taw
                    and self._k < self.cfg.num_iterations
                )
            if accepted:
                # bump the model generation and unpublish the snapshot
                # BEFORE the clock tick: a concurrent lock-free pull
                # that reads this item's new clock must see the new
                # generation too and keep the snapshot's own (older)
                # version stamp -- never pair new version, old bytes.
                # Dropped pushes tick the clock WITHOUT bumping: the
                # model is unchanged, so the snapshot stays valid.
                self._model_gen += 1
                self._snap = None
                batch.append((item, idx))
                self._k += 1
                self.accepted += 1
                self.accepted_by_wid[item.wid] = (
                    self.accepted_by_wid.get(item.wid, 0) + 1
                )
                if self._k % self.cfg.printer_freq == 0:
                    item.do_snapshot = True
                if self._k >= self.cfg.num_iterations:
                    self._done.set()
                    if self.supervisor is not None:
                        # run complete: pin membership -- post-done silence
                        # (evaluation, teardown) is not death
                        self.supervisor.freeze()
            else:
                self.dropped += 1
            self._clock += 1
            item.staleness = staleness
            item.task_ms = task_ms
            item.accepted = accepted
            if accepted:
                # delay-adaptive step damping (CTRL law; 1.0 = exact
                # undamped legacy whenever control is off): decided per
                # item at drain time from ITS observed staleness, so a
                # dedup-replayed stamp -- which never reaches a second
                # drain -- keeps exactly the factor it was applied with
                item.damp = self._item_damp(item.wid, staleness)
            item.k_at_merge = self._k
            self._wstat_merge(item.wid, staleness, accepted)
            ack = {"op": "ACK", "accepted": bool(accepted),
                   "done": self._done.is_set()}
            # record INSIDE the lock, before any send: (1) a retry after a
            # lost ACK must find the (sid, seq) applied; (2) the checkpoint
            # writer serializes state under this same lock, so a saved
            # model can never be missing the dedup entry of a push it
            # already contains (that gap would re-apply the push after a
            # restart)
            self._dedup.record(item.header, ack)
            item.ack = ack
            if item.do_snapshot:
                # close the batch at the snapshot boundary: the pinned
                # host copy must be exactly version k, not a later one
                break
        if batch:
            donate_ok = (prev_snap is not None
                         and prev_snap.gen == prev_gen)
            if len(batch) == 1 or self._apply_merge is None:
                self._apply_one(batch[0][0], batch[0][1])
            elif not donate_ok:
                # outgoing version not host-published (two drains raced
                # faster than the off-lock pre-warm): the fused kernel
                # would donate a device buffer the next rebuild still
                # needs.  Apply serially instead -- the merge kernel is
                # bit-identical to this order by contract, so the model
                # cannot tell which path ran.
                for it, idx2 in batch:
                    self._apply_one(it, idx2)
            else:
                # donation window: until this drain publishes its new
                # basis below, point rebuilds at the HOST copy of the
                # outgoing version -- the device buffer dies the moment
                # the donated dispatch below runs
                self._snap_basis = (prev_snap.ts, prev_snap.w_host,
                                    prev_snap.gen)
                # ONE fused device dispatch for the whole drained batch:
                # padded to the static merge bound so the kernel compiles
                # once, masked so padding slots are no-ops.  The scratch is
                # reused (no per-drain allocation) and padding rows keep
                # whatever a previous drain left: the scan's
                # `where(mask > 0, ...)` discards their w2 elementwise, so
                # they never touch the result
                G, mask = self._merge_G, self._merge_mask
                for j, (it, _idx) in enumerate(batch):
                    G[j] = it.g_host
                    # a mask slot carries the per-item step-DAMP factor
                    # (1.0 = classic keep bit, exact; 0 below = skip)
                    mask[j] = it.damp
                mask[len(batch):] = 0.0
                G_dev = jax.device_put(G, self.device)
                m_dev = jax.device_put(mask, self.device)
                if self.algo == "asaga":
                    self._w, self._ab = self._apply_merge(
                        self._w, self._ab, G_dev, m_dev
                    )
                    with self._saga_lock:  # vs checkpoint table copies
                        for it, idx2 in batch:
                            self._table[it.wid][idx2] = (
                                it.diff[: idx2.size]
                            )
                else:
                    self._w, self._k_dev = self._apply_merge(
                        self._w, G_dev, m_dev, self._k_dev
                    )
            # publish the new build basis (O(1) tuple swap under the lock
            # this drain already holds): the next snapshot rebuild reads
            # it lock-free instead of queueing on the model lock
            self._snap_basis = (self._clock, self._w, self._model_gen)
            # version birth (serving plane): this drain PUBLISHED a new
            # model version -- stamp its clock with the wall time so
            # SUBSCRIBE replies can price freshness age in ms (O(1), its
            # own small lock; never the pull path's).
            if self._t0 is not None:
                with self._born_lock:
                    self._ver_born[self._clock] = self._now_ms()
                    while len(self._ver_born) > 1024:
                        self._ver_born.popitem(last=False)
            self.merge_batches += 1
            self.merge_merged += len(batch)
            self.merge_batch_max = max(self.merge_batch_max, len(batch))
        if self.repl is not None and drained:
            # hot-standby replication: hand the WHOLE drained batch --
            # verdicts, (sid, seq) stamps, staleness, and the accepted
            # gradients' host arrays -- to the stream.  O(items) list
            # work under the lock; serialization and I/O happen on the
            # sender thread.  Dropped items ride too: they tick the
            # standby's clock and land their dedup verdicts, so a
            # promoted standby re-answers EVERY replayed stamp.
            items = []
            grads = []
            for it in drained:
                # the per-item step-DAMP factor rides the stream: the
                # mirror must apply EXACTLY the step the primary did or
                # its model silently diverges (and a promotion would
                # serve the divergent copy)
                items.append([it.wid, it.ts, 1 if it.accepted else 0,
                              it.header.get("sid"), it.header.get("seq"),
                              it.ack, int(it.staleness),
                              float(it.damp)])
                if it.accepted:
                    grads.append(it.g_host)
            self.repl.enqueue(pre_clock, items, grads,
                              [self._cal_ms, self._cal_n,
                               self.avg_delay_ms])
        if drained:
            # flight-recorder breadcrumb (metrics/flightrec.py): one
            # event per drain so a SIGKILLed PS's dump ends with its
            # last applied batch (no-op when no recorder is installed)
            _flight.note("merge", clock=self._clock, k=self._k,
                         batch=len(drained),
                         accepted=self.accepted, dropped=self.dropped)
        for item in drained:
            if item.do_snapshot:
                # host copy NOW: the snapshot must pin this version (the
                # boundary item closed its batch above, so _w is exactly
                # the k it rode in on).  Owned copy, not a buffer view:
                # a later donated drain overwrites the device memory
                self._snapshots.append(
                    (self._now_ms(), np.array(self._w, np.float32))
                )
            if item.tc is not None:
                item.t_done = _trace.now_ms()
            item.done = True

    def _apply_one(self, item: _PendingPush,
                   idx: Optional[np.ndarray]) -> None:
        """Serial single-push apply (the classic one-dispatch path; caller
        holds ``_lock``)."""
        import jax

        g_dev = jax.device_put(item.g_host, self.device)
        if self.algo == "asaga":
            # three-term update + alpha_bar advance (delta == g is exact
            # over DCN; see __init__); then the ScalarMap merge -- commit
            # this push's candidate scalars
            self._w, self._ab = self._apply(self._w, self._ab, g_dev, g_dev)
            with self._saga_lock:  # vs checkpoint table copies
                self._table[item.wid][idx] = item.diff[: idx.size]
        elif item.damp != 1.0 and self._apply_damped is not None:
            # delay-adaptive damped apply: the SAME expression as the
            # damped merge-kernel body, so serial and fused drains stay
            # bit-identical at every damp value
            self._w, self._k_dev = self._apply_damped(
                self._w, g_dev, self._k_dev, np.float32(item.damp))
        else:
            self._w, self._k_dev = self._apply(self._w, g_dev, self._k_dev)

    # ------------------------------------------------------------ evaluation
    def wait_done(self, timeout_s: float,
                  progress_timeout_s: Optional[float] = None) -> "WaitDone":
        """Progress-aware wait for the run to finish.

        Returns a truthy :class:`WaitDone` on completion.  On timeout --
        or, with ``progress_timeout_s``, as soon as NO worker has contacted
        the PS and the merge clock has not moved for that long -- returns a
        falsy ``WaitDone`` carrying the per-worker last-contact +
        contribution-bitmap diagnostic instead of a bare ``False``, so a
        stalled run names its silent workers instead of hanging mute for
        the full timeout.
        """
        deadline = time.monotonic() + timeout_s
        last_progress = time.monotonic()
        seen_clock = -1
        seen_contact = -1.0
        while True:
            left = deadline - time.monotonic()
            if self._done.wait(timeout=max(0.0, min(0.2, left))):
                return WaitDone(True, None)
            now = time.monotonic()
            with self._lock:
                clock = self._clock
                contact = max(self._last_contact.values(), default=-1.0)
            if clock != seen_clock or contact != seen_contact:
                seen_clock, seen_contact = clock, contact
                last_progress = now
            stalled = (
                progress_timeout_s is not None
                and now - last_progress > progress_timeout_s
            )
            if stalled or now >= deadline:
                return WaitDone(False, self.progress_diagnostic(
                    stalled="stalled" if stalled else "timeout"
                ))

    def progress_diagnostic(self, stalled: str = "timeout") -> str:
        """Per-worker last-contact ages, push/accept counts, and the
        contribution bitmap -- everything needed to see WHO went silent."""
        with self._lock:
            now = self._now_ms() if self._t0 is not None else 0.0
            k, clock = self._k, self._clock
            contact = dict(self._last_contact)
            pushes = dict(self.pushes_by_wid)
            accepted = dict(self.accepted_by_wid)
        member = (self.supervisor.membership()
                  if self.supervisor is not None else {})
        nw = self.cfg.num_workers
        bitmap = "".join(
            "1" if accepted.get(w, 0) > 0 else "0" for w in range(nw)
        )
        lines = [
            f"PS {stalled}: k={k}/{self.cfg.num_iterations} "
            f"clock={clock} contributed-bitmap={bitmap}",
        ]
        for w in range(nw):
            age = contact.get(w)
            age_s = "never" if age is None else f"{now - age:8.0f}ms ago"
            extra = ""
            m = member.get(w)
            if m:
                extra = f" state={m['state']} owner={m['owner']}"
            lines.append(
                f"  wid {w:3d}: last-contact {age_s:>14}  "
                f"pushes={pushes.get(w, 0):<6d} "
                f"accepted={accepted.get(w, 0):<6d}{extra}"
            )
        return "\n".join(lines)

    def snapshot_stack(self) -> Tuple[List[float], np.ndarray]:
        with self._lock:
            final = (self._now_ms(), np.array(self._w, np.float32))
            snaps = list(self._snapshots) + [final]
        times = [t for (t, _w) in snaps]
        W = np.stack([w for (_t, w) in snaps])
        return times, W

    def collect_eval(self, num_worker_procs: int, timeout_s: float,
                     await_all: bool = False) -> Optional[np.ndarray]:
        """Sum per-process snapshot losses pushed via EVAL_RESULT.

        With the supervisor, the expected count is clamped to processes
        that were still ALIVE when the run finished: a crashed worker's
        EVAL never comes, but its adopted shards are scored by their
        adopter -- the union still covers the full dataset, so waiting
        for the dead process would only trade the objective for a
        timeout.  ``await_all`` skips the clamp (a process that joined
        after DONE is alive but was not in the frozen roster)."""
        deadline = time.monotonic() + timeout_s
        with self._eval_cv:
            while True:
                expected = num_worker_procs
                if self.supervisor is not None and not await_all:
                    # clamp only when processes actually registered (an
                    # unelastic client set leaves the roster empty)
                    live = self.supervisor.live_proc_count()
                    if live > 0:
                        expected = min(expected, live)
                if len(self._eval_results) >= expected:
                    break
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._eval_cv.wait(timeout=min(left, 0.2))
            total = None
            for arr in self._eval_results.values():
                total = arr if total is None else total + arr
            return total

    @property
    def dedup_hits(self) -> int:
        """Retried PUSHes answered from the dedup window (each one is a
        gradient that would have merged twice before net/session.py)."""
        return self._dedup.hits

    def stop(self) -> None:
        self._stop.set()
        self._done.set()
        if self.repl is not None:
            self.repl.stop()
        if getattr(self, "_ts_source", None) is not None:
            from asyncframework_tpu.metrics import timeseries as _ts

            # identity-gated: a stopped PS must not unhook its replacement
            _ts.unregister_source("ps", self._ts_source)
        if getattr(self, "_workers_section", None) is not None:
            from asyncframework_tpu.metrics import live as _live

            _live.unregister_status_section("ps_workers",
                                            self._workers_section)
        if self.supervisor is not None:
            self.supervisor.stop()
        with self._wave_cv:
            self._wave_cv.notify_all()
        try:
            self._srv.close()
        except OSError:
            pass
        # reap on stop: drop every finished handler thread (live ones are
        # daemons draining their last reply; they exit with the sockets)
        self._threads = [x for x in self._threads if x.is_alive()]


class FencedError(ConnectionError):
    """The server refused this client's ops under epoch fencing and the
    client cannot self-heal by adopting a newer epoch -- the server
    itself is at (or below) the client's epoch, i.e. the client is
    talking to a deposed zombie.  Subclasses ConnectionError so worker
    loops treat it like any other dead endpoint: pace, re-dial, and
    land on the current owner."""


# -------------------------------------------------------------- worker side
class PSClient:
    """One TCP connection to the PS (workers may hold several, one per
    logical worker id, or share one -- the protocol is synchronous per
    connection, like an RpcEndpointRef).

    Transport faults are the retry layer's problem now: every RPC routes
    through a :class:`~asyncframework_tpu.net.RetryPolicy` (backoff +
    jitter + per-endpoint circuit breaker), reconnecting between attempts.
    Mutating ops (PUSH) are stamped with this client's session ``(sid,
    seq)`` so a retry after a lost ACK is answered from the PS's dedup
    window instead of merging the gradient twice."""

    def __init__(self, host: str, port: int, timeout_s: float = 120.0,
                 retry: Optional[RetryPolicy] = None,
                 session: Optional[ClientSession] = None,
                 proc: Optional[str] = None,
                 recorder: Optional["_trace.TraceRecorder"] = None,
                 pull_mode: Optional[str] = None,
                 pl_stats: Optional[_PipelineStats] = None,
                 cv_buf=None, epoch: int = 0,
                 push_codec: Optional[str] = None, ctrl_sink=None,
                 shm: Optional[bool] = None):
        self.host, self.port = host, int(port)
        # adaptive control plane: a ControlSink (parallel/controller.py)
        # shared by this worker process's clients.  PULL requests stamp
        # the sink's decision seq (``cs``) and PULL replies carrying a
        # newer CTRL payload install into it (monotone by (ep, seq)).
        # None (every non-controlled client) = no header field,
        # byte-identical wire.
        self.ctrl_sink = ctrl_sink
        self.endpoint = f"{host}:{self.port}"
        # fencing epoch this client stamps on every PULL/PUSH/SUBSCRIBE
        # (``ep`` header key; 0 = fencing off, no key, byte-identical
        # legacy wire).  Seeded from the WELCOME handshake and advanced
        # by MODEL replies / REJECT_FENCED verdicts -- a fenced client
        # adopts the minted epoch and its NEXT op is admitted; entries
        # already stamped (the windowed push pipe replays verbatim) keep
        # their old epoch and are rejected exactly once each, which is
        # the point: a deposed incarnation's buffered writes never land.
        self.epoch = int(epoch)
        self.fenced_replies = 0
        self.retry = retry if retry is not None else RetryPolicy.from_conf(
            attempt_timeout_s=timeout_s
        )
        self.session = session if session is not None else ClientSession()
        # version-gated delta pulls (net/wiredelta.py): in 'delta' mode the
        # client advertises its basis version (``have=<ts>``) on every
        # PULL and keeps the last successfully decoded model per wid so a
        # NOT_MODIFIED / XDELTA reply can reconstruct byte-exactly.  Any
        # decode mismatch or cache miss falls back to a full pull -- the
        # basis is only ever replaced by a CRC-validated reconstruction or
        # an authoritative full payload, never left wrong.
        if pull_mode is None:
            from asyncframework_tpu.conf import PULL_MODE, global_conf

            pull_mode = str(global_conf().get(PULL_MODE))
        self.pull_mode = pull_mode
        # gradient quantization (net/wirecodec.py, async.codec.push):
        # 'off' (default) ships raw f32 -- byte-identical legacy wire;
        # fp16/int8 quantize each dense ASGD push and keep the residual
        # in a per-wid error-feedback accumulator folded into the next
        # push, so the model's deviation from the uncompressed
        # trajectory is bounded by ONE step's quantization error.
        if push_codec is None:
            from asyncframework_tpu.conf import CODEC_PUSH, global_conf

            push_codec = str(global_conf().get(CODEC_PUSH))
        self.push_codec = push_codec
        self._ef: Dict[int, np.ndarray] = {}  # wid -> carried residual
        # wid -> (ts, float32 basis array, crc of its bytes)
        self._basis: Dict[int, Tuple[int, np.ndarray, int]] = {}
        self.pull_wenc: Dict[str, int] = {"full": 0, "nm": 0, "xdelta": 0}
        self.pull_model_bytes = 0  # model-part payload bytes received
        self.delta_fallbacks = 0   # decode mismatch/cache miss full re-pulls
        # distributed tracing: completed spans from this process's recorder
        # piggyback on PUSH (and BYE) headers -- the PS folds them into its
        # event stream, so spans survive this worker's death.  None =
        # tracing off for this client, zero extra wire bytes.
        self.recorder = recorder
        # pipelined-loop counters (prefetch hits / stale discards /
        # in-flight depth): deltas piggyback on PUSH and BYE headers the
        # same way spans do.  None (every non-pipelined client) = no
        # header field, byte-identical wire.
        self.pl_stats = pl_stats
        # convergence telemetry (metrics/timeseries.ConvergenceBuffer):
        # buffered (version, loss, grad_norm) samples ride PUSH/BYE
        # headers as the ``cv`` entry, same discipline as spans and
        # pipeline counters.  None (the default) = no header field,
        # byte-identical wire.
        self.cv_buf = cv_buf
        # elastic membership: the worker PROCESS token stamped on every
        # PULL/PUSH so the PS supervisor knows who serves which shard;
        # None = classic fixed-membership client
        self.proc = proc
        self.released = False    # the PS deposed this client's wid
        self._orders: List[int] = []  # adoption orders from PULL replies
        # windowed push pipe (push_start/push_finish): sent-but-unACKed
        # entries, oldest first -- replayed wholesale on reconnect.  The
        # window lock serializes senders against the reaper's
        # reconnect+replay; receives happen outside it (full duplex).
        from collections import deque as _dq
        self._push_window: "_dq[list]" = _dq()
        self._win_lock = threading.Lock()
        # the one in-flight prefetched PULL (pull_start/pull_finish)
        self._pending_pull: Optional[tuple] = None
        # shared-memory transport (net/shmring.py): when enabled AND the
        # PS is colocated (loopback peer), each (re)dial opportunistically
        # upgrades the fresh TCP connection to a ring pair -- same framed
        # protocol, fewer copies, no GIL on the byte path.  A ring-level
        # failure latches _shm_failed so the NEXT dial stays on plain
        # TCP: the degrade is one reconnect away and never loops.
        if shm is None:
            from asyncframework_tpu.conf import SHM_ENABLED, global_conf

            shm = bool(global_conf().get(SHM_ENABLED))
        self.shm = bool(shm)
        self._shm_failed = False
        self._sock: Optional[socket.socket] = None
        self.bytes_pushed = 0  # payload bytes shipped by push/push_saga
        # eager first dial (historical behavior: constructing a client to a
        # dead PS raises) -- but through the policy, so a PS mid-restart is
        # ridden out instead of surfaced
        self._call_raw(connect_only=True)

    @property
    def sock(self) -> Optional[socket.socket]:
        return self._sock

    def _drop_sock(self) -> None:
        if self._sock is not None:
            if isinstance(self._sock, _shmring.ShmSocket):
                # a dropped ring transport is never resurrected blind:
                # the next dial stays on plain TCP (the upgrade is
                # opportunistic, the degrade is sticky per client --
                # reconnect-and-retry loops must converge, not oscillate
                # between a wedged ring and the socket)
                self._shm_failed = True
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _dial(self):
        """Fresh connection under this client's transport policy: the
        TCP dial, then the opportunistic shm-ring upgrade (colocated
        peer + conf gate + not previously degraded)."""
        sock = _frame.connect((self.host, self.port),
                              timeout=self.retry.attempt_timeout_s)
        if self.shm and not self._shm_failed:
            sock, _ = _shmring.maybe_upgrade(sock)
        return sock

    def _call_raw(self, header: Optional[dict] = None, payload: bytes = b"",
                  connect_only: bool = False) -> Tuple[dict, bytes]:
        """One stamped-or-not request/reply under the retry policy.  The
        header is REUSED verbatim across attempts -- a stamped op keeps its
        (sid, seq) so the server can dedup."""

        def attempt() -> Tuple[dict, bytes]:
            try:
                if self._sock is None:
                    self._sock = self._dial()
                if connect_only:
                    return {}, b""
                _send_msg(self._sock, header, payload)
                return _recv_msg(self._sock)
            except OSError:
                # dead/poisoned connection: never reuse it for the retry
                # (and _drop_sock pins a failed ring transport to TCP)
                self._drop_sock()
                raise

        return self.retry.call(attempt, endpoint=self.endpoint)

    def _proc_hdr(self, hdr: dict) -> dict:
        if self.proc is not None:
            hdr["proc"] = self.proc
        if self.epoch:
            hdr["ep"] = self.epoch
        return hdr

    def _note_orders(self, header: dict) -> None:
        if "adopt" in header:
            self._orders.extend(int(w) for w in header["adopt"])
        if self.ctrl_sink is not None and "ctrl" in header:
            # adaptive-control decisions ride replies like adoption
            # orders; the sink's monotone install discards stale ones
            self.ctrl_sink.install(header["ctrl"])

    def take_orders(self) -> List[int]:
        """Adoption orders received so far (drained)."""
        out, self._orders = self._orders, []
        return out

    def hello(self, proc: str, wids: List[int],
              pid: Optional[int] = None) -> dict:
        """Introduce this worker process to the PS (elastic registration;
        a fixed-membership PS just says WELCOME and ignores it).  Carries
        this process's /proc start time next to its pid so the
        supervisor's liveness probe can tell a recycled pid from the
        registered member."""
        import socket as _socket

        hdr = {
            "op": "HELLO", "proc": proc, "wids": [int(w) for w in wids],
            "pid": pid, "host": _socket.gethostname(),
        }
        if pid is not None:
            pstart = supervisor_mod.proc_start_time(pid)
            if pstart is not None:
                hdr["pstart"] = pstart
        # advertise this process's telemetry endpoint (when one serves):
        # the supervisor records it per member and the cluster observer
        # discovers worker scrape targets from the membership instead of
        # needing static endpoints.  Absent when telemetry is off -- the
        # byte-identity suites' wire is unchanged.
        from asyncframework_tpu.metrics import live as _live

        mport = _live.telemetry_port()
        if mport:
            hdr["mport"] = int(mport)
        header, _ = self._call_raw(hdr)
        return header

    def _traced_call(self, tr, stage: str, header: dict,
                     payload: bytes = b"") -> Tuple[dict, bytes]:
        """One RPC under an optional update trace: installs the ambient
        context (frame.send_msg stamps the ``tc`` header from it) for the
        call's duration and records the client-observed round-trip span.
        With ``tr=None`` this is exactly ``_call_raw``."""
        if tr is None:
            return self._call_raw(header, payload)
        token = tr.rpc_begin(stage)
        try:
            out = self._call_raw(header, payload)
        except BaseException:
            _trace.set_current(None)  # never leak the context on failure
            raise
        # wire cost of the RPC that just completed (frame bytes, both
        # directions) rides the rtt span -- latency AND volume decompose
        # per stage (net/frame.py counts at the choke point)
        tr.rpc_end(token, bytes=_frame.last_io_bytes())
        return out

    def _have_hdr(self, wid: int, hdr: dict) -> dict:
        """Advertise this wid's basis version on a PULL (delta mode),
        and the installed CTRL decision seq (``cs``) when this client
        rides a control sink -- the PS re-delivers the CTRL payload
        only while the stamp lags its newest decision."""
        if self.pull_mode == "delta":
            basis = self._basis.get(wid)
            if basis is not None:
                hdr["have"] = basis[0]
        if self.ctrl_sink is not None:
            hdr["cs"] = self.ctrl_sink.stamp
        return hdr

    def _decode_model(self, wid: int, header: dict, payload: bytes,
                      extra_len: int) -> Optional[np.ndarray]:
        """The model part of a MODEL reply -> float32 array, maintaining
        the basis cache.  ``extra_len`` is the trailing non-model payload
        (ASAGA's idx/alpha block).  Returns None on decode mismatch or
        basis cache miss -- the caller MUST fall back to a full pull; the
        basis is only ever replaced by a CRC-validated reconstruction or
        an authoritative full payload, never left wrong."""
        ts = int(header["ts"])
        wenc = header.get("wenc")
        if wenc is None or wenc == wiredelta.FULL:
            if wenc is None:  # legacy reply: model part is the payload head
                end = len(payload) - extra_len
                model_part = payload[:end] if extra_len else payload
            else:
                model_part = payload[: int(header.get("wlen", 0))]
            w = np.frombuffer(model_part, np.float32)
            if self.pull_mode == "delta":
                crc_hdr = header.get("crc")
                self._basis[wid] = (
                    ts, w,
                    int(crc_hdr) if crc_hdr is not None
                    else wiredelta.crc(model_part),
                )
            self.pull_wenc["full"] += 1
            self.pull_model_bytes += len(model_part)
            return w
        model_part = payload[: int(header.get("wlen", 0))]
        basis = self._basis.get(wid)
        crc_hdr = header.get("crc")
        w = wiredelta.decode(
            wenc, model_part, int(header.get("nnz", 0)),
            basis[1] if basis is not None else None,
            int(crc_hdr) if crc_hdr is not None else None,
            basis[2] if basis is not None else None,
        )
        if w is None:
            return None
        self._basis[wid] = (ts, w, int(crc_hdr))
        self.pull_wenc[wenc] = self.pull_wenc.get(wenc, 0) + 1
        self.pull_model_bytes += len(model_part)
        return w

    def _note_fenced(self, header: dict) -> bool:
        """Fold one REJECT_FENCED verdict: adopt the minted epoch when it
        is NEWER than ours (we were deposed and can self-heal -- the next
        op, stamped fresh, will be admitted) and return True; False means
        the SERVER is the stale party (a zombie) and cannot serve us."""
        self.fenced_replies += 1
        srv_ep = int(header.get("epoch", 0))
        if srv_ep > self.epoch:
            self.epoch = srv_ep
            return True
        return False

    def _process_pull_reply(self, wid: int, header: dict, payload: bytes,
                            make_hdr, extra_len_of, tr
                            ) -> Optional[Tuple[dict, bytes, np.ndarray]]:
        """Shared back half of a model pull: RELEASED/DONE handling,
        REJECT_FENCED self-healing, adoption orders, and decode with the
        ONE-full-re-pull fallback (basis cache miss, CRC disagreement --
        a full reply always decodes; never a wrong model).  Returns
        (header, payload, w), or None on RELEASED/DONE (``self.released``
        distinguishes them)."""
        fence_left = True
        fallback_left = True
        while True:
            op = header["op"]
            if op == "RELEASED":
                self.released = True
                return None
            if op == "DONE":
                return None
            if op == "ERR":
                # a refusing endpoint (a hot STANDBY answers the
                # training plane this way): surface as a dead endpoint
                # so loops pace and sharded facades re-resolve the map
                raise ConnectionError(
                    f"{self.endpoint} refused: {header.get('msg')!r}")
            if op == "REJECT_FENCED":
                # deposed basis: adopt the minted epoch and re-pull ONCE
                # with the fresh stamp (the current owner admits it); a
                # second fence, or a server whose epoch does not exceed
                # ours, is a zombie endpoint -- surface it
                if self._note_fenced(header) and fence_left:
                    fence_left = False
                    header, payload = self._traced_call(
                        tr, _trace.PULL_RTT,
                        self._proc_hdr(self._have_hdr(wid, make_hdr())),
                    )
                    continue
                raise FencedError(
                    f"fenced by {self.endpoint} at epoch "
                    f"{int(header.get('epoch', 0))} (client epoch "
                    f"{self.epoch})"
                )
            srv_ep = header.get("ep")
            if srv_ep is not None and int(srv_ep) > self.epoch:
                # replies advertise the server's current epoch: track it
                # so our next op is stamped current without a fence trip
                self.epoch = int(srv_ep)
            self._note_orders(header)
            w = self._decode_model(wid, header, payload,
                                   extra_len_of(header))
            if w is not None:
                return header, payload, w
            if not fallback_left:  # pragma: no cover - full always decodes
                break
            fallback_left = False
            self._basis.pop(wid, None)
            self.delta_fallbacks += 1
            header, payload = self._traced_call(
                tr, _trace.PULL_RTT, self._proc_hdr(make_hdr())
            )
        raise ConnectionError("PULL: full reply failed to decode")

    def _pull_model_rpc(self, wid: int, make_hdr, extra_len_of, tr
                        ) -> Optional[Tuple[dict, bytes, np.ndarray]]:
        """One negotiated model pull (request + reply + fallback)."""
        header, payload = self._traced_call(
            tr, _trace.PULL_RTT,
            self._proc_hdr(self._have_hdr(wid, make_hdr())),
        )
        return self._process_pull_reply(wid, header, payload, make_hdr,
                                        extra_len_of, tr)

    # ---------------------------------------------------- prefetched pull
    # The pipelined loop's pull prefetch: pull_start SENDS the next
    # PULL and returns (the request parks in the PS wave gate and the
    # reply accumulates in this socket's kernel buffer while the caller
    # computes); pull_finish receives and decodes it.  Single-threaded
    # by design -- the overlap lives in the socket, not in a thread --
    # and safe to retry: a PULL is idempotent and unstamped, so a
    # reconnect simply re-sends it.

    def pull_start(self, wid: int, tr=None) -> None:
        """Send the next PULL without waiting for the reply."""
        hdr = self._proc_hdr(self._have_hdr(wid, {"op": "PULL",
                                                  "wid": wid}))
        token = tr.rpc_begin(_trace.PULL_RTT) if tr is not None else None
        if tr is not None:
            _trace.set_current(None)
        # trailing slot: sent frame bytes, captured at send (see the
        # push-window entries)
        pending = [hdr, tr, token, 0]
        self._pending_pull = pending
        try:
            if self._sock is None:
                self._sock = self._dial()
            if tr is not None:
                _trace.set_current(tr.ctx)
            try:
                _send_msg(self._sock, hdr)
                pending[3] = _frame.last_sent_bytes()
            finally:
                if tr is not None:
                    _trace.set_current(None)
        except OSError:
            self._drop_sock()  # deferred: pull_finish re-dials + re-sends

    def pull_ready(self) -> bool:
        """True when the prefetched reply's first bytes are already in
        the kernel buffer (the prefetch fully hid the pull)."""
        if self._sock is None:
            return False
        if isinstance(self._sock, _shmring.ShmSocket):
            # ring bytes never show on the retained TCP fd; ask the
            # ring's counters instead (same zero-wait semantics)
            return self._sock.readable()
        import select

        try:
            return bool(select.select([self._sock], [], [], 0.0)[0])
        except (OSError, ValueError):
            return False

    def pull_finish(self, wid: int
                    ) -> Optional[Tuple[int, np.ndarray, float, bool]]:
        """Receive the prefetched PULL's reply; same returns as
        :meth:`pull`.  A dead connection re-dials and re-sends the
        pending request under the retry policy."""
        pending = self._pending_pull
        if pending is None:
            raise RuntimeError("pull_finish without pull_start")
        hdr, tr, token = pending[0], pending[1], pending[2]

        def attempt() -> Tuple[dict, bytes]:
            try:
                if self._sock is None:
                    self._sock = self._dial()
                    if tr is not None:
                        _trace.set_current(tr.ctx)
                    try:
                        _send_msg(self._sock, hdr)
                        pending[3] = _frame.last_sent_bytes()
                    finally:
                        if tr is not None:
                            _trace.set_current(None)
                return _recv_msg(self._sock)
            except OSError:
                self._drop_sock()
                raise

        try:
            header, payload = self.retry.call(attempt,
                                              endpoint=self.endpoint)
        finally:
            self._pending_pull = None
        if tr is not None and token is not None:
            tr.rpc_end(token,
                       bytes=pending[3] + _frame.last_recv_bytes())
        got = self._process_pull_reply(
            wid, header, payload,
            lambda: {"op": "PULL", "wid": wid}, lambda _h: 0, tr,
        )
        if got is None:
            return None
        header, _payload, w = got
        if tr is not None:
            tr.set_model_version(int(header["ts"]))
        return (int(header["ts"]), w, float(header["avg_delay_ms"]),
                bool(header["calibrated"]))

    def pull(self, wid: int, tr=None
             ) -> Optional[Tuple[int, np.ndarray, float, bool]]:
        """Returns (ts, w, avg_delay_ms, calibrated); None when DONE or
        when this client's wid was RELEASED (check ``self.released``).
        ``tr`` (an UpdateTrace) records this pull's round trip as a
        pull.rtt span and propagates the trace context on the wire.

        In ``delta`` pull mode the request advertises the cached basis
        version (``have``) and the reply may be NOT_MODIFIED (zero model
        payload) or a byte-exact XOR delta; a decode mismatch or basis
        cache miss re-pulls FULL -- never a wrong model."""
        got = self._pull_model_rpc(
            wid, lambda: {"op": "PULL", "wid": wid}, lambda _h: 0, tr
        )
        if got is None:
            return None
        header, _payload, w = got
        if tr is not None:
            tr.set_model_version(int(header["ts"]))
        return (int(header["ts"]), w, float(header["avg_delay_ms"]),
                bool(header["calibrated"]))

    def subscribe(self, wid: int = 0, extra: Optional[dict] = None
                  ) -> Optional[Tuple[int, np.ndarray, int, int,
                                      float, bool]]:
        """Serving-tier snapshot subscription: one ``have=``-negotiated
        SUBSCRIBE round trip (NOT_MODIFIED / XDELTA / FULL, CRC-gated,
        full-pull fallback -- the same basis-cache machinery as delta
        PULLs, keyed by ``wid``; replicas pass their replica id).

        Returns ``(ts, w, clock, k, age_ms, done)``: the served version
        and model, the PS merge clock and accepted-update count at reply
        time, the served version's freshness age in ms (0 while it is
        still the current model), and whether training has finished.
        Unlike :meth:`pull` this never parks in the wave gate and keeps
        working after DONE.  ``extra`` merges additional header fields
        into every attempt (relaycast advertises its relay port as
        ``rport`` here, which registers it for the PS's offer path)."""
        def mk() -> dict:
            hdr = {"op": "SUBSCRIBE", "wid": wid}
            if extra:
                hdr.update(extra)
            return hdr

        got = self._pull_model_rpc(wid, mk, lambda _h: 0, None)
        if got is None:
            return None  # RELEASED/DONE headers never come from SUBSCRIBE
        header, _payload, w = got
        ts = int(header["ts"])
        return (ts, w, int(header.get("clock", ts)),
                int(header.get("k", 0)),
                float(header.get("age_ms", 0.0)),
                bool(header.get("done", False)))

    @staticmethod
    def _sparse_grad_enc(g: np.ndarray) -> Optional[Tuple[int, bytes]]:
        """(idx u32, val f32) pair encoding when it beats the dense d*4
        bytes (rcv1-class gradients touch only the sampled rows' columns);
        None when dense is smaller."""
        (nz,) = np.nonzero(g)
        if nz.size * 8 >= g.shape[0] * 4:
            return None
        return nz.size, (nz.astype(np.uint32).tobytes()
                         + g[nz].astype(np.float32).tobytes())

    def _encode_push(self, wid: int, ts: int, g: np.ndarray,
                     sparse: bool, diff: Optional[np.ndarray], tr
                     ) -> Tuple[dict, bytes, List[dict], dict, List[list]]:
        """Shared encode/stamp front half of :meth:`push` and
        :meth:`push_start`: returns ``(header, payload, spans, pl_delta,
        cv_wire)`` with the piggybacks already attached to the header."""
        t_enc0 = _trace.now_ms() if tr is not None else 0.0
        g = np.asarray(g, np.float32)
        # ASAGA pushes ride their own verb so fault schedules can tell the
        # two solvers' streams apart (the PS treats both identically)
        op = "PUSH_SAGA" if diff is not None else "PUSH"
        enc = self._sparse_grad_enc(g) if sparse else None
        if enc is not None:
            nnz, payload = enc
            hdr = {"op": op, "wid": wid, "ts": ts,
                   "enc": "sparse", "nnz": nnz}
        else:
            hdr, payload = {"op": op, "wid": wid, "ts": ts}, None
            if diff is None and self.push_codec != wirecodec.OFF:
                # quantize with error feedback (dense ASGD only: sparse
                # already beat dense above, and ASAGA's history scalars
                # must be exact).  encode_grad returns None for any
                # input it cannot encode safely (non-finite, fp16
                # overflow) -- that push ships raw and the residual
                # simply rides to the next quantized one.
                q = wirecodec.encode_grad(g, self.push_codec,
                                          self._ef.get(wid))
                if q is not None:
                    qhdr, payload, new_err = q
                    self._ef[wid] = new_err
                    hdr.update(qhdr)
            if payload is None:
                payload = g.tobytes()
        if diff is not None:
            payload += np.asarray(diff, np.float32).tobytes()
        self.bytes_pushed += len(payload)
        if tr is not None:
            tr.add(_trace.PUSH_WAIT, t_enc0, _trace.now_ms())
        spans: List[dict] = []
        if self.recorder is not None:
            # the PUSH piggyback: completed spans (a previous traced
            # update's push.rtt, this one's pull.rtt/compute/push.wait)
            # ship in the header -- one drain per logical push; retries
            # re-send the same header, and the PS dedup window keeps a
            # re-applied push from double-folding them
            spans = self.recorder.drain_wire()
            if spans:
                hdr["spans"] = spans
        pl_delta: dict = {}
        if self.pl_stats is not None:
            # pipeline-counter piggyback, same discipline as spans: ship
            # the unshipped delta; the PS folds it once (dedup'd retries
            # never reach the handler)
            pl_delta = self.pl_stats.take_wire()
            if pl_delta:
                hdr["pl"] = pl_delta
        cv_wire: List[list] = []
        if self.cv_buf is not None:
            # convergence-sample piggyback: drain the unshipped tail (a
            # bounded slice; the rest rides later pushes)
            cv_wire = self.cv_buf.take_wire()
            if cv_wire:
                hdr["cv"] = cv_wire
        return hdr, payload, spans, pl_delta, cv_wire

    def _requeue_piggybacks(self, spans: List[dict], pl_delta: dict,
                            cv_wire: Optional[List[list]] = None) -> None:
        """A push whose whole retry budget was spent must not silently eat
        its piggybacked telemetry: spans, counter deltas, and convergence
        samples go back to ride the next push/BYE."""
        if spans and self.recorder is not None:
            self.recorder.requeue(spans)
        if pl_delta and self.pl_stats is not None:
            self.pl_stats.merge_back(pl_delta)
        if cv_wire and self.cv_buf is not None:
            self.cv_buf.merge_back(cv_wire)

    def push(self, wid: int, ts: int, g: np.ndarray,
             sparse: bool = False, diff: Optional[np.ndarray] = None,
             tr=None) -> Tuple[bool, bool]:
        """Returns (accepted, run_done).  ``diff`` (ASAGA candidate history
        scalars) rides after the gradient when given.  ``tr`` records this
        push's encode time (push.wait) and round trip (push.rtt); any
        completed spans in the client's recorder piggyback on the header
        either way."""
        hdr, payload, spans, pl_delta, cv_wire = self._encode_push(
            wid, ts, g, sparse, diff, tr
        )
        # stamp ONCE: retries re-send the same (sid, seq), so a push whose
        # ACK was lost is answered from the PS dedup window, not re-applied
        try:
            header, _ = self._traced_call(
                tr, _trace.PUSH_RTT,
                self.session.stamp(self._proc_hdr(hdr)), payload,
            )
        except BaseException:
            self._requeue_piggybacks(spans, pl_delta, cv_wire)
            raise
        if header.get("op") == "REJECT_FENCED":
            # this gradient was computed under a deposed epoch: it is
            # DROPPED (the same loss as a taw rejection), and with the
            # adopted epoch the next round is admitted
            if self._note_fenced(header):
                return False, False
            raise FencedError(
                f"push fenced by zombie {self.endpoint} (epoch "
                f"{int(header.get('epoch', 0))} <= ours {self.epoch})"
            )
        if header.get("op") == "ERR":
            # a refusing endpoint (standby / malformed push): dead-
            # endpoint semantics, same as the pull path
            raise ConnectionError(
                f"push refused by {self.endpoint}: "
                f"{header.get('msg')!r}")
        if header.get("released"):
            self.released = True
        return bool(header.get("accepted")), bool(header.get("done"))

    # ------------------------------------------------- windowed push pipe
    # The pipelined sender's wire window: push k+1 goes OUT before push
    # k's ACK returns, so per-update push cost drops from a full RTT to
    # the send itself.  The server already supports this shape -- its
    # per-connection loop handles frames in order and replies in order --
    # so ACKs pair with pushes FIFO.  Exactly-once survives every fault:
    # each entry is stamped once, and on any connection error the whole
    # unacked window is REPLAYED on the fresh socket (the PS dedup window
    # re-ACKs already-applied entries instead of re-merging them).  These
    # concurrency contract: any number of calls from ONE sending thread
    # (push_start) plus ONE reaping thread (push_finish/push_abandon);
    # the window lock serializes sends and reconnect/replay, receives
    # run outside it (TCP full duplex).

    def push_start(self, wid: int, ts: int, g: np.ndarray,
                   sparse: bool = False,
                   diff: Optional[np.ndarray] = None, tr=None) -> None:
        """Encode, stamp, window, and SEND one push without waiting for
        its ACK.  A send error (or an already-dead socket) is deferred:
        the entry stays in the window and :meth:`push_finish`'s
        reconnect replays it."""
        hdr, payload, spans, pl_delta, cv_wire = self._encode_push(
            wid, ts, g, sparse, diff, tr
        )
        token = tr.rpc_begin(_trace.PUSH_RTT) if tr is not None else None
        if tr is not None:
            _trace.set_current(None)  # _send_entry scopes the context
        # trailing slot: this entry's sent frame bytes (captured at send,
        # so the rtt span's `bytes` pairs OUR send with OUR reply even
        # though the single-threaded loop interleaves other frames)
        entry = [self.session.stamp(self._proc_hdr(hdr)), payload, tr,
                 token, spans, pl_delta, cv_wire, 0]
        with self._win_lock:
            self._push_window.append(entry)
            if self._sock is not None:
                try:
                    self._send_entry(entry)
                except OSError:
                    self._drop_sock()  # reaper reconnects and replays

    def _send_entry(self, entry) -> None:
        hdr, payload, tr = entry[0], entry[1], entry[2]
        if tr is not None:
            _trace.set_current(tr.ctx)  # the tc header for THIS push
        try:
            _send_msg(self._sock, hdr, payload)
            entry[7] = _frame.last_sent_bytes()
        finally:
            if tr is not None:
                _trace.set_current(None)

    def _replay_window(self) -> None:
        """Re-send every unacked push on the (fresh) socket, oldest
        first, same stamps: applied-but-unACKed entries are answered from
        the PS dedup window, lost ones are applied now -- FIFO ACK
        pairing is preserved either way."""
        for entry in self._push_window:
            self._send_entry(entry)

    def inflight_pushes(self) -> int:
        return len(self._push_window)

    def push_finish(self) -> Tuple[bool, bool]:
        """Receive the OLDEST in-flight push's ACK (FIFO), under the
        retry policy: a dead connection is re-dialed and the unacked
        window replayed before the next receive attempt.  Returns
        (accepted, run_done)."""

        def attempt() -> Tuple[dict, bytes]:
            try:
                with self._win_lock:
                    sock = self._sock
                    if sock is None:
                        sock = self._sock = self._dial()
                        self._replay_window()
                # recv OUTSIDE the window lock: the sender keeps sending
                # while this blocks (full duplex)
                return _recv_msg(sock)
            except OSError:
                self._drop_sock()
                raise

        header, _ = self.retry.call(attempt, endpoint=self.endpoint)
        entry = self._push_window.popleft()
        _hdr, _payload, tr, token, _spans, _pl, _cv, sent_bytes = entry
        if tr is not None and token is not None:
            tr.rpc_end(token,
                       bytes=sent_bytes + _frame.last_recv_bytes())
        if header.get("op") == "REJECT_FENCED":
            # a windowed entry stamped under a deposed epoch (typically a
            # replay onto a fenced range's replacement): dropped, epoch
            # adopted -- later push_start calls stamp the current epoch.
            # Judge against THIS ENTRY'S stamp, not self.epoch: with >= 2
            # stale entries in flight, the first fence already advanced
            # self.epoch, and comparing the second reply against the
            # advanced value would misread the healthy replacement as a
            # zombie (each stale entry is rejected exactly once, that is
            # the design -- only a server whose epoch does not exceed
            # what WE stamped on the op is actually stale itself).
            self.fenced_replies += 1
            srv_ep = int(header.get("epoch", 0))
            if srv_ep > self.epoch:
                self.epoch = srv_ep
            if srv_ep > int(entry[0].get("ep", 0) or 0):
                return False, False
            raise FencedError(
                f"push fenced by zombie {self.endpoint} (epoch "
                f"{srv_ep} <= op stamp {entry[0].get('ep')})"
            )
        if header.get("op") == "ERR":
            raise ConnectionError(
                f"windowed push refused by {self.endpoint}: "
                f"{header.get('msg')!r}")
        if header.get("released"):
            self.released = True
        return bool(header.get("accepted")), bool(header.get("done"))

    def push_abandon(self) -> int:
        """Drop every in-flight push (the window's whole retry budget is
        spent -- the serial loop's error path loses its round the same
        way), requeueing piggybacked telemetry.  Returns the number of
        pushes abandoned."""
        with self._win_lock:
            n = len(self._push_window)
            while self._push_window:
                entry = self._push_window.popleft()
                self._requeue_piggybacks(entry[4], entry[5], entry[6])
            self._drop_sock()
        return n

    def pull_saga(self, wid: int, n_p: int, tr=None) -> Optional[
        Tuple[int, np.ndarray, np.ndarray, np.ndarray, int, float, bool]
    ]:
        """ASAGA pull: the PS samples this worker's rows and ships their
        current history scalars with the model (the reference's sampledMap).
        Returns (ts, w, idx, alpha_sel, n_valid, avg_delay_ms, calibrated)
        or None when DONE."""
        got = self._pull_model_rpc(
            wid, lambda: {"op": "PULL_SAGA", "wid": wid, "n_p": n_p},
            lambda h: 8 * int(h["cap"]), tr,
        )
        if got is None:
            return None
        header, payload, w = got
        if tr is not None:
            tr.set_model_version(int(header["ts"]))
        # the ASAGA extra block (idx, alpha) always rides AFTER the model
        # part, whatever its encoding; its offset is the payload tail
        cap = int(header["cap"])
        tail = len(payload) - 8 * cap
        idx = np.frombuffer(payload[tail: tail + 4 * cap], np.uint32)
        alpha_sel = np.frombuffer(payload[tail + 4 * cap:], np.float32)
        return (int(header["ts"]), w, idx, alpha_sel, int(header["n_valid"]),
                float(header["avg_delay_ms"]), bool(header["calibrated"]))

    def push_saga(self, wid: int, ts: int, g: np.ndarray, diff: np.ndarray,
                  sparse: bool = False, tr=None) -> Tuple[bool, bool]:
        """ASAGA push: gradient + candidate history scalars for the sampled
        rows (committed by the PS only on accept).  Returns (accepted, done).
        """
        return self.push(wid, ts, g, sparse=sparse, diff=diff, tr=tr)

    def snapshots(self) -> Tuple[List[float], np.ndarray]:
        header, payload = self._call_raw({"op": "SNAPSHOTS"})
        W = np.frombuffer(payload, np.float32).reshape(header["shape"])
        return list(header["times"]), W

    def send_eval(self, wid: int, losses: np.ndarray) -> None:
        self._call_raw(self.session.stamp({"op": "EVAL_RESULT", "wid": wid}),
                       np.asarray(losses, np.float64).tobytes())

    def bye(self) -> None:
        try:
            if self._pending_pull is not None:
                # a prefetched PULL is still parked in the PS wave gate:
                # its MODEL reply would arrive (possibly after a ~1 s
                # starvation-fallback wait) ahead of any BYE ACK.  Just
                # drop the connection -- the PS treats EOF as goodbye,
                # and this client's telemetry rides its sibling push
                # connection's BYE.
                self._drop_sock()
                return
            if self._sock is not None:
                hdr: dict = {"op": "BYE"}
                if self.recorder is not None:
                    # last drain: the final traced update's push.rtt has no
                    # later PUSH to ride, so it leaves with the goodbye
                    spans = self.recorder.drain_wire()
                    if spans:
                        hdr["spans"] = spans
                if self.pl_stats is not None:
                    pl_delta = self.pl_stats.take_wire()
                    if pl_delta:
                        hdr["pl"] = pl_delta
                if self.cv_buf is not None:
                    # the final unshipped convergence samples leave with
                    # the goodbye, like the last traced update's spans
                    cv_wire = self.cv_buf.take_wire()
                    if cv_wire:
                        hdr["cv"] = cv_wire
                _send_msg(self._sock, hdr)
                _recv_msg(self._sock)
        except (ConnectionError, OSError):
            pass
        self._drop_sock()


def run_worker_process(
    host: str,
    port: int,
    wids: List[int],
    shards: Dict[int, object],
    cfg,
    d: int,
    n: int,
    eval_wid: Optional[int] = None,
    deadline_s: float = 600.0,
    algo: str = "asgd",
    shard_factory=None,
    proc_token: Optional[str] = None,
) -> Dict[int, int]:
    """Worker-process main loop: one thread per owned logical worker, each
    pulling models and pushing gradients until the PS says DONE.

    ``shards``: wid -> Shard (device-resident, this process's chips).
    Returns per-wid gradient counts.  When ``eval_wid`` is set, after DONE
    this process scores the PS's snapshot stack over ALL its shards and
    pushes one EVAL_RESULT (the distributed optVars evaluation).

    ``algo="asaga"``: the PS samples and ships (idx, alpha) with each model
    (it owns the history table); the worker runs the history-corrected
    gradient step and pushes candidate scalars back with the gradient.

    Elastic plane (``parallel/supervisor.py``): this process HELLOs the PS
    with ``proc_token`` + its wids + pid, and every PULL/PUSH carries the
    token.  When the PS's supervisor re-homes a dead peer's shard here, the
    adoption order arrives on a PULL reply; ``shard_factory(wid)`` builds
    the orphan shard locally (datasets are seed-deterministic or disk-
    loadable, the DCN analog of lineage recomputation) and a fresh loop
    thread starts serving it.  A thread whose wid is reclaimed by a
    rejoining process is told RELEASED and stands down.  With
    ``shard_factory=None`` adoption orders are ignored (classic behavior).

    Pipelining (``async.pipeline.depth`` / ``SolverConfig.pipeline_depth``):
    depth 0 runs the classic serial loop below, byte- and step-identical;
    depth >= 1 runs :func:`pipelined_worker_loop` -- prefetched pulls on a
    second connection, a bounded in-flight push sender, and the
    host<->device transfers staged off the compute thread.  ASAGA always
    runs serial (PS-side sampling requires pull->push alternation).
    """
    import jax

    from asyncframework_tpu.engine.straggler import DelayModel
    from asyncframework_tpu.ops import steps

    proc_token = proc_token or f"{socket.gethostname()}-{os.getpid()}"
    # distributed tracing (metrics/trace.py): one sampling recorder + span
    # ring per worker process, shared by its loop threads.  With
    # async.trace.sample = 0 the recorder is None and the hot path does no
    # tracing work at all (and frames stay byte-identical).
    _rec = _trace.TraceRecorder()
    recorder = _rec if _rec.enabled else None
    sparse = any(hasattr(s, "cols") for s in shards.values())
    if algo == "asaga":
        step = (steps.make_saga_dcn_sparse_worker_step(d) if sparse
                else steps.make_saga_dcn_worker_step())
    else:
        step = (steps.make_sparse_asgd_worker_step(cfg.batch_rate, d)
                if sparse
                else steps.make_asgd_worker_step(cfg.batch_rate, cfg.loss))
    delay_model = DelayModel(cfg.coeff, cfg.num_workers, cfg.seed)
    counts = {wid: 0 for wid in wids}
    stop = threading.Event()
    calibrated_once = threading.Event()
    # pipelined update loop (async.pipeline.depth): 0 = the classic
    # serial pull -> compute -> push loop below, untouched (byte- and
    # step-identical); >= 1 = prefetched pulls on a second connection +
    # a bounded in-flight push sender (at most `depth` unacked pushes).
    pipe_depth = getattr(cfg, "pipeline_depth", None)
    if pipe_depth is None:
        from asyncframework_tpu.conf import PIPELINE_DEPTH, global_conf

        pipe_depth = global_conf().get(PIPELINE_DEPTH)
    pipe_depth = max(0, int(pipe_depth))
    if algo == "asaga":
        # the PS samples per pull and holds ONE pending (idx, alpha) slot
        # per wid: a prefetched pull would clobber the slot the in-flight
        # push must commit against.  ASAGA keeps the strict pull->push
        # alternation; pipelining is an ASGD-path capability.
        pipe_depth = 0
    pl_stats = _PipelineStats() if pipe_depth > 0 else None
    # mesh compute plane (async.mesh.devices / SolverConfig.mesh_devices):
    # 0 = the classic single-device gradient step below, byte- and step-
    # identical; >= 2 = each logical worker computes its mini-batch
    # gradient batch-parallel over a LOCAL dp mesh -- shard rows are
    # padded+sharded into HBM once per run (pad_and_shard), per-device
    # partial gradients psum-reduce on the mesh, and the loop still
    # pushes ONE fused gradient per step (the wire cannot tell).  A conf
    # asking for more chips than the rig has clamps (make_mesh clamp=
    # True, logged); fewer than 2 effective devices, or sparse
    # (padded-ELL) shards, degrade to the serial path -- an operator
    # overshooting a knob must cost a warning, never the worker daemon.
    mesh_devices = getattr(cfg, "mesh_devices", None)
    if mesh_devices is None:
        from asyncframework_tpu.conf import MESH_DEVICES, global_conf

        mesh_devices = global_conf().get(MESH_DEVICES)
    mesh_devices = max(0, int(mesh_devices))
    worker_mesh = None
    mesh_step = None
    mesh_replicated = None
    if mesh_devices:
        import logging as _logging

        _mlog = _logging.getLogger(__name__)
        from asyncframework_tpu.parallel.mesh import (
            make_mesh,
            replicated_sharding,
        )

        if sparse:
            _mlog.warning(
                "async.mesh.devices=%d ignored: sparse (padded-ELL) "
                "shards run the single-device step", mesh_devices,
            )
        else:
            # make_mesh owns the clamp: an over-ask logs the documented
            # "requested N but only M available; clamping" warning there
            mesh = make_mesh(mesh_devices, clamp=True)
            if mesh.devices.size < 2:
                _mlog.warning(
                    "async.mesh.devices=%d yields a %d-device mesh; "
                    "running the single-device step", mesh_devices,
                    mesh.devices.size,
                )
            else:
                worker_mesh = mesh
                mesh_replicated = replicated_sharding(worker_mesh)
                if algo == "asaga":
                    mesh_step = steps.make_mesh_saga_dcn_worker_step(
                        worker_mesh
                    )
                else:
                    mesh_step = steps.make_mesh_asgd_worker_step(
                        cfg.batch_rate, worker_mesh, cfg.loss
                    )
    # one-time per-wid mesh placement (HBM-resident across the run);
    # built lazily under its own lock so adopted shards place too
    mesh_lock = threading.Lock()
    mesh_placed: Dict[int, tuple] = {}

    def mesh_place(wid: int, shard):
        """Row-shard this wid's batch over the worker mesh ONCE."""
        if worker_mesh is None:
            return None
        with mesh_lock:
            got = mesh_placed.get(wid)
        if got is not None:
            return got
        from asyncframework_tpu.parallel.mesh import pad_and_shard

        Xs, ys, vs, _n = pad_and_shard(
            worker_mesh, np.asarray(shard.X), np.asarray(shard.y)
        )
        with mesh_lock:
            return mesh_placed.setdefault(wid, (Xs, ys, vs))
    # convergence telemetry (async.convergence.sample /
    # SolverConfig.conv_sample): every Nth update per logical worker
    # evaluates the shard's mean loss (one extra jitted eval against the
    # model the gradient was computed on) plus the gradient norm, and
    # buffers the (version, loss, grad_norm) sample for the next PUSH
    # header's ``cv`` entry -- the PS folds them into the process-global
    # loss-vs-wallclock / loss-vs-version curves (metrics/timeseries.py).
    # 0 = off: no eval, no header field, byte-identical wire.
    conv_every = getattr(cfg, "conv_sample", None)
    if conv_every is None:
        from asyncframework_tpu.conf import CONV_SAMPLE, global_conf

        conv_every = global_conf().get(CONV_SAMPLE)
    conv_every = max(0, int(conv_every))
    cv_buf = None
    conv_eval = None
    if conv_every > 0:
        from asyncframework_tpu.metrics.timeseries import ConvergenceBuffer

        cv_buf = ConvergenceBuffer()
        conv_eval = (steps.make_sparse_trajectory_loss_eval() if sparse
                     else steps.make_trajectory_loss_eval(
                         getattr(cfg, "loss", "least_squares")))

    def conv_sample(shard, w_dev, ts, g_host: np.ndarray) -> None:
        """One convergence sample: shard mean loss at the pulled model +
        gradient norm, buffered for the PUSH piggyback.  Telemetry must
        never break the update loop.  Against a sharded PS group ``ts``
        is the version VECTOR -- the sample is stamped with the primary's
        component (its clock drives the convergence curves)."""
        try:
            if sparse:
                sums = conv_eval(shard.cols, shard.vals, shard.y,
                                 w_dev[None, :])
            else:
                sums = conv_eval(shard.X, shard.y, w_dev[None, :])
            loss = (float(np.asarray(sums)[0])
                    / max(1, int(shard.y.shape[0])))
            ver = int(ts[0]) if isinstance(ts, (tuple, list)) else int(ts)
            cv_buf.add(ver, loss, float(np.linalg.norm(g_host)))
        except Exception:  # noqa: BLE001
            pass

    # sharded PS group (parallel/shardgroup.py): resolved from the HELLO
    # WELCOME below.  None = the classic single PS -- every client below
    # is a stock PSClient and the wire is byte-identical.  The WELCOME
    # also seeds the fencing epochs (async.fence.enabled on the servers;
    # absent = 0 = legacy, clients stamp nothing).
    smap = None
    smap_epochs: Optional[List[int]] = None
    ps_epoch = 0
    # adaptive control plane: built from the WELCOME's CTRL payload when
    # the PS runs a controller (async.control.enabled); every client of
    # this process shares it, and the pipelined loops read the live
    # depth target off it each iteration.  None = control off -- no
    # ``cs`` stamps, byte-identical wire.
    ctrl_sink = None

    def make_client(recorder=None, pl_stats=None, cv_buf=None):
        """One PS-facing client: a ShardedPSClient fan-out facade when
        the HELLO resolved a shard map, the classic PSClient otherwise.
        Same surface either way -- the loops below cannot tell."""
        if smap is not None:
            from asyncframework_tpu.parallel.shardgroup import (
                ShardedPSClient,
            )

            return ShardedPSClient(
                smap, proc=proc_token, recorder=recorder,
                pull_mode=getattr(cfg, "pull_mode", None),
                pl_stats=pl_stats, cv_buf=cv_buf, epochs=smap_epochs,
                ctrl_sink=ctrl_sink,
            )
        return PSClient(host, port, proc=proc_token, recorder=recorder,
                        pull_mode=getattr(cfg, "pull_mode", None),
                        pl_stats=pl_stats, cv_buf=cv_buf, epoch=ps_epoch,
                        push_codec=getattr(cfg, "push_codec", None),
                        ctrl_sink=ctrl_sink)

    # elastic adoption bookkeeping: which wids this process serves (own +
    # adopted), and every loop thread ever started (joined at the end)
    group_lock = threading.Lock()
    active_wids = set(wids)
    threads: List[threading.Thread] = []

    def shard_dev(shard):
        return (shard.cols if sparse else shard.X).device

    def run_step(shard, w_dev, key, placed=None):
        """Dense/sparse/mesh ASGD: (g, new_key)."""
        if placed is not None:
            Xs, ys, vs = placed
            return mesh_step(Xs, ys, vs, w_dev, key)
        if sparse:
            return step(shard.cols, shard.vals, shard.y, w_dev, key)
        return step(shard.X, shard.y, w_dev, key)

    def run_saga_step(shard, w_dev, idx_dev, alpha_dev, n_valid,
                      placed=None):
        """Dense/sparse/mesh DCN-ASAGA: (g, diff_sel)."""
        if placed is not None:
            Xs, ys, _vs = placed
            return mesh_step(Xs, ys, w_dev, idx_dev, alpha_dev, n_valid)
        if sparse:
            return step(shard.cols, shard.vals, shard.y, w_dev, idx_dev,
                        alpha_dev, n_valid)
        return step(shard.X, shard.y, w_dev, idx_dev, alpha_dev, n_valid)

    def put_model(w_host, dev, placed):
        """Host model -> device(s): replicated over the mesh when this
        wid computes mesh-parallel, the classic single-device put
        otherwise."""
        if placed is not None:
            return jax.device_put(w_host, mesh_replicated)
        return jax.device_put(w_host, dev)

    # warm every owned shard's executable BEFORE the first pull
    # (first-iteration-blocking parity): without this, compile skew across
    # worker threads lets fast workers drive the run to done while slow ones
    # are still in XLA -- their first push then lands post-done and drops
    import jax.numpy as jnp

    warmed = set()
    for wid in wids:
        shard = shards[wid]
        dev = shard_dev(shard)
        n_p = int(shard.y.shape[0])
        shape = (shard.cols if sparse else shard.X).shape
        placed = mesh_place(wid, shard)  # one-time HBM placement per wid
        wkey = (shape, "mesh" if placed is not None else dev)
        if wkey in warmed:
            continue
        warmed.add(wkey)
        w0 = put_model(np.zeros(d, np.float32), dev, placed)
        if algo == "asaga":
            cap = steps.sparse_step_capacity(cfg.batch_rate, n_p)
            g0, _ = run_saga_step(
                shard, w0,
                np.zeros(cap, np.int32) if placed is not None
                else jax.device_put(jnp.zeros(cap, jnp.int32), dev),
                np.zeros(cap, np.float32) if placed is not None
                else jax.device_put(jnp.zeros(cap, jnp.float32), dev),
                np.int32(0), placed=placed,
            )
        else:
            key0 = (jax.random.PRNGKey(0) if placed is not None
                    else jax.device_put(jax.random.PRNGKey(0), dev))
            g0, _ = run_step(shard, w0, key0, placed=placed)
        g0.block_until_ready()

    def adopt(orphan: int) -> None:
        """Adoption order from the PS: materialize the dead peer's shard
        locally and start serving it (idempotent -- orders are re-delivered
        until the first pull for the orphan lands)."""
        with group_lock:
            if orphan in active_wids:
                return
            active_wids.add(orphan)
        try:
            built = shard_factory(orphan)  # device placement: off the lock
        except Exception:
            with group_lock:
                active_wids.discard(orphan)
            return
        with group_lock:
            # shared-dict writes under the lock: the end-of-run eval reads
            # `shards` under it too, and a late adoption racing that read
            # must not blow up the iteration
            shards[orphan] = built
            counts.setdefault(orphan, 0)
        spawn(orphan)

    def worker_loop(wid: int) -> None:
        shard = shards[wid]
        dev = shard_dev(shard)
        placed = mesh_place(wid, shard)  # None = single-device step
        key = None
        if algo != "asaga":  # ASAGA samples PS-side; workers need no chain
            key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), wid)
            key = (jax.device_put(key, mesh_replicated)
                   if placed is not None else jax.device_put(key, dev))
        deadline = time.monotonic() + deadline_s
        cl: Optional[PSClient] = None
        try:
            while not stop.is_set() and time.monotonic() < deadline:
                try:
                    if cl is None:
                        cl = make_client(recorder=recorder, cv_buf=cv_buf)
                    # per-update sampling decision: a traced update's RPCs
                    # carry the trace context on the wire and its lifecycle
                    # spans (pull.rtt/compute/push.wait/push.rtt) land in
                    # the recorder ring for the PUSH piggyback
                    tr = (recorder.start_update(wid)
                          if recorder is not None else None)
                    # per-RPC transport faults (reconnect, backoff, jitter,
                    # breaker) are the client's RetryPolicy's problem now;
                    # PUSH retries are exactly-once-applied via the PS
                    # dedup window, so nothing here needs to reason about
                    # "did my gradient land"
                    if algo == "asaga":
                        got = cl.pull_saga(wid, int(shard.y.shape[0]),
                                           tr=tr)
                    else:
                        got = cl.pull(wid, tr=tr)
                    if got is None:
                        break  # DONE, or this wid was RELEASED to a rejoiner
                    if shard_factory is not None:
                        for orphan in cl.take_orders():
                            adopt(orphan)
                    if algo == "asaga":
                        (ts, w_host, idx, alpha_sel, n_valid, avg_ms,
                         calibrated) = got
                    else:
                        ts, w_host, avg_ms, calibrated = got
                    if calibrated and not calibrated_once.is_set():
                        delay_model.calibrate(avg_ms)
                        calibrated_once.set()
                    # compute span: straggler delay + host->device put +
                    # gradient step + device->host readback -- everything
                    # between the pull reply and the push encode
                    t_c0 = _trace.now_ms() if tr is not None else 0.0
                    dly = delay_model.delay_ms(wid) if calibrated else 0.0
                    if dly > 0:
                        time.sleep(dly / 1e3)
                    w_dev = put_model(w_host, dev, placed)
                    counts[wid] += 1
                    if algo == "asaga":
                        idx32 = idx.astype(np.int32)
                        g, diff = run_saga_step(
                            shard, w_dev,
                            idx32 if placed is not None
                            else jax.device_put(idx32, dev),
                            alpha_sel if placed is not None
                            else jax.device_put(alpha_sel, dev),
                            np.int32(n_valid), placed=placed,
                        )
                        g_host = np.asarray(g)
                        diff_host = np.asarray(diff)
                        if tr is not None:
                            tr.add(_trace.COMPUTE, t_c0, _trace.now_ms())
                        if cv_buf is not None and \
                                counts[wid] % conv_every == 0:
                            # mesh path: the shard-loss eval runs on the
                            # shard's own device -- hand it the HOST
                            # model, not the mesh-replicated handle
                            # (committed-device mismatch would raise)
                            conv_sample(shard,
                                        w_host if placed is not None
                                        else w_dev, ts, g_host)
                        _accepted, done = cl.push_saga(
                            wid, ts, g_host, diff_host, sparse=sparse,
                            tr=tr,
                        )
                    else:
                        g, new_key = run_step(shard, w_dev, key,
                                              placed=placed)
                        key = new_key
                        g_host = np.asarray(g)  # the push IS the readback
                        if tr is not None:
                            tr.add(_trace.COMPUTE, t_c0, _trace.now_ms())
                        if cv_buf is not None and \
                                counts[wid] % conv_every == 0:
                            conv_sample(shard,
                                        w_host if placed is not None
                                        else w_dev, ts, g_host)
                        _accepted, done = cl.push(wid, ts, g_host,
                                                  sparse=sparse, tr=tr)
                    # flight-recorder breadcrumb: the last acked push
                    # rides the dump, so a SIGKILLed worker's post-mortem
                    # ends at (wid, basis version, cumulative count) the
                    # PS-side ledgers can be checked against.  ``ts`` is
                    # an int against a single PS and a per-shard vector
                    # against a sharded group -- pass it through as-is
                    # (the dump serializer stringifies anything exotic)
                    _flight.note("push", wid=wid, ts=ts,
                                 acc=bool(_accepted), n=counts[wid])
                    if done:
                        break
                except (ConnectionError, OSError):
                    # the RPC's whole retry budget is spent (RetryError) or
                    # the endpoint's breaker is open (CircuitOpenError): the
                    # PS is restarting from checkpoint or the DCN is down
                    # for longer than one policy window.  Pace and re-enter
                    # -- the client reconnects lazily, and a restarted PS
                    # has no pending state for the lost round anyway.
                    time.sleep(0.2)
        finally:
            if cl is not None:
                if cl.released:
                    # the wid was reclaimed by a rejoiner: forget it so a
                    # LATER re-adoption (rejoiner dies again) can restart
                    # a loop here instead of finding the wid "active"
                    with group_lock:
                        active_wids.discard(wid)
                cl.bye()

    def pipelined_worker_loop(wid: int) -> None:
        """Pipelined update loop (``async.pipeline.depth`` >= 1): the
        serial loop's per-update stall structure is pull(RTT + wave wait)
        -> compute -> push(RTT + merge wait), strictly serialized -- the
        device idles during every RTT and the socket idles during every
        compute.  Here the three overlap, on ONE thread per worker (the
        overlap lives in the kernel socket buffers, not in extra threads
        whose GIL handoffs would eat the win):

        - **prefetched pulls** on a second PSClient connection:
          ``pull_start`` SENDS the pull for model v(k+1) before step k
          computes; the request parks in the PS wave gate and the reply
          lands in this socket's kernel buffer while the step runs
          (delta-mode ``have=`` pulls make an unchanged version a
          header-only NOT_MODIFIED); ``pull_finish`` then decodes it --
          usually without blocking at all (``prefetch_hits``);
        - **decoupled pushes** on a bounded wire window:
          ``push_start`` sends step k's gradient and the loop moves
          straight on -- push k+1 goes out before ACK k returns (the
          server replies in order, so ACKs pair FIFO); ACKs are reaped
          lazily, and only when ``depth`` pushes are unacknowledged
          does the loop block on one (``push_finish``);
        - staleness stays bounded: the PS's taw admission prices the
          in-flight window, and a taw REJECTION makes this loop discard
          its prefetched model and pull fresh (``stale_discards``).

        Exactly-once pushes ride the session/dedup machinery: window
        entries are stamped once and REPLAYED on reconnect, so a
        delivered-but-unACKed push is re-answered from the PS dedup
        window, never re-applied.  Adoption orders (they ride PULL
        replies, so they arrive on the prefetch connection),
        RELEASED/DONE, and trace spans all keep working; the residual
        stall (blocking in pull_finish or on the window cap) is
        recorded as the ``pipeline`` trace stage.

        Mesh interaction (``async.mesh.devices``): with a worker mesh
        the staged host->device put replicates the pulled model over
        every mesh device (make_pipelined_transfer handed the mesh's
        replicated sharding) -- the P transfer-engine
        copies overlap step k's compute exactly like the single-device
        double buffer, and the psum at the end of the mesh step overlaps
        the next prefetch's RTT the same way single-device compute did.
        Everything else (two connections, bounded window, exactly-once
        replay) is mesh-oblivious: the loop pushes the one fused
        gradient the mesh step returns."""
        shard = shards[wid]
        dev = shard_dev(shard)
        placed = mesh_place(wid, shard)  # None = single-device step
        stage, readback = steps.make_pipelined_transfer(
            mesh_replicated if placed is not None else dev
        )
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), wid)
        key = (jax.device_put(key, mesh_replicated)
               if placed is not None else jax.device_put(key, dev))
        deadline = time.monotonic() + deadline_s
        pull_cl: Optional[PSClient] = None
        push_cl: Optional[PSClient] = None
        done = False
        stale_feedback = False

        def reap_one() -> None:
            """Collect the oldest in-flight push's ACK (FIFO)."""
            nonlocal done, stale_feedback
            try:
                accepted, acked_done = push_cl.push_finish()
                pl_stats.bump("pushes_async")
                _flight.note("push", wid=wid, acc=bool(accepted),
                             n=counts[wid])
                if acked_done:
                    done = True
                elif not accepted:
                    # taw rejection: the in-flight window ran too stale
                    # -- discard the prefetched model and pull fresh
                    stale_feedback = True
            except (ConnectionError, OSError):
                # whole retry budget spent: the unacked window is lost,
                # exactly as the serial loop's error path loses its
                # round; pace and keep going
                lost = push_cl.push_abandon()
                pl_stats.bump("push_errors", max(lost, 1))
                time.sleep(0.2)

        try:
            while not stop.is_set() and time.monotonic() < deadline:
                try:
                    pull_cl = make_client(recorder=recorder)
                    push_cl = make_client(recorder=recorder,
                                          pl_stats=pl_stats,
                                          cv_buf=cv_buf)
                    break
                except (ConnectionError, OSError):
                    time.sleep(0.2)  # PS mid-restart: pace and re-dial
            if push_cl is None:
                return
            tr = recorder.start_update(wid) if recorder is not None else None
            pull_cl.pull_start(wid, tr=tr)
            while (not stop.is_set() and not done
                   and time.monotonic() < deadline):
                was_ready = pull_cl.pull_ready()
                t_w0 = _trace.now_ms()
                try:
                    got = pull_cl.pull_finish(wid)
                except (ConnectionError, OSError):
                    time.sleep(0.2)
                    tr = (recorder.start_update(wid)
                          if recorder is not None else None)
                    pull_cl.pull_start(wid, tr=tr)
                    continue
                if got is None:
                    break  # DONE, or this wid was RELEASED to a rejoiner
                if was_ready:
                    pl_stats.bump("prefetch_hits")   # reply was buffered
                else:
                    pl_stats.bump("prefetch_waits")  # loop blocked on it
                if tr is not None:
                    # the pipeline's residual stall: whatever pull wait
                    # the prefetch could not hide
                    tr.add(_trace.PIPELINE, t_w0, _trace.now_ms())
                # adoption orders ride PULL replies, i.e. arrive on the
                # prefetch connection
                if shard_factory is not None:
                    for orphan in pull_cl.take_orders():
                        adopt(orphan)
                if stale_feedback:
                    # stale-prefetch discard: pull fresh instead of
                    # computing on a basis the taw filter just priced out
                    # (delta mode makes the re-pull nearly free)
                    stale_feedback = False
                    pl_stats.bump("stale_discards")
                    tr = (recorder.start_update(wid)
                          if recorder is not None else None)
                    pull_cl.pull_start(wid, tr=tr)
                    continue
                ts, w_host, avg_ms, calibrated = got
                cur_tr = tr
                # prefetch the NEXT model before computing: its wave-gate
                # wait and RTT ride this step's compute
                tr = (recorder.start_update(wid)
                      if recorder is not None else None)
                pull_cl.pull_start(wid, tr=tr)
                if calibrated and not calibrated_once.is_set():
                    delay_model.calibrate(avg_ms)
                    calibrated_once.set()
                t_c0 = _trace.now_ms() if cur_tr is not None else 0.0
                dly = delay_model.delay_ms(wid) if calibrated else 0.0
                if dly > 0:
                    time.sleep(dly / 1e3)
                w_dev = stage(w_host)
                counts[wid] += 1
                g, key = run_step(shard, w_dev, key, placed=placed)
                g_host = readback(g)
                if cur_tr is not None:
                    cur_tr.add(_trace.COMPUTE, t_c0, _trace.now_ms())
                if cv_buf is not None and counts[wid] % conv_every == 0:
                    conv_sample(shard,
                                w_host if placed is not None else w_dev,
                                ts, g_host)
                # depth cap: at most depth_now unACKed pushes in flight
                # -- THE staleness bound the taw admission prices.  The
                # adaptive controller moves the live window within
                # [1, configured depth] (CTRL rides the pull replies
                # this very loop prefetches); without control the cap
                # IS the configured depth.  Reap lazily: ACKs usually
                # sit in the buffer already.
                depth_now = (ctrl_sink.depth(pipe_depth)
                             if ctrl_sink is not None else pipe_depth)
                t_q0 = _trace.now_ms() if cur_tr is not None else 0.0
                blocked = False
                while (push_cl.inflight_pushes() >= depth_now
                       and not done):
                    blocked = True
                    reap_one()
                if done:
                    break
                push_cl.push_start(wid, ts, g_host, sparse=sparse,
                                   tr=cur_tr)
                pl_stats.high_water("inflight_max",
                                    push_cl.inflight_pushes())
                if blocked and cur_tr is not None:
                    # window backpressure: the bounded in-flight cap held
                    # the loop back -- the other face of the pipeline
                    # stage
                    cur_tr.add(_trace.PIPELINE, t_q0, _trace.now_ms())
        finally:
            if push_cl is not None:
                # drain the window: every sent push gets its verdict (a
                # DONE ack inside the tail is fine -- we are leaving)
                while push_cl.inflight_pushes():
                    reap_one()
            released = ((pull_cl is not None and pull_cl.released)
                        or (push_cl is not None and push_cl.released))
            if released:
                with group_lock:
                    active_wids.discard(wid)
            if push_cl is not None:
                push_cl.bye()
            if pull_cl is not None:
                pull_cl.bye()

    def spawn(w: int) -> None:
        target = pipelined_worker_loop if pipe_depth > 0 else worker_loop
        t = threading.Thread(target=target, args=(w,),
                             name=f"dcn-worker-{w}", daemon=True)
        with group_lock:
            threads.append(t)
        t.start()

    # introduce this process to the PS before serving: the supervisor
    # learns the proc token, wids, and pid (local-exit detection); a
    # rejoining process's HELLO is also what deposes its surrogate.  A
    # fixed-membership PS just says WELCOME.  The WELCOME reply is also
    # the SHARD-MAP handshake (parallel/shardgroup.py): against a sharded
    # PS group it carries the per-shard [host, port, lo, hi] map and every
    # loop below runs a ShardedPSClient instead -- so HELLO is retried
    # for the WHOLE worker deadline, never skipped: without the WELCOME
    # this process cannot know whether the PS is a shard group, and
    # serving a sharded group as if it were one PS would pull a single
    # range as the whole model (a width mismatch the loops' transport
    # except clauses cannot absorb).  A PS dark past the deadline aborts
    # the process cleanly instead.
    hello_deadline = time.monotonic() + deadline_s
    hello_ok = False
    while True:
        try:
            hello_cl = PSClient(host, port, proc=proc_token)
            welcome = hello_cl.hello(proc_token, wids, pid=os.getpid())
            hello_cl.bye()
            wire_map = welcome.get("shards") or []
            if len(wire_map) > 1:
                from asyncframework_tpu.parallel.shardgroup import ShardMap

                if algo != "asgd":
                    raise ValueError(
                        "sharded PS groups serve algo='asgd' only"
                    )
                smap = ShardMap.from_wire(wire_map)
                wire_epochs = welcome.get("epochs")
                if wire_epochs:
                    smap_epochs = [int(e) for e in wire_epochs]
            ps_epoch = int(welcome.get("epoch", 0) or 0)
            if welcome.get("ctrl"):
                from asyncframework_tpu.parallel.controller import (
                    ControlSink,
                )

                ctrl_sink = ControlSink(welcome["ctrl"])
            hello_ok = True
            break
        except (ConnectionError, OSError):
            if time.monotonic() >= hello_deadline:
                break
            # gentle pacing: each PSClient ctor already spent a full retry
            # budget (backoff + breaker); hammering here only keeps the
            # shared breaker's open-window fresh and starves the half-open
            # probe that would notice the PS came up
            time.sleep(0.5)
    if not hello_ok:
        # the PS never answered within the worker budget: there is no
        # safe topology to assume, so give up loudly with empty counts
        # (the launcher's summary shows zero contributed gradients)
        return dict(counts)

    for w in wids:
        spawn(w)
    join_deadline = time.monotonic() + deadline_s
    while time.monotonic() < join_deadline:
        with group_lock:
            snapshot = list(threads)
        if all(not t.is_alive() for t in snapshot):
            break
        time.sleep(0.05)
    if eval_wid is not None:
        # distributed optVars evaluation: score the PS's snapshot stack over
        # this process's shards, push one summed loss vector.  Only shards
        # this process still SERVES count -- an adopted shard whose owner
        # rejoined (RELEASED) is evaluated by its real owner, and summing
        # it here too would double-count its loss.  Against a shard group
        # the client assembles the full-width snapshot stack per range.
        # The fan-out is RETRIED under pacing: a shard mid-relaunch
        # (elastic failover; a fenced zombie being replaced right at run
        # end) must cost the eval plane a pause, not the whole trajectory
        # -- before this, one refused dial here crashed the worker and
        # silently voided the assembled loss curve.
        eval_deadline = time.monotonic() + min(60.0, deadline_s)
        while True:
            cl = None
            try:
                cl = make_client()
                times, W = cl.snapshots()
                with group_lock:
                    served = {w: s for w, s in shards.items()
                              if w in active_wids}
                losses = evaluate_snapshots_on_shards(served, times, W,
                                                      cfg.loss)
                cl.send_eval(eval_wid, losses)
                break
            except (ConnectionError, OSError):
                if time.monotonic() >= eval_deadline:
                    break  # trajectory forfeited, counts still returned
                if smap is not None:
                    # a hot-standby promotion may have MOVED a shard's
                    # endpoint since HELLO: every retry here builds a
                    # FRESH facade, so refresh the map from any live
                    # member or the rebuilds would dial the dead
                    # endpoint until the deadline forfeits the curve
                    from asyncframework_tpu.parallel.shardgroup import (
                        resolve_live_group,
                    )

                    smap2, epochs2 = resolve_live_group(smap.entries)
                    if smap2 is not None:
                        smap = smap2
                        if epochs2:
                            smap_epochs = epochs2
                time.sleep(0.5)
            finally:
                if cl is not None:
                    try:
                        cl.bye()
                    except (ConnectionError, OSError):
                        pass
    return counts


def evaluate_snapshots_on_shards(shards: Dict[int, object], times: List[float],
                                 W: np.ndarray, loss: str = "least_squares"
                                 ) -> np.ndarray:
    """Per-snapshot loss SUMS over this process's shards (caller divides by
    global N after summing across processes)."""
    import jax
    import jax.numpy as jnp

    from asyncframework_tpu.ops import steps

    ev_dense = steps.make_trajectory_loss_eval(loss)
    ev_sparse = steps.make_sparse_trajectory_loss_eval()
    total = np.zeros(W.shape[0], np.float64)
    for shard in shards.values():
        if hasattr(shard, "cols"):
            Wd = jax.device_put(jnp.asarray(W), shard.cols.device)
            part = ev_sparse(shard.cols, shard.vals, shard.y, Wd)
        else:
            Wd = jax.device_put(jnp.asarray(W), shard.X.device)
            part = ev_dense(shard.X, shard.y, Wd)
        total += np.asarray(part, np.float64)
    return total
