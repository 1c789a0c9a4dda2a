"""Pipelined update loop (ISSUE 5): prefetched pulls, decoupled pushes,
lock-free PULL serving.

The correctness spine:

- depth=0 IS the serial loop: same accepted/dropped/staleness trajectory
  under a fixed seed AND byte-identical wire (per-op frame-byte totals),
  with the pipelined code path provably never entered;
- seeded chaos (drop_reply / cut_mid_frame) on the prefetch and push
  connections never yields a wrong model basis (the CRC machinery
  degrades to full pulls) and never double-applies a push (window
  replays are answered from the PS dedup window);
- the debug lock watchdog (net/lockwatch.py) proves no socket send/recv
  ever happens while the PS model lock is held -- the lock-free PULL
  claim -- on both a unit socketpair and a real pipelined run;
- a real two-process DCN run with pipelining on passes the
  full-coverage assert (every shard's samples contributed).
"""

import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu.conf import AsyncConf, set_global_conf
from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.metrics import trace as trace_mod
from asyncframework_tpu.net import frame, lockwatch, reset_net_totals
from asyncframework_tpu.net import faults
from asyncframework_tpu.net.faults import (
    CUT_MID_FRAME,
    DROP_REPLY,
    FaultSchedule,
)
from asyncframework_tpu.parallel import ps_dcn
from asyncframework_tpu.solvers import SolverConfig

pytestmark = pytest.mark.pipeline

CHILD = Path(__file__).parent / "ps_dcn_child.py"


def make_cfg(**kw):
    defaults = dict(
        num_workers=2, num_iterations=60, gamma=1.2, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.0, printer_freq=20, seed=42,
        calibration_iters=8, run_timeout_s=120.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


@pytest.fixture(autouse=True)
def _clean_state():
    """Pipeline totals, wire-byte totals, and fault schedules are
    process-global; runs must neither inherit nor leak them."""
    ps_dcn.reset_pipeline_totals()
    reset_net_totals()
    faults.clear()
    yield
    ps_dcn.reset_pipeline_totals()
    reset_net_totals()
    faults.clear()
    set_global_conf(None)


def run_dcn(devices, cfg, conf, nw=None, n=1024, d=16, seed=11,
            deadline_s=120.0):
    """One in-process PS + worker-process run under ``conf``."""
    nw = nw if nw is not None else cfg.num_workers
    set_global_conf(conf)
    ds = ShardedDataset.generate_on_device(n, d, nw, devices=devices[:nw],
                                           seed=seed, noise=0.01)
    ps = ps_dcn.ParameterServer(cfg, d, n, device=devices[0], port=0).start()
    try:
        shards = {w: ds.shard(w) for w in range(nw)}
        counts = ps_dcn.run_worker_process(
            "127.0.0.1", ps.port, list(range(nw)), shards, cfg, d, n,
            deadline_s=deadline_s,
        )
        done = ps.wait_done(timeout_s=10.0)
        return ps, counts, done
    finally:
        ps.stop()


# ------------------------------------------------------ depth=0 identity
class TestDepthZeroIsSerial:
    def test_depth0_never_enters_pipelined_path(self, devices8):
        """With the knob unset (default 0) the pipelined machinery must
        not even be touched: a serial run leaves ZERO pipeline counters
        (the pipelined loop cannot run without bumping them -- every
        consumed model ticks a hit or a wait)."""
        conf = AsyncConf().set("async.trace.sample", 0.0)
        cfg = make_cfg(num_workers=1, num_iterations=30)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=1)
        assert done and ps.accepted == 30
        assert ps_dcn.pipeline_totals() == {}

    def test_depth0_conf_set_matches_unset_byte_identical(self, devices8):
        """`async.pipeline.depth=0` is byte-identical on the wire and
        step-identical (accepted/dropped/staleness) to the knob being
        absent, under a fixed seed.  One worker + full pulls + no
        calibration makes the whole exchange deterministic, so per-op
        frame-byte totals must match EXACTLY."""
        results = []
        for depth_conf in (None, "0"):
            conf = (AsyncConf().set("async.pull.mode", "full")
                    .set("async.trace.sample", 0.0))
            if depth_conf is not None:
                conf.set("async.pipeline.depth", depth_conf)
            reset_net_totals()
            cfg = make_cfg(num_workers=1, num_iterations=40,
                           calibration_iters=10**9)
            ps, counts, done = run_dcn(devices8, cfg, conf, nw=1)
            assert done, "run did not finish"
            results.append({
                "accepted": ps.accepted,
                "dropped": ps.dropped,
                "max_staleness": ps.max_staleness,
                "clock": ps._clock,
                "pull_replies": dict(ps.pull_replies),
                "bytes": frame.bytes_totals(),
            })
        unset, zero = results
        assert unset["accepted"] == zero["accepted"] == 40
        assert unset["dropped"] == zero["dropped"]
        assert unset["max_staleness"] == zero["max_staleness"]
        assert unset["clock"] == zero["clock"]
        assert unset["pull_replies"] == zero["pull_replies"]
        # byte-identity: every op's sent/recv frame-byte totals agree
        assert unset["bytes"] == zero["bytes"], (unset["bytes"],
                                                 zero["bytes"])


# ---------------------------------------------------------- pipelined run
class TestPipelinedRun:
    def test_run_completes_with_full_coverage_and_counters(self, devices8):
        """Pipelined loop end to end: run completes, every shard
        contributed accepted gradients, the prefetch/window counters
        engaged, and the `pipeline` trace stage shows up in the
        aggregator (spans piggybacked to the PS)."""
        trace_mod.reset_aggregator()
        conf = (AsyncConf().set("async.pull.mode", "delta")
                .set("async.pipeline.depth", 2)
                .set("async.trace.sample", 0.25))
        cfg = make_cfg(num_workers=4, num_iterations=200,
                       bucket_ratio=0.5)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=4)
        assert done, "pipelined run did not finish"
        assert ps.accepted == 200
        for w in range(4):
            assert ps.accepted_by_wid.get(w, 0) > 0, ps.accepted_by_wid
        pl = ps_dcn.pipeline_totals()
        assert pl.get("pushes_async", 0) >= 200
        assert 1 <= pl.get("inflight_max", 0) <= 2
        assert (pl.get("prefetch_hits", 0)
                + pl.get("prefetch_waits", 0)) >= 200
        snap = trace_mod.aggregator().snapshot()
        assert trace_mod.PIPELINE in snap["stages_ms"], snap["stages_ms"]

    def test_asaga_ignores_pipeline_depth(self, devices8):
        """ASAGA's PS-side sampling holds one pending (idx, alpha) slot
        per wid -- the pipelined loop must never run for it, whatever
        the conf says."""
        conf = (AsyncConf().set("async.pipeline.depth", 4)
                .set("async.trace.sample", 0.0))
        set_global_conf(conf)
        n, d, nw = 512, 12, 2
        cfg = make_cfg(num_workers=nw, num_iterations=40, gamma=0.5)
        ds = ShardedDataset.generate_on_device(n, d, nw,
                                               devices=devices8[:nw],
                                               seed=3, noise=0.01)
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0], port=0,
                                    algo="asaga").start()
        try:
            shards = {w: ds.shard(w) for w in range(nw)}
            ps_dcn.run_worker_process(
                "127.0.0.1", ps.port, list(range(nw)), shards, cfg, d, n,
                deadline_s=120.0, algo="asaga",
            )
            assert ps.wait_done(timeout_s=10.0)
            assert ps.accepted == 40
            # the serial ASAGA path leaves no pipeline counters behind
            assert ps_dcn.pipeline_totals() == {}
        finally:
            ps.stop()

    def test_taw_rejections_trigger_stale_prefetch_discards(self, devices8):
        """taw=0 makes every in-flight-stale push bounce; each rejection
        must make the worker discard its prefetched model and re-pull
        fresh (the pipelined loop's staleness feedback)."""
        conf = (AsyncConf().set("async.pull.mode", "delta")
                .set("async.pipeline.depth", 2)
                .set("async.trace.sample", 0.0))
        cfg = make_cfg(num_workers=2, num_iterations=30, taw=0)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=2)
        assert done, "taw=0 pipelined run did not finish"
        assert ps.accepted == 30
        pl = ps_dcn.pipeline_totals()
        if ps.dropped >= 2:
            assert pl.get("stale_discards", 0) >= 1, (ps.dropped, pl)


# ------------------------------------------------------------- chaos
class TestPipelineChaos:
    def test_faults_on_both_connections_never_wrong_never_double(
            self, devices8):
        """Seeded drop_reply/cut_mid_frame on the prefetch (PULL) and
        push (PUSH) connections: the run still completes exactly, the
        clock never exceeds the gradients actually computed (no push
        applied twice -- window replays hit the dedup cache), and every
        scheduled fault fired."""
        conf = (AsyncConf().set("async.pull.mode", "delta")
                .set("async.pipeline.depth", 2)
                .set("async.trace.sample", 0.0))
        set_global_conf(conf)
        n, d, nw = 1024, 16, 2
        cfg = make_cfg(num_workers=nw, num_iterations=80)
        ds = ShardedDataset.generate_on_device(n, d, nw,
                                               devices=devices8[:nw],
                                               seed=11, noise=0.01)
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        ep = f"127.0.0.1:{ps.port}"
        sched = (FaultSchedule(seed=13)
                 .add(ep, "PULL", 3, DROP_REPLY)
                 .add(ep, "PULL", 9, CUT_MID_FRAME)
                 .add(ep, "PULL", 15, DROP_REPLY)
                 .add(ep, "PUSH", 4, DROP_REPLY)
                 .add(ep, "PUSH", 11, CUT_MID_FRAME)
                 .add(ep, "PUSH", 17, DROP_REPLY))
        try:
            with faults.injected(sched) as inj:
                shards = {w: ds.shard(w) for w in range(nw)}
                counts = ps_dcn.run_worker_process(
                    "127.0.0.1", ps.port, list(range(nw)), shards, cfg,
                    d, n, deadline_s=120.0,
                )
                done = ps.wait_done(timeout_s=10.0)
                assert done, "chaos pipelined run did not finish"
                assert ps.accepted == 80
                # exactly-once: every merged push maps to one computed
                # gradient; a double-applied window replay would break
                # clock <= computed
                assert ps._clock <= sum(counts.values()), (
                    ps._clock, counts,
                )
                # the drop_reply-on-PUSH faults force window replays of
                # already-applied pushes: the dedup cache must answer
                assert ps.dedup_hits >= 1
                assert inj.remaining() == [], "all faults must fire"
        finally:
            ps.stop()


# ------------------------------------------------------- accept-loop reap
class TestAcceptLoopReap:
    def test_finished_handler_threads_are_reaped(self, devices8):
        """A long-running PS must not accumulate one Thread object per
        connection ever accepted: finished handlers are pruned on
        accept and on stop()."""
        cfg = make_cfg(num_workers=1, num_iterations=10**6)
        ps = ps_dcn.ParameterServer(cfg, 8, 64,
                                    device=devices8[0], port=0).start()
        try:
            for _ in range(12):
                cl = ps_dcn.PSClient("127.0.0.1", ps.port)
                cl.bye()
                # wait for the handler to exit before the next connect so
                # the reap-on-append has something to prune
                deadline = time.monotonic() + 5
                while (sum(t.is_alive() for t in ps._threads) > 0
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
            assert len(ps._threads) <= 3, (
                f"{len(ps._threads)} handler threads retained after 12 "
                f"sequential connections"
            )
        finally:
            ps.stop()
        # reap-on-stop dropped whatever had finished by then too
        assert len(ps._threads) <= 3


# ------------------------------------------------------------- lockwatch
class TestLockWatchdog:
    def test_socket_io_under_watched_lock_raises(self):
        """The watchdog's core contract at the frame choke point."""
        lockwatch.reset_totals()
        lockwatch.enable(True)
        try:
            a, b = socket.socketpair()
            wl = lockwatch.WatchedLock("test.model")
            with wl:
                with pytest.raises(AssertionError, match="test.model"):
                    frame.send_msg(a, {"op": "PING"})
            # outside the hold the same send goes through
            frame.send_msg(a, {"op": "PING"})
            hdr, _ = frame.recv_msg(b)
            assert hdr["op"] == "PING"
            a.close()
            b.close()
            t = lockwatch.totals()
            assert t["violations"] == 1
            assert t["holds"] >= 1
            assert t["max_hold_ms"] >= 0.0
        finally:
            lockwatch.enable(False)
            lockwatch.reset_totals()

    def test_pipelined_run_is_clean_under_watchdog(self, devices8):
        """The lock-free PULL claim, checked live: a pipelined run with
        the watchdog armed (watched PS model lock) completes with ZERO
        violations and real hold-time stats."""
        lockwatch.reset_totals()
        lockwatch.enable(True)
        try:
            conf = (AsyncConf().set("async.pull.mode", "delta")
                    .set("async.pipeline.depth", 2)
                    .set("async.trace.sample", 0.0))
            cfg = make_cfg(num_workers=2, num_iterations=60)
            ps, counts, done = run_dcn(devices8, cfg, conf, nw=2)
            assert done and ps.accepted == 60
            assert isinstance(ps._lock, lockwatch.WatchedLock)
            t = lockwatch.totals()
            assert t["violations"] == 0, t
            assert t["holds"] > 0
        finally:
            lockwatch.enable(False)
            lockwatch.reset_totals()

    def test_live_ui_snapshot_carries_pipeline_and_lockwatch(self):
        from asyncframework_tpu.metrics.live import LiveStateListener

        snap = LiveStateListener(2).snapshot()
        assert "pipeline" in snap
        assert "lockwatch" in snap
        assert set(snap["lockwatch"]) >= {"enabled", "holds",
                                          "violations", "max_hold_ms"}


# ----------------------------------------------------- two-process run
class TestTwoProcessPipelined:
    def test_real_worker_process_pipelined_full_coverage(self, devices8):
        """THE acceptance scenario: a real OS worker process runs the
        pipelined loop (depth 2, delta pulls) against an in-process PS;
        the run completes with EVERY shard's samples contributing
        accepted gradients, and the worker's pipeline counters arrive at
        the PS via the PUSH/BYE piggyback."""
        ps_dcn.reset_pipeline_totals()
        nw, n, d = 8, 4096, 24
        cfg = SolverConfig(
            num_workers=nw, num_iterations=400, gamma=1.2,
            taw=2**31 - 1, batch_rate=0.3, bucket_ratio=0.5,
            printer_freq=50, seed=42, calibration_iters=20,
            run_timeout_s=120.0,
        )
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env.update(
            PS_ROLE="worker", PS_PORT=str(ps.port), PS_WORKER_ID="0",
            PS_NUM_WORKER_PROCS="1", PS_EVAL="0", PS_NUM_ITER="400",
            ASYNCTPU_ASYNC_PIPELINE_DEPTH="2",
            ASYNCTPU_ASYNC_PULL_MODE="delta",
            ASYNCTPU_ASYNC_TRACE_SAMPLE="0.25",
        )
        try:
            worker = subprocess.run(
                [sys.executable, str(CHILD)], env=env,
                capture_output=True, text=True, timeout=180,
            )
            assert worker.returncode == 0, worker.stderr[-2000:]
            res = ps.wait_done(timeout_s=30.0)
            assert res, str(res)
        finally:
            ps.stop()
        assert ps.accepted == 400
        # full data coverage: every shard contributed accepted gradients
        for w in range(nw):
            assert ps.accepted_by_wid.get(w, 0) > 0, ps.accepted_by_wid
        # the pipelined loop really ran in the child, and its counters
        # crossed the process boundary on the piggyback
        pl = ps_dcn.pipeline_totals()
        assert pl.get("pushes_async", 0) >= 400, pl
        assert pl.get("inflight_max", 0) >= 1, pl
