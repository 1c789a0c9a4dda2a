"""Elastic training plane (ISSUE 2): worker/PS crash survival mid-run.

The supervisor (parallel/supervisor.py) closes the loop between the
robustness primitives (heartbeats, shard re-homing, checkpoints, session
dedup) and the multi-process DCN training path: worker death -> shard
adoption by a survivor (full data coverage at degraded cohort size),
worker rejoin -> surrogate release, PS kill -9 -> restart-from-checkpoint
with exactly-once PUSH semantics ACROSS the restart, and a progress-aware
``wait_done`` that names silent workers instead of hanging.

Layers here mirror the repo's testing doctrine: pure-logic supervisor
tests on a ManualClock; in-process PS + client-thread "processes"
(deterministic interleavings); and one real-OS-process leg where a DCN
worker is SIGKILLed mid-ASGD-run (the acceptance scenario).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.parallel import ps_dcn
from asyncframework_tpu.parallel.supervisor import (
    DEAD,
    ElasticSupervisor,
    recovery_totals,
)
from asyncframework_tpu.solvers import SolverConfig
from asyncframework_tpu.utils.clock import ManualClock

CHILD = Path(__file__).parent / "ps_dcn_child.py"


def make_cfg(**kw):
    defaults = dict(
        num_workers=4, num_iterations=200, gamma=1.2, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=50, seed=42,
        calibration_iters=8, run_timeout_s=120.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


class TestSupervisorLogic:
    """Pure membership logic on a ManualClock -- no sockets, no devices."""

    def _sup(self, nw=4, dead_after_s=1.0, boot_grace_s=5.0):
        clock = ManualClock()
        sup = ElasticSupervisor(nw, dead_after_s=dead_after_s,
                                check_interval_s=0.05,
                                boot_grace_s=boot_grace_s, clock=clock)
        return sup, clock

    def test_silence_declares_dead_and_plans_adoption(self):
        sup, clock = self._sup()
        sup.register("A", [0, 1])
        sup.register("B", [2, 3])
        for w in range(4):
            sup.touch(w, "A" if w < 2 else "B")
        clock.advance(600)
        for w in (0, 1):
            sup.touch(w, "A")   # A stays chatty; B goes silent
        clock.advance(600)      # B's wids now silent for 1.2s > 1.0s
        for w in (0, 1):
            sup.touch(w, "A")
        dead = sup.check_once()
        assert sorted(dead) == [2, 3]
        assert sup.live_worker_count() == 2
        # both orphans re-homed onto the surviving process
        assert sorted(sup.orders_for("A")) == [2, 3]
        assert sup.counters()["workers_lost"] == 2
        assert sup.counters()["shards_adopted"] == 2
        # deposed B may not push its old shards anymore
        assert not sup.owns("B", 2)
        assert sup.owns("A", 2)

    def test_adoption_order_redelivered_until_acked(self):
        sup, clock = self._sup()
        sup.register("A", [0, 1])
        sup.register("B", [2, 3])
        clock.advance(1200)
        sup.touch(0, "A")
        sup.touch(1, "A")
        sup.check_once()
        assert sorted(sup.orders_for("A")) == [2, 3]
        assert sorted(sup.orders_for("A")) == [2, 3]  # still pending
        sup.touch(2, "A")
        sup.ack_adoption("A", 2)   # adopter's first pull for the orphan
        assert sup.orders_for("A") == [3]

    def test_rejoin_takes_shards_back_and_releases_surrogate(self):
        sup, clock = self._sup()
        sup.register("A", [0, 1])
        sup.register("B", [2, 3])
        clock.advance(1200)
        sup.touch(0, "A")
        sup.touch(1, "A")
        sup.check_once()           # B dead, A adopts 2,3
        sup.touch(2, "A")
        sup.ack_adoption("A", 2)
        # B's replacement process comes back with a fresh token
        sup.register("B2", [2, 3])
        assert sup.owns("B2", 2) and sup.owns("B2", 3)
        assert not sup.owns("A", 2)      # surrogate deposed
        assert sup.orders_for("A") == []  # pending adoption revoked
        c = sup.counters()
        assert c["rejoins"] >= 2 and c["releases"] >= 1
        assert sup.live_worker_count() == 4

    def test_unclaimed_shards_wait_for_boot_grace(self):
        sup, clock = self._sup(boot_grace_s=5.0)
        sup.register("A", [0, 1])
        sup.touch(0, "A")
        clock.advance(2000)        # past dead_after, inside boot grace
        sup.touch(0, "A")
        sup.touch(1, "A")
        assert sup.check_once() == []     # 2,3 never claimed: not dead yet
        clock.advance(4000)
        sup.touch(0, "A")
        sup.touch(1, "A")
        dead = sup.check_once()           # grace over: hand them out
        assert sorted(dead) == [2, 3]
        assert sorted(sup.orders_for("A")) == [2, 3]

    def test_process_exit_detected_immediately_via_pid(self):
        import socket as socket_mod

        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        proc.wait()
        sup, clock = self._sup(dead_after_s=60.0)
        # pid probes only apply to peers that HELLO'd from THIS host
        sup.register("gone", [2, 3], pid=proc.pid,
                     host=socket_mod.gethostname())
        sup.register("A", [0, 1])
        sup.touch(0, "A")
        clock.advance(100)        # far inside the silence window
        dead = sup.check_once()   # ...but the pid is gone: dead NOW
        assert sorted(dead) == [2, 3]

    def test_restarted_ps_rebuilds_membership_from_traffic(self):
        # a fresh supervisor (PS restarted) knows nobody; first contact
        # claims the wid instead of bouncing the worker
        sup, _clock = self._sup()
        assert sup.owns("A", 0)
        sup.touch(0, "A")
        assert sup.owns("A", 0) and not sup.owns("B", 0)

    def test_unacked_adoption_order_expires_and_replans(self):
        """An adopter that never acts on its order (failing shard_factory,
        or a classic client that ignores orders) must not strand the
        orphan: past the expiry the orphan re-enters the plan pool."""
        sup, clock = self._sup(dead_after_s=1.0)
        sup.register("A", [0, 1])
        sup.register("B", [2])
        sup.register("C", [3])
        clock.advance(1200)
        sup.touch(0, "A")
        sup.touch(1, "A")
        sup.touch(3, "C")
        sup.check_once()                     # wid 2 dead, order issued
        first_adopter = next(p for p in ("A", "C")
                             if sup.orders_for(p) == [2])
        # the adopter keeps pulling but never acks wid 2; past the
        # expiry (2x dead_after) the order is revoked and re-planned
        # (least-loaded-first may legitimately pick the same proc; the
        # point is the order stays LIVE, not pinned to a stale issue)
        clock.advance(2500)
        sup.touch(0, "A")
        sup.touch(1, "A")
        sup.touch(3, "C")
        before = sup.counters()["shards_adopted"]
        sup.check_once()
        assert sup.counters()["shards_adopted"] == before + 1
        assert any(sup.orders_for(p) == [2] for p in ("A", "C"))
        # once SOME adopter finally picks it up, the order clears
        sup.touch(2, first_adopter if sup.owns(first_adopter, 2) else "C")
        adopter = next(p for p in ("A", "C") if sup.orders_for(p) == [2])
        sup.ack_adoption(adopter, 2)
        assert all(sup.orders_for(p) == [] for p in ("A", "C"))

    def test_dead_adopter_triggers_replan(self):
        sup, clock = self._sup()
        sup.register("A", [0, 1])
        sup.register("B", [2, 3])
        sup.register("C", [])       # idle spare process
        clock.advance(1200)
        sup.touch(0, "A")
        sup.touch(2, "C")           # C chats too (keeps itself live)
        sup.check_once()            # B dead; orphans planned somewhere
        # now A dies as well before picking anything up
        clock.advance(1200)
        sup.touch(2, "C")
        sup.check_once()
        clock.advance(100)
        sup.touch(2, "C")
        sup.check_once()
        # every dead wid's pending adopter must be the only live proc
        pend = sup.orders_for("C")
        member = sup.membership()
        dead_wids = [w for w, m in member.items() if m["state"] == DEAD]
        for w in dead_wids:
            assert member[w]["owner"] == "C" or w in pend


class TestWaitDoneDiagnostic:
    def test_timeout_returns_falsy_diagnostic_not_bare_false(self, devices8):
        cfg = make_cfg(num_iterations=10**6)
        n, d = 256, 8
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port)
            got = cl.pull(0)
            assert got is not None
            cl.push(0, got[0], np.zeros(d, np.float32))
            cl.bye()
            res = ps.wait_done(timeout_s=0.5)
            assert not res                      # falsy like the old False
            s = str(res)
            assert "wid   0" in s and "last-contact" in s
            assert "pushes=1" in s
            # done-bitmap: wid 0 contributed, the rest never did
            assert "contributed-bitmap=1000" in s
            assert "wid   1" in s and "never" in s
        finally:
            ps.stop()

    def test_progress_timeout_fails_fast(self, devices8):
        """No worker contact + no clock movement -> return well before the
        full timeout, with the diagnostic."""
        cfg = make_cfg(num_iterations=10**6)
        ps = ps_dcn.ParameterServer(cfg, 8, 256, device=devices8[0],
                                    port=0).start()
        try:
            t0 = time.monotonic()
            res = ps.wait_done(timeout_s=60.0, progress_timeout_s=0.5)
            elapsed = time.monotonic() - t0
            assert not res and elapsed < 10.0, elapsed
            assert "stalled" in str(res)
        finally:
            ps.stop()

    def test_done_run_stays_truthy(self, devices8):
        cfg = make_cfg(num_iterations=20, bucket_ratio=0.0, num_workers=1)
        n, d = 256, 8
        ds = ShardedDataset.generate_on_device(n, d, 1,
                                               devices=devices8[:1], seed=3)
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        ps_dcn.run_worker_process("127.0.0.1", ps.port, [0],
                                  {0: ds.shard(0)}, cfg, d, n,
                                  deadline_s=60.0)
        res = ps.wait_done(timeout_s=5.0)
        ps.stop()
        assert res and bool(res) is True and str(res) == "done"


class TestElasticInProcess:
    def test_silent_worker_group_adopted_run_covers_all_shards(
            self, devices8):
        """Proc B (wids 2,3) goes silent mid-run; the supervisor declares
        its workers dead and proc A adopts their shards via PULL-reply
        orders -- the run completes with EVERY shard still contributing
        accepted gradients after the death (data coverage), at a cohort
        clamped to live membership."""
        sup = ElasticSupervisor(4, dead_after_s=0.5, check_interval_s=0.1,
                                boot_grace_s=30.0)
        # long enough that the run outlasts the 0.5 s death verdict on its
        # own, not because it happens to compile first
        cfg = make_cfg(num_iterations=1200, printer_freq=400)
        n, d = 1024, 16
        ds = ShardedDataset.generate_on_device(n, d, 4, devices=devices8[:4],
                                               seed=11, noise=0.01)
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0], port=0,
                                    supervisor=sup).start()
        doomed_stop = threading.Event()
        doomed_pushes = {"n": 0}

        def doomed():
            cls = {w: ps_dcn.PSClient("127.0.0.1", ps.port, proc="procB")
                   for w in (2, 3)}
            try:
                cls[2].hello("procB", [2, 3])
                while not doomed_stop.is_set():
                    for w, c in cls.items():
                        got = c.pull(w)
                        if got is None or doomed_stop.is_set():
                            return
                        c.push(w, got[0], np.zeros(d, np.float32))
                        doomed_pushes["n"] += 1
            except (ConnectionError, OSError):
                return

        t_doomed = threading.Thread(target=doomed, daemon=True)
        t_doomed.start()
        counts = {}

        def survivors():
            counts.update(ps_dcn.run_worker_process(
                "127.0.0.1", ps.port, [0, 1],
                {0: ds.shard(0), 1: ds.shard(1)}, cfg, d, n,
                deadline_s=120.0, shard_factory=ds.shard,
                proc_token="procA",
            ))

        t_surv = threading.Thread(target=survivors, daemon=True)
        t_surv.start()
        deadline = time.monotonic() + 30
        while doomed_pushes["n"] < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        doomed_stop.set()
        with ps._lock:
            accepted_at_kill = dict(ps.accepted_by_wid)
        t_surv.join(timeout=120)
        res = ps.wait_done(timeout_s=15.0)
        ps.stop()
        assert res, str(res)
        assert ps.accepted == cfg.num_iterations
        # the dead group's workers were declared lost and their shards
        # adopted (recovery counters visible, incl. process-wide totals)
        c = sup.counters()
        assert c["workers_lost"] >= 2 and c["shards_adopted"] >= 2
        totals = recovery_totals()
        assert totals["workers_lost"] >= 2
        # full data coverage: every shard kept contributing AFTER the kill
        for w in range(4):
            assert ps.accepted_by_wid.get(w, 0) > 0
        for w in (2, 3):
            assert ps.accepted_by_wid[w] > accepted_at_kill.get(w, 0), (
                w, accepted_at_kill, ps.accepted_by_wid,
            )
            assert counts.get(w, 0) > 0   # served by the ADOPTER process

    def test_rejoining_worker_reclaims_shard_from_surrogate(self, devices8):
        """After adoption, a replacement process HELLOs with the dead
        worker's wids: the surrogate is RELEASED mid-run and the rejoiner
        serves its own shard again -- membership rebalances."""
        sup = ElasticSupervisor(2, dead_after_s=0.4, check_interval_s=0.1,
                                boot_grace_s=30.0)
        cfg = make_cfg(num_workers=2, num_iterations=10**6,
                       bucket_ratio=0.0, printer_freq=10**5)
        n, d = 512, 8
        ds = ShardedDataset.generate_on_device(n, d, 2, devices=devices8[:2],
                                               seed=5, noise=0.01)
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0], port=0,
                                    supervisor=sup).start()
        stop_b = threading.Event()
        b_pushes = {"n": 0}

        def proc_b(token, stop_ev, counter):
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, proc=token)
            try:
                cl.hello(token, [1])
                while not stop_ev.is_set():
                    got = cl.pull(1)
                    if got is None:
                        return cl.released
                    cl.push(1, got[0], np.zeros(d, np.float32))
                    counter["n"] += 1
            except (ConnectionError, OSError):
                return False
            finally:
                cl.bye()
            return False

        t_b = threading.Thread(target=proc_b, args=("procB", stop_b, b_pushes),
                               daemon=True)
        t_b.start()
        counts = {}
        t_a = threading.Thread(
            target=lambda: counts.update(ps_dcn.run_worker_process(
                "127.0.0.1", ps.port, [0], {0: ds.shard(0)}, cfg, d, n,
                deadline_s=120.0, shard_factory=ds.shard,
                proc_token="procA")),
            daemon=True,
        )
        t_a.start()
        # let B participate, then crash it (silence)
        deadline = time.monotonic() + 30
        while b_pushes["n"] < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        stop_b.set()
        # wait for A to adopt shard 1
        while (sup.counters()["shards_adopted"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert sup.counters()["shards_adopted"] >= 1
        while counts.get(1, 0) == 0 and time.monotonic() < deadline:
            time.sleep(0.05)  # counts mutates live: adopter serving wid 1
        # rejoin: B2 takes wid 1 back; A's surrogate loop gets RELEASED
        stop_b2 = threading.Event()
        b2_pushes = {"n": 0}
        t_b2 = threading.Thread(target=proc_b,
                                args=("procB2", stop_b2, b2_pushes),
                                daemon=True)
        t_b2.start()
        deadline = time.monotonic() + 60
        while b2_pushes["n"] < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b2_pushes["n"] >= 3, "rejoined process never served"
        c = sup.counters()
        assert c["rejoins"] >= 1 and c["releases"] >= 1
        # the run is open-ended (we tested mid-run membership, not
        # completion); end it -- every pull now answers DONE
        ps._done.set()
        stop_b2.set()
        t_a.join(timeout=30)
        assert not t_a.is_alive()
        ps.stop()
        assert ps.accepted > 0


class TestWorkerSigkill:
    def test_sigkill_dcn_worker_process_midrun_run_completes(
            self, devices8):
        """THE acceptance scenario: a real OS worker process (wids 4..7)
        is SIGKILLed mid-ASGD-run.  The supervisor detects the exit via
        the HELLO'd pid, re-homes all four shards onto the surviving
        process, and the run completes with every shard's samples
        contributing (coverage assert) and recovery counters visible."""
        sup = ElasticSupervisor(8, dead_after_s=1.0, check_interval_s=0.2,
                                boot_grace_s=60.0)
        cfg = make_cfg(num_workers=8, num_iterations=2000, printer_freq=500,
                       run_timeout_s=240.0)
        n, d = 4096, 24
        ds = ShardedDataset.generate_on_device(n, d, 8, devices=devices8,
                                               seed=11, noise=0.01)
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0], port=0,
                                    supervisor=sup).start()
        env_base = dict(os.environ)
        env_base.pop("JAX_PLATFORMS", None)
        env_base.pop("XLA_FLAGS", None)
        env = dict(
            env_base, PS_ROLE="worker", PS_PORT=str(ps.port),
            PS_WORKER_ID="1", PS_NUM_WORKER_PROCS="2",
            PS_WIDS="4,5,6,7", PS_EVAL="0", PS_NUM_ITER="2000",
        )
        doomed = subprocess.Popen(
            [sys.executable, str(CHILD)], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        counts = {}
        try:
            t_surv = threading.Thread(
                target=lambda: counts.update(ps_dcn.run_worker_process(
                    "127.0.0.1", ps.port, [0, 1, 2, 3],
                    {w: ds.shard(w) for w in range(4)}, cfg, d, n,
                    eval_wid=0, deadline_s=240.0, shard_factory=ds.shard,
                    proc_token="survivor")),
                daemon=True,
            )
            t_surv.start()
            # wait until the doomed process has contributed for all its
            # wids, then kill -9 it mid-run
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                with ps._lock:
                    if all(ps.pushes_by_wid.get(w, 0) >= 2
                           for w in (4, 5, 6, 7)):
                        break
                time.sleep(0.05)
            with ps._lock:
                assert all(ps.pushes_by_wid.get(w, 0) >= 2
                           for w in (4, 5, 6, 7)), \
                    "doomed worker process never participated"
                accepted_at_kill = dict(ps.accepted_by_wid)
            doomed.send_signal(signal.SIGKILL)
            doomed.wait(timeout=10)
            t_surv.join(timeout=240)
            assert not t_surv.is_alive(), "survivor never finished"
            res = ps.wait_done(timeout_s=30.0)
            assert res, str(res)
            total = ps.collect_eval(num_worker_procs=1, timeout_s=60.0)
        finally:
            if doomed.poll() is None:
                doomed.kill()
            ps.stop()
        assert ps.accepted == cfg.num_iterations
        # recovery counters: 4 workers lost with the process, 4 shards
        # adopted by the survivor
        c = sup.counters()
        assert c["workers_lost"] >= 4 and c["shards_adopted"] >= 4, c
        # full data coverage: every shard contributed, and the dead
        # process's shards kept contributing AFTER the kill (adoption,
        # not leftovers)
        for w in range(8):
            assert ps.accepted_by_wid.get(w, 0) > 0, ps.accepted_by_wid
        post_kill = sum(
            ps.accepted_by_wid[w] - accepted_at_kill.get(w, 0)
            for w in (4, 5, 6, 7)
        )
        assert post_kill > 0, (accepted_at_kill, ps.accepted_by_wid)
        assert sum(counts.get(w, 0) for w in (4, 5, 6, 7)) > 0, counts
        # the run converged over the FULL dataset (survivor evaluated its
        # own + adopted shards = all 8)
        assert total is not None
        traj = np.asarray(total) / n
        assert traj[-1] < traj[0] * 0.05, traj


class TestRunSyncFailFast:
    def test_killed_executor_aborts_run_sync_promptly_with_diagnostic(
            self, devices8, monkeypatch):
        """SIGKILL-analog during the synchronous barrier: with heartbeat
        monitoring off, a dead executor used to hang the drain for the
        full run timeout; now it aborts within the dead-grace window and
        the error names the dead worker with per-worker liveness."""
        from asyncframework_tpu.solvers import asgd as asgd_mod
        from asyncframework_tpu.solvers import engine_loop
        from asyncframework_tpu.solvers.base import DeadWorkerError

        class SlowW2(engine_loop.DelayModel):
            """Worker 2's task holds the executor busy long enough for the
            kill to land mid-task deterministically."""

            def delay_ms(self, wid):
                return 3000.0 if wid == 2 else 0.0

        monkeypatch.setattr(engine_loop, "DelayModel", SlowW2)
        X = np.random.default_rng(0).normal(size=(256, 8)).astype(np.float32)
        y = X @ np.ones(8, np.float32)
        cfg = make_cfg(num_iterations=50, heartbeat=False,
                       run_timeout_s=300.0)
        solver = asgd_mod.ASGD(X, y, cfg, devices=devices8[:4])
        err = {}

        def run():
            try:
                solver.run_sync()
            except Exception as e:  # noqa: BLE001 - captured for asserts
                err["e"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        deadline = time.monotonic() + 60
        killed = False
        first_job = None
        while time.monotonic() < deadline and not killed:
            sched = getattr(solver, "scheduler", None)
            if sched is not None:
                ex = sched.pool.executors.get(2)
                task = ex.current_task if ex is not None else None
                if task is not None and first_job is None:
                    first_job = task.job_id
                # kill mid-task but only from round 1 on (a later job than
                # the first one seen): the scheduler's FIRST job blocks
                # inside run_job (first-iteration warm-up semantics)
                # before the drain loop ever runs
                if (task is not None and ex.busy
                        and task.job_id != first_job):
                    ex.kill()   # mid-task: its result will never report
                    killed = True
            time.sleep(0.01)
        assert killed, "executor 2 never observed busy past round 0"
        t.join(timeout=30)   # must abort FAR below run_timeout_s=300
        assert not t.is_alive(), "run_sync hung after executor death"
        assert isinstance(err.get("e"), DeadWorkerError), err
        msg = str(err["e"])
        assert "wid   2" in msg and "DEAD" in msg
        assert "last-heartbeat" in msg
