"""Serving tier (ISSUE 6): snapshot-subscribing predict replicas with
freshness-lag SLOs.

The correctness spine:

- a replica's served model is ALWAYS a version the PS actually published:
  refreshes ride the CRC-gated delta-pull machinery (NM/XDELTA/FULL with
  full-pull fallback), the served reference swaps atomically, and seeded
  chaos on the SUBSCRIBE stream (drop_reply / cut_mid_frame) can delay a
  refresh but never tear a model;
- PREDICT replies are stamped with the served version and its freshness
  lag (versions + ms); a replica past the staleness SLO answers
  UNHEALTHY and the frontend fails over -- unless the run is DONE and
  the replica holds the final version (fresh forever by construction);
- the frontend's rotation survives replica death: a real kill -9 of a
  replica OS process mid-load degrades to failover, never an outage,
  and the PR 2 membership machinery (adopt=False mode) declares the
  corpse dead by pid probe.
"""

import os
import signal
import socket as socket_mod
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu.conf import set_global_conf
from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.metrics import reset_totals
from asyncframework_tpu.metrics.live import LiveStateListener
from asyncframework_tpu.net import frame as _frame
from asyncframework_tpu.net import faults
from asyncframework_tpu.net.faults import (
    CUT_MID_FRAME,
    DROP_REPLY,
    FaultSchedule,
)
from asyncframework_tpu.net.retry import reset_breakers
from asyncframework_tpu.ops import steps
from asyncframework_tpu.parallel import ps_dcn
from asyncframework_tpu.serving import (
    ModelReplica,
    PredictError,
    ServingFrontend,
)
from asyncframework_tpu.serving.replica import serve_replica
from asyncframework_tpu.serving import metrics as smetrics
from asyncframework_tpu.solvers import SolverConfig

pytestmark = pytest.mark.serve

REPO = Path(__file__).parent.parent
CHAOS_SEED = int(os.environ.get("ASYNC_CHAOS_SEED", "7"))


def make_cfg(**kw):
    defaults = dict(
        num_workers=2, num_iterations=40, gamma=1.2, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.0, printer_freq=10, seed=42,
        calibration_iters=4, run_timeout_s=60.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


@pytest.fixture(autouse=True)
def _clean_state():
    """Serving totals, fault schedules, and endpoint breakers are
    process-global; tests must neither inherit nor leak them."""
    reset_totals()
    reset_breakers()
    faults.clear()
    yield
    reset_totals()
    reset_breakers()
    faults.clear()
    set_global_conf(None)


def start_ps(devices, cfg=None, d=16, n=256):
    cfg = cfg or make_cfg()
    ps = ps_dcn.ParameterServer(cfg, d, n, device=devices[0],
                                port=0).start()
    return ps, cfg, d, n


def push_once(cl, wid, d, scale=1.0):
    """One pull+push through a FULL-mode client: advances the model by a
    known gradient (taw=inf, so it always lands)."""
    ts, _w, _avg, _cal = cl.pull(wid)
    cl.push(wid, ts, np.full(d, scale, np.float32))


def predict_direct(port: int, X: np.ndarray):
    """One raw PREDICT frame against a replica (no frontend)."""
    X = np.ascontiguousarray(X, np.float32)
    sock = _frame.connect(("127.0.0.1", port))
    try:
        _frame.send_msg(sock, {"op": "PREDICT", "n": X.shape[0]},
                        X.tobytes())
        return _frame.recv_msg(sock)
    finally:
        sock.close()


# -------------------------------------------------------------- predict op
class TestPredictStep:
    def test_matches_numpy(self, rng):
        X = rng.normal(size=(32, 16)).astype(np.float32)
        w = rng.normal(size=16).astype(np.float32)
        y = np.asarray(steps.make_predict_step("least_squares")(X, w))
        np.testing.assert_allclose(y, X @ w, rtol=1e-5, atol=1e-5)
        p = np.asarray(steps.make_predict_step("logistic")(X, w))
        np.testing.assert_allclose(p, 1.0 / (1.0 + np.exp(-(X @ w))),
                                   rtol=1e-5, atol=1e-5)

    def test_unknown_loss_rejected(self):
        with pytest.raises(ValueError):
            steps.make_predict_step("hinge")


# ------------------------------------------------------------ replica core
class TestReplicaRefresh:
    def test_refresh_matches_direct_pull_at_same_version(self, devices8,
                                                         rng):
        """THE correctness claim: what the replica serves is byte-for-byte
        what a direct PS pull returns at the same version."""
        ps, cfg, d, n = start_ps(devices8)
        rep = None
        try:
            pusher = ps_dcn.PSClient("127.0.0.1", ps.port,
                                     pull_mode="full")
            for i in range(5):
                push_once(pusher, 0, d, scale=0.1 * (i + 1))
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0).start()
            assert rep.refresh_once()
            served = rep._served
            direct = ps_dcn.PSClient("127.0.0.1", ps.port,
                                     pull_mode="delta").subscribe(9)
            ts, w, clock, k, _age, _done = direct
            assert served.ts == ts == clock and k == 5
            assert served.w_host.tobytes() == w.tobytes()
            # and the wire PREDICT agrees with the math
            X = rng.normal(size=(8, d)).astype(np.float32)
            hdr, payload = predict_direct(rep.port, X)
            assert hdr["op"] == "PREDICTION" and hdr["ts"] == ts
            y = np.frombuffer(payload, np.float32)
            np.testing.assert_allclose(y, X @ w, rtol=1e-5, atol=1e-5)
        finally:
            if rep is not None:
                rep.stop()
            ps.stop()

    def test_refresh_shapes_nm_then_full_on_change(self, devices8):
        """Steady state is a header-only NOT_MODIFIED; a changed model
        re-syncs via delta/full -- the PR 4 cache-invalidation protocol
        doing replica duty."""
        ps, cfg, d, n = start_ps(devices8)
        rep = None
        try:
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0).start()
            assert rep.refresh_once()  # first: full (no basis)
            assert rep.refresh_once()  # unchanged: NM
            # >=: the background loop's own first refresh also counts
            assert rep._client.pull_wenc["nm"] >= 1
            pusher = ps_dcn.PSClient("127.0.0.1", ps.port,
                                     pull_mode="full")
            push_once(pusher, 0, d)
            assert rep.refresh_once()
            assert (rep._client.pull_wenc["full"]
                    + rep._client.pull_wenc["xdelta"] >= 2)
            assert rep._served.ts == ps._clock
            # NM replies cost zero model payload on the PS side
            assert ps.subscribe_replies["nm"] >= 1
        finally:
            if rep is not None:
                rep.stop()
            ps.stop()

    def test_crc_mismatch_falls_back_to_full_pull(self, devices8):
        """A corrupted basis can never be served: the next NM/delta
        decode fails its CRC and the client re-pulls FULL."""
        ps, cfg, d, n = start_ps(devices8)
        rep = None
        try:
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0).start()
            assert rep.refresh_once()
            cl = rep._client
            ts, w, crc = cl._basis[0]
            cl._basis[0] = (ts, w, crc ^ 0xDEADBEEF)  # poison the CRC
            assert rep.refresh_once()
            assert cl.delta_fallbacks == 1
            direct = ps_dcn.PSClient("127.0.0.1", ps.port,
                                     pull_mode="delta").subscribe(9)
            assert rep._served.w_host.tobytes() == direct[1].tobytes()
            assert smetrics.serving_totals().get("refresh_fallbacks") == 1
        finally:
            if rep is not None:
                rep.stop()
            ps.stop()


# ---------------------------------------------------------- freshness lag
class TestFreshnessLag:
    def test_version_age_on_ps(self, devices8):
        """age_ms(ts) is 0 while ts is still the served content (dropped
        pushes tick the clock without changing the model) and grows once
        a newer version is published."""
        ps, cfg, d, n = start_ps(devices8)
        try:
            pusher = ps_dcn.PSClient("127.0.0.1", ps.port,
                                     pull_mode="full")
            push_once(pusher, 0, d)
            c = ps._clock
            assert ps._version_age_ms(c, c) == 0.0
            time.sleep(0.05)
            push_once(pusher, 0, d)
            age = ps._version_age_ms(c, ps._clock)
            assert age > 0.0
        finally:
            ps.stop()

    def test_reply_lag_fields(self, devices8, rng):
        ps, cfg, d, n = start_ps(devices8)
        rep = None
        try:
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0).start()
            assert rep.refresh_once()
            hdr, _ = predict_direct(rep.port,
                                    rng.normal(size=(2, d)).astype(
                                        np.float32))
            assert hdr["lag_versions"] == 0
            assert hdr["lag_ms"] >= 0.0
            assert hdr["ts"] == rep._served.ts
        finally:
            if rep is not None:
                rep.stop()
            ps.stop()

    def test_unhealthy_past_staleness_slo_and_recovery(self, devices8,
                                                       rng):
        """A replica whose refresh is older than the SLO answers
        UNHEALTHY (the frontend raises once NOBODY is healthy); the next
        successful refresh restores it."""
        ps, cfg, d, n = start_ps(devices8)
        rep = fe = None
        try:
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0,
                               max_stale_ms=120.0).start()
            assert rep.refresh_once()
            assert rep.healthy()
            fe = ServingFrontend([("127.0.0.1", rep.port)],
                                 deadline_s=0.4).start()
            X = rng.normal(size=(2, d)).astype(np.float32)
            fe.predict(X)  # fresh: answers
            time.sleep(0.3)  # blow the 120 ms SLO
            assert not rep.healthy()
            with pytest.raises(PredictError):
                fe.predict(X)
            assert smetrics.serving_totals()["unhealthy_rejects"] > 0
            assert rep.refresh_once()  # refresh lands: healthy again
            fe.predict(X)
        finally:
            if fe is not None:
                fe.stop()
            if rep is not None:
                rep.stop()
            ps.stop()

    def test_done_run_is_fresh_forever(self, devices8, rng):
        """Training DONE + final version held => the model can never
        change again: the replica stays healthy with the PS gone (reads
        outlive the training plane)."""
        cfg = make_cfg(num_iterations=20)
        ps, cfg, d, n = start_ps(devices8, cfg)
        rep = None
        try:
            ds = ShardedDataset.generate_on_device(
                n, d, cfg.num_workers, devices=devices8[:2], seed=11,
                noise=0.01,
            )
            ps_dcn.run_worker_process(
                "127.0.0.1", ps.port, list(range(cfg.num_workers)),
                {w: ds.shard(w) for w in range(cfg.num_workers)},
                cfg, d, n, deadline_s=60.0,
            )
            assert ps.wait_done(timeout_s=10.0)
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0,
                               max_stale_ms=100.0).start()
            assert rep.refresh_once()
            served = rep._served
            assert served.done and served.ts >= served.clock
            ps.stop()
            time.sleep(0.25)  # way past the SLO; done-exemption holds
            assert rep.healthy()
            hdr, _ = predict_direct(
                rep.port, rng.normal(size=(2, d)).astype(np.float32)
            )
            assert hdr["op"] == "PREDICTION"
            assert hdr["lag_versions"] == 0 and hdr["lag_ms"] == 0.0
        finally:
            if rep is not None:
                rep.stop()
            ps.stop()


# ------------------------------------------------------------------ chaos
class TestServingChaos:
    def test_subscribe_chaos_never_serves_a_torn_model(self, devices8):
        """Seeded drop_reply / cut_mid_frame on the SUBSCRIBE stream: the
        retry layer re-pulls, the CRC gate discards anything suspect, and
        every model the replica EVER serves is byte-for-byte a version
        the PS actually published."""
        ps, cfg, d, n = start_ps(devices8)
        rep = None
        try:
            pusher = ps_dcn.PSClient("127.0.0.1", ps.port,
                                     pull_mode="full")
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0).start()
            assert rep.refresh_once()  # clean first sync
            versions = {}  # ts -> published bytes, harvested via PULL
            sched = FaultSchedule(seed=CHAOS_SEED)
            sched.add("*", "SUBSCRIBE", 1, DROP_REPLY)
            sched.add("*", "SUBSCRIBE", 3, CUT_MID_FRAME)
            sched.add("*", "SUBSCRIBE", 5, DROP_REPLY)
            with faults.injected(sched) as inj:
                for i in range(6):
                    push_once(pusher, 0, d, scale=0.1 * (i + 1))
                    ts, w, _avg, _cal = pusher.pull(0)
                    versions[ts] = w.tobytes()
                    if rep.refresh_once():
                        served = rep._served
                        assert served.ts in versions
                        assert (served.w_host.tobytes()
                                == versions[served.ts]), \
                            "torn model served after wire fault"
                assert inj.fired, "schedule never fired"
            # post-chaos: one clean refresh converges on the live version
            assert rep.refresh_once()
            ts, w, *_rest = ps_dcn.PSClient(
                "127.0.0.1", ps.port, pull_mode="delta"
            ).subscribe(9)
            assert rep._served.ts == ts
            assert rep._served.w_host.tobytes() == w.tobytes()
        finally:
            if rep is not None:
                rep.stop()
            ps.stop()

    def test_predict_chaos_and_dead_replica_failover(self, devices8, rng):
        """drop_reply on a PREDICT is retried/failed over transparently;
        a stopped replica drops out of rotation and the frontend keeps
        answering from the survivor."""
        ps, cfg, d, n = start_ps(devices8)
        rep_a = rep_b = fe = None
        try:
            rep_a = ModelReplica("127.0.0.1", ps.port, rid=0,
                                 host="127.0.0.1",
                                 refresh_interval_s=999.0).start()
            rep_b = ModelReplica("127.0.0.1", ps.port, rid=1,
                                 host="127.0.0.1",
                                 refresh_interval_s=999.0).start()
            assert rep_a.refresh_once() and rep_b.refresh_once()
            fe = ServingFrontend(
                [("127.0.0.1", rep_a.port), ("127.0.0.1", rep_b.port)],
                deadline_s=2.0,
            ).start()
            X = rng.normal(size=(4, d)).astype(np.float32)
            expect = X @ np.asarray(rep_a._served.w_host)
            sched = FaultSchedule(seed=CHAOS_SEED)
            sched.add("*", "PREDICT", 1, DROP_REPLY)
            sched.add("*", "PREDICT", 2, CUT_MID_FRAME)
            with faults.injected(sched) as inj:
                for _ in range(4):
                    y = fe.predict(X)
                    np.testing.assert_allclose(y, expect, rtol=1e-5,
                                               atol=1e-5)
                assert inj.fired
            # now lose a replica outright: rotation degrades, answers don't
            rep_a.stop()
            for _ in range(4):
                y, meta = fe.predict_ex(X)
                np.testing.assert_allclose(y, expect, rtol=1e-5,
                                           atol=1e-5)
                assert meta["endpoint"].endswith(str(rep_b.port))
        finally:
            if fe is not None:
                fe.stop()
            for r in (rep_a, rep_b):
                if r is not None:
                    r.stop()
            ps.stop()


# --------------------------------------------- kill -9 acceptance (2 proc)
class TestKillNineAcceptance:
    def test_sigkill_replica_mid_load_frontend_keeps_answering(
            self, devices8, rng, tmp_path):
        """THE acceptance test: two REAL replica OS processes register
        with the frontend via HELLO; one is SIGKILLed mid-load; every
        client request keeps being answered (failover within the
        deadline, zero client-visible errors) and the membership
        machinery declares the corpse dead by pid probe."""
        cfg = make_cfg(num_iterations=10_000)
        ps, cfg, d, n = start_ps(devices8)
        fe = None
        procs = []
        try:
            fe = ServingFrontend(deadline_s=3.0).serve(port=0,
                                                       host="127.0.0.1")
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = str(REPO)
            env["ASYNCTPU_ASYNC_SERVE_REFRESH_INTERVAL_S"] = "0.02"
            for rid in range(2):
                procs.append(subprocess.Popen(
                    [sys.executable, "-m",
                     "asyncframework_tpu.serving.cli", "replica",
                     "--ps", f"127.0.0.1:{ps.port}",
                     "--host", "127.0.0.1", "--rid", str(rid),
                     "--frontend", f"127.0.0.1:{fe.port}"],
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    env=env, cwd=str(REPO), text=True,
                ))
            deadline = time.monotonic() + 90.0
            while fe.replica_count() < 2:
                assert time.monotonic() < deadline, \
                    "replicas never registered"
                time.sleep(0.1)
            # light training keeps versions moving under the load
            pusher = ps_dcn.PSClient("127.0.0.1", ps.port,
                                     pull_mode="full")
            X = rng.normal(size=(4, d)).astype(np.float32)
            answered = 0
            endpoints = set()
            for i in range(60):
                if i == 20:
                    os.kill(procs[0].pid, signal.SIGKILL)
                if i % 10 == 0:
                    push_once(pusher, 0, d, scale=0.05)
                y, meta = fe.predict_ex(X)  # must NEVER raise
                assert y.shape == (4,)
                answered += 1
                endpoints.add(meta["endpoint"])
                time.sleep(0.01)
            assert answered == 60
            assert len(endpoints) == 2  # both replicas served pre-kill
            # the pid probe (HELLO carried pid+host) declares the corpse
            member_deadline = time.monotonic() + 10.0
            while time.monotonic() < member_deadline:
                states = [m.get("state")
                          for m in fe.membership().values()]
                if "dead" in states:
                    break
                time.sleep(0.2)
            assert "dead" in [m.get("state")
                              for m in fe.membership().values()]
            assert smetrics.serving_totals().get("failovers", 0) >= 1
        finally:
            if fe is not None:
                fe.stop()
            for p in procs:
                try:
                    p.kill()
                except OSError:
                    pass
            ps.stop()


# ------------------------------------------------------ frontend mechanics
class TestFrontend:
    def test_round_robin_spreads_load(self, devices8, rng):
        ps, cfg, d, n = start_ps(devices8)
        rep_a = rep_b = fe = None
        try:
            rep_a = ModelReplica("127.0.0.1", ps.port, rid=0,
                                 host="127.0.0.1",
                                 refresh_interval_s=999.0).start()
            rep_b = ModelReplica("127.0.0.1", ps.port, rid=1,
                                 host="127.0.0.1",
                                 refresh_interval_s=999.0).start()
            assert rep_a.refresh_once() and rep_b.refresh_once()
            fe = ServingFrontend(
                [("127.0.0.1", rep_a.port), ("127.0.0.1", rep_b.port)],
                deadline_s=2.0,
            ).start()
            X = rng.normal(size=(2, d)).astype(np.float32)
            seen = [fe.predict_ex(X)[1]["endpoint"] for _ in range(6)]
            assert len(set(seen)) == 2  # both replicas take traffic
        finally:
            if fe is not None:
                fe.stop()
            for r in (rep_a, rep_b):
                if r is not None:
                    r.stop()
            ps.stop()

    def test_reregistration_is_idempotent(self):
        fe = ServingFrontend(deadline_s=0.1)
        try:
            a = fe.add_replica("127.0.0.1", 12345)
            b = fe.add_replica("127.0.0.1", 12345)
            assert a == b and fe.replica_count() == 1
            assert smetrics.serving_totals()["replicas_registered"] == 1
        finally:
            fe.stop()

    def test_dead_slot_reclaimed_at_capacity(self):
        """Replica churn hands every replacement a fresh endpoint: at
        capacity a DEAD slot is reclaimed, never a permanent refusal."""
        fe = ServingFrontend(deadline_s=0.1, max_replicas=2,
                             dead_after_s=0.15)
        try:
            # pid 2^22+1 is beyond pid_max on this box: the local-pid
            # probe declares the slot's proc exited on the first scan
            fe.add_replica("127.0.0.1", 11111, pid=4_194_305,
                           hostname=socket_mod.gethostname())
            fe.add_replica("127.0.0.1", 11112)
            with pytest.raises(ValueError):
                fe.add_replica("127.0.0.1", 11113)  # full, nobody dead
            time.sleep(0.25)  # both slots silent past dead_after
            fe.supervisor.check_once()
            idx = fe.add_replica("127.0.0.1", 11113)
            assert idx in (0, 1)
            assert "127.0.0.1:11113" in fe.membership()
            assert fe.replica_count() == 2
        finally:
            fe.stop()

    def test_replica_rehello_survives_frontend_restart(self, devices8):
        """HELLO is a heartbeat loop: a restarted frontend (same
        address, as behind a k8s Service) rebuilds its rotation from the
        replicas' next beats -- no replica restart required."""
        ps, cfg, d, n = start_ps(devices8)
        rep = fe = fe2 = None
        try:
            fe = ServingFrontend(deadline_s=1.0).serve(port=0,
                                                       host="127.0.0.1")
            port0 = fe.port
            rep = serve_replica(f"127.0.0.1:{ps.port}", rid=0,
                                host="127.0.0.1",
                                frontend=f"127.0.0.1:{port0}",
                                announce=lambda *a, **k: None,
                                hello_interval_s=0.1)
            deadline = time.monotonic() + 10.0
            while fe.replica_count() < 1:
                assert time.monotonic() < deadline
                time.sleep(0.05)
            fe.stop()
            # rebind the same address (a restarting daemon retries while
            # the old instance's sockets drain)
            deadline = time.monotonic() + 10.0
            while True:
                try:
                    fe2 = ServingFrontend(deadline_s=1.0).serve(
                        port=port0, host="127.0.0.1"
                    )
                    break
                except OSError:
                    assert time.monotonic() < deadline
                    time.sleep(0.1)
            assert fe2.replica_count() == 0  # fresh process state
            deadline = time.monotonic() + 10.0
            while fe2.replica_count() < 1:
                assert time.monotonic() < deadline, \
                    "replica never re-registered with restarted frontend"
                time.sleep(0.05)
        finally:
            for f in (fe, fe2):
                if f is not None:
                    f.stop()
            if rep is not None:
                rep.stop()
            ps.stop()

    def test_frontdoor_hello_and_predict_proxy(self, devices8, rng):
        """The daemon face: a replica HELLOs the front door in, a client
        PREDICT frame is proxied through the rotation."""
        ps, cfg, d, n = start_ps(devices8)
        rep = fe = None
        try:
            rep = ModelReplica("127.0.0.1", ps.port, rid=0,
                               host="127.0.0.1",
                               refresh_interval_s=999.0).start()
            assert rep.refresh_once()
            fe = ServingFrontend(deadline_s=2.0).serve(port=0,
                                                       host="127.0.0.1")
            sock = _frame.connect(("127.0.0.1", fe.port))
            _frame.send_msg(sock, {"op": "HELLO", "replica": True,
                                   "proc": "t-rep", "port": rep.port,
                                   "host": socket_mod.gethostname(),
                                   "pid": os.getpid()})
            hdr, _ = _frame.recv_msg(sock)
            assert hdr["op"] == "WELCOME"
            X = rng.normal(size=(3, d)).astype(np.float32)
            _frame.send_msg(sock, {"op": "PREDICT", "n": 3}, X.tobytes())
            hdr, payload = _frame.recv_msg(sock)
            assert hdr["op"] == "PREDICTION"
            y = np.frombuffer(payload, np.float32)
            np.testing.assert_allclose(
                y, X @ np.asarray(rep._served.w_host), rtol=1e-5,
                atol=1e-5,
            )
            sock.close()
        finally:
            if fe is not None:
                fe.stop()
            if rep is not None:
                rep.stop()
            ps.stop()


# ---------------------------------------------------- counters (satellite)
class TestServingCounters:
    def test_reset_totals_zeroes_serving(self):
        smetrics.bump("predicts", 3)
        smetrics.observe_predict("x:1", 1.0, 2, 30.0, 5)
        assert smetrics.serving_totals()["predicts"] == 4
        reset_totals()
        assert smetrics.serving_totals() == {}
        assert smetrics.serving_snapshot()["predict_ms"] == {"count": 0}

    def test_live_ui_second_run_starts_at_zero(self):
        """The PR 3 bug class, serving edition: a listener built for a
        second run must not inherit the first run's QPS/lag totals."""
        smetrics.bump("predicts", 10)
        smetrics.bump("failovers", 2)
        listener = LiveStateListener(2)  # second run starts HERE
        snap = listener.snapshot()["serving"]
        assert snap["predicts"] == 0 and snap["failovers"] == 0
        smetrics.bump("predicts", 5)
        assert listener.snapshot()["serving"]["predicts"] == 5
        # the raw detail view still carries the process totals
        assert listener.snapshot()["serving"]["detail"]["predicts"] == 15
