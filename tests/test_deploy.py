"""Standalone Master/Worker deploy layer (SURVEY §2.4 "Deploy").

Parity coverage: worker registration + heartbeat liveness
(Master.scala:41), executor launch + exit reporting (Worker.scala:43),
app lifecycle states, submission client (StandaloneAppClient.scala:44),
worker-loss detection, and master-restart recovery through the
file persistence engine (ZooKeeperPersistenceEngine.scala:34 role).
"""

import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from asyncframework_tpu.deploy import Master, MasterClient, Worker, wait_app

_REPO = Path(__file__).parent.parent
_SPMD_CPU_REASON = None  # session cache: None = not probed, '' = capable


def cpu_spmd_capability() -> str:
    """Probed capability (ISSUE 12 deflake): can THIS rig's jax run a
    2-process SPMD computation on the CPU backend?  A jax build without
    gloo-capable CPU collectives raises "Multiprocess computations
    aren't implemented on the CPU backend" -- the same class as the
    documented tests/test_multihost.py baseline failures, but here it
    surfaced as a flaky-looking master-submit failure (supervised
    executor restarts hid the real error).  The probe runs the repo's
    own bring-up (multihost.ensure_initialized + sync_hosts, a
    cross-process pmap psum) in two real subprocesses once per session.
    Returns '' when capable, else the reason to skip with."""
    global _SPMD_CPU_REASON
    if _SPMD_CPU_REASON is not None:
        return _SPMD_CPU_REASON
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = (
        "import sys\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from asyncframework_tpu.parallel import multihost\n"
        "multihost.ensure_initialized(\n"
        "    coordinator_address='127.0.0.1:%d',\n"
        "    num_processes=2, process_id=int(sys.argv[1]))\n"
        "multihost.sync_hosts('probe')\n"
        "print('OK')\n" % port
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(_REPO)
    procs = [subprocess.Popen([sys.executable, "-c", script, str(i)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env=env, cwd=str(_REPO))
             for i in range(2)]
    try:
        outs = [p.communicate(timeout=120) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        _SPMD_CPU_REASON = "2-process CPU SPMD probe timed out"
        return _SPMD_CPU_REASON
    if all(p.returncode == 0 for p in procs):
        _SPMD_CPU_REASON = ""
    else:
        err = next((e for (_o, e), p in zip(outs, procs)
                    if p.returncode != 0), "")
        tail = err.strip().splitlines()[-1] if err.strip() else "rc != 0"
        _SPMD_CPU_REASON = f"CPU backend lacks multiprocess SPMD: {tail}"
    return _SPMD_CPU_REASON


@pytest.fixture()
def rig(tmp_path):
    m = Master(persistence_dir=str(tmp_path), worker_timeout_s=2.0).start()
    workers = [
        Worker("127.0.0.1", m.port, worker_id=f"w{i}",
               heartbeat_s=0.3,
               launch_env_extra={"JAX_PLATFORMS": "cpu"}).start()
        for i in range(2)
    ]
    yield m, workers
    for w in workers:
        w.stop()
    m.stop()


class TestRegistryAndLiveness:
    def test_register_and_list(self, rig):
        m, _ = rig
        cl = MasterClient("127.0.0.1", m.port)
        ws = cl.workers()
        assert set(ws) == {"w0", "w1"}
        assert all(w["alive"] for w in ws.values())

    def test_worker_loss_detected(self, rig):
        m, workers = rig
        workers[1].stop()
        deadline = time.monotonic() + 10
        cl = MasterClient("127.0.0.1", m.port)
        while time.monotonic() < deadline:
            ws = cl.workers()
            if not ws["w1"]["alive"]:
                break
            time.sleep(0.2)
        assert not cl.workers()["w1"]["alive"]
        assert cl.workers()["w0"]["alive"]

    def test_submit_with_no_workers_rejected(self, tmp_path):
        m = Master(persistence_dir=str(tmp_path)).start()
        try:
            cl = MasterClient("127.0.0.1", m.port)
            with pytest.raises(RuntimeError, match="no alive workers"):
                cl.submit(["--quiet", "asgd"], 2)
        finally:
            m.stop()


class TestAppLifecycle:
    def test_spmd_app_runs_to_finished(self, rig):
        """Capability-gated (ISSUE 13 tier-1 deflake): the 2-process
        sgd-mllib recipe is an SPMD program over a cross-process mesh --
        the same jax-build capability the documented test_multihost
        baseline class needs.  The session-cached probe runs the real
        bring-up once; incapable rigs SKIP with the probed reason
        instead of carrying a permanent baseline failure."""
        reason = cpu_spmd_capability()
        if reason:
            pytest.skip(reason)
        m, _ = rig
        cl = MasterClient("127.0.0.1", m.port)
        # a 2-process SPMD recipe placed by the master: coordinator env is
        # assigned by the scheduler, processes join over jax.distributed
        app_id = cl.submit(
            ["--quiet", "sgd-mllib", "synthetic", "synthetic",
             "16", "512", "4", "20", "1.0", "0", "0.5", "0.5",
             "10", "0", "42"],
            num_processes=2,
        )
        st = wait_app(f"127.0.0.1:{m.port}", app_id, timeout_s=240.0)
        assert st["state"] == "FINISHED", st
        assert len(st["exits"]) == 2
        assert all(rc == 0 for rc in st["exits"].values())

    def test_asgd_ps_app_through_master(self, rig):
        """The full standalone-cluster story: the master schedules a
        3-process DCN asgd app (PS + 2 gradient-pushing workers) across
        its registered worker daemons, and it runs to FINISHED."""
        m, _ = rig
        cl = MasterClient("127.0.0.1", m.port)
        app_id = cl.submit(
            ["--quiet", "asgd", "synthetic", "synthetic",
             "16", "2048", "8", "200", "1.0", "2147483647", "0.3",
             "0.5", "50", "0", "42"],
            num_processes=3,
        )
        st = wait_app(f"127.0.0.1:{m.port}", app_id, timeout_s=240.0)
        assert st["state"] == "FINISHED", st
        assert len(st["exits"]) == 3

    def test_failed_app_reported(self, rig):
        m, _ = rig
        cl = MasterClient("127.0.0.1", m.port)
        app_id = cl.submit(["definitely-not-a-driver"], num_processes=1)
        st = wait_app(f"127.0.0.1:{m.port}", app_id, timeout_s=120.0)
        assert st["state"] == "FAILED"

    def test_kill_app_reclaims_executors(self, rig):
        """KILL_APP terminates the app's executor processes on every
        worker and the app lands in KILLED (not FAILED: the terminations'
        nonzero exits must not relabel it)."""
        m, _ = rig
        cl = MasterClient("127.0.0.1", m.port)
        # 2-process DCN asgd with a huge iteration budget: runs for minutes
        # unless killed
        app_id = cl.submit(
            ["--quiet", "asgd", "synthetic", "synthetic",
             "16", "2048", "8", "5000000", "0.01", "2147483647", "0.3",
             "0.5", "1000", "0", "42"],
            num_processes=2,
        )
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if cl.status(app_id)["state"] == "RUNNING":
                break
            time.sleep(0.2)
        time.sleep(2.0)  # let the executors get properly underway
        reply = cl.kill(app_id)
        assert reply["op"] == "KILLED"
        st = wait_app(f"127.0.0.1:{m.port}", app_id, timeout_s=60.0)
        assert st["state"] == "KILLED"
        # exit reports land asynchronously after the terminations
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = cl.status(app_id)
            if len(st["exits"]) == 2:
                break
            time.sleep(0.2)
        assert len(st["exits"]) == 2  # both executors reported their death
        assert st["state"] == "KILLED"  # nonzero exits did not relabel it


class TestSubmitCLIMasterMode:
    def test_cli_master_submit_waits_to_finished(self, rig, capsys):
        """spark-submit --master parity: the SAME CLI surface ships the
        recipe to the daemon master, waits, and exits 0 on FINISHED.

        Capability-gated (ISSUE 12 deflake): the 2-process sgd-mllib
        recipe is an SPMD program over a cross-process mesh, which this
        rig's CPU backend may not implement (the documented
        test_multihost baseline class).  The probe runs the real
        bring-up once per session; on incapable rigs this SKIPS with
        the probed reason instead of failing as a pseudo-flake."""
        import json as _json

        reason = cpu_spmd_capability()
        if reason:
            pytest.skip(reason)

        from asyncframework_tpu.cli import main as cli_main

        m, _ = rig
        rc = cli_main([
            "--master", f"127.0.0.1:{m.port}", "--processes", "2",
            "--supervise", "--quiet",
            "sgd-mllib", "synthetic", "synthetic",
            "16", "512", "4", "20", "1.0", "0", "0.5", "0.5",
            "10", "0", "42",
        ])
        assert rc == 0
        lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.startswith("{")]
        sub = _json.loads(lines[0])
        fin = _json.loads(lines[-1])
        assert sub["supervise"] is True and sub["num_processes"] == 2
        assert fin["state"] == "FINISHED"
        assert all(rc == 0 for rc in fin["exits"].values())
        # the master recorded the supervise flag on the app
        assert m.apps[sub["app_id"]]["supervise"] is True


class TestMasterUI:
    def test_status_page_and_api(self, tmp_path):
        import json as _json
        import urllib.request

        from asyncframework_tpu.deploy import Master, Worker

        m = Master(persistence_dir=str(tmp_path), ui_port=0).start()
        w = Worker("127.0.0.1", m.port, worker_id="w0",
                   heartbeat_s=0.3).start()
        try:
            base = f"http://127.0.0.1:{m._ui.port}"
            with urllib.request.urlopen(base + "/api/status", timeout=5) as r:
                st = _json.loads(r.read())
            assert st["active"] is True
            assert "w0" in st["workers"]
            with urllib.request.urlopen(base + "/", timeout=5) as r:
                html = r.read().decode()
            assert "async master" in html
        finally:
            w.stop()
            m.stop()

    def test_ui_host_is_configurable(self, tmp_path):
        """ISSUE 1 satellite: the UI used to hard-bind 127.0.0.1 -- a k8s
        Service could never route to it.  ``ui_host`` must reach the HTTP
        server's actual bind address."""
        from asyncframework_tpu.deploy import Master

        m = Master(persistence_dir=str(tmp_path), ui_port=0,
                   ui_host="0.0.0.0").start()
        try:
            assert m._ui._httpd.server_address[0] == "0.0.0.0"
            # still reachable over loopback (0.0.0.0 covers it)
            import urllib.request

            with urllib.request.urlopen(
                f"http://127.0.0.1:{m._ui.port}/api/status", timeout=5
            ) as r:
                assert r.status == 200
        finally:
            m.stop()


class TestMasterRecovery:
    def test_state_survives_master_restart(self, tmp_path):
        m = Master(persistence_dir=str(tmp_path), worker_timeout_s=2.0).start()
        w = Worker("127.0.0.1", m.port, worker_id="w0",
                   heartbeat_s=0.3).start()
        cl = MasterClient("127.0.0.1", m.port)
        assert "w0" in cl.workers()
        port = m.port
        m.stop()
        time.sleep(0.2)
        # new master on the SAME port recovers the registry from disk;
        # the worker's heartbeat (or RECONNECT reply) re-validates it.
        # The old listener can take a beat to release the port under a
        # loaded host -- retry the rebind briefly (real restarts do too).
        deadline = time.monotonic() + 10
        while True:
            try:
                m2 = Master(port=port, persistence_dir=str(tmp_path),
                            worker_timeout_s=2.0).start()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.3)
        try:
            cl2 = MasterClient("127.0.0.1", m2.port)
            ws = cl2.workers()
            assert "w0" in ws  # recovered from the persistence engine
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if cl2.workers()["w0"]["alive"]:
                    break
                time.sleep(0.2)
            assert cl2.workers()["w0"]["alive"]  # re-validated by heartbeat
        finally:
            w.stop()
            m2.stop()

    def test_running_apps_marked_lost_on_recovery(self, tmp_path):
        state = {
            "workers": {},
            "apps": {"app-0001": {
                "argv": ["x"], "env": {}, "num_processes": 2,
                "state": "RUNNING",
            }},
            "app_seq": 1,
        }
        with open(f"{tmp_path}/master-state.json", "w") as f:
            json.dump(state, f)
        m2 = Master(persistence_dir=str(tmp_path)).start()
        try:
            cl = MasterClient("127.0.0.1", m2.port)
            assert cl.status("app-0001")["state"] == "LOST"
        finally:
            m2.stop()


@pytest.mark.slow
class TestStandbyFailover:
    def test_kill_active_master_standby_takes_over_app_finishes(
        self, tmp_path
    ):
        """VERDICT r3 item 9, the exact done-criterion: kill the active
        master mid-app; the standby wins the flock lease, recovers state
        from the shared persistence dir (RUNNING stays RUNNING -- the
        executors belong to live worker daemons), workers rotate their
        heartbeats to it, and the app runs to FINISHED."""
        import signal
        import subprocess
        import sys

        # active master: a real OS process, so SIGKILL exercises the
        # kernel's automatic flock release (the lease's whole point)
        active = subprocess.Popen(
            [sys.executable, "-m", "asyncframework_tpu.deploy.master",
             "--port", "0", "--persistence-dir", str(tmp_path), "--ha"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        standby = None
        workers = []
        try:
            line = active.stdout.readline()
            active_addr = line.split()[-2 if "(ha)" in line else -1]
            a_host, a_port = active_addr.rsplit(":", 1)

            from asyncframework_tpu.deploy.client import (
                MasterClient as MC,
                _client as _client_for,
            )

            # wait for the active master to win the lease and serve
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    MC(a_host, int(a_port)).workers()
                    break
                except (ConnectionError, OSError):
                    time.sleep(0.1)

            standby = Master(persistence_dir=str(tmp_path),
                             worker_timeout_s=2.0, ha=True).start()
            # standby must refuse service while the active master lives
            with pytest.raises(ConnectionError):
                MC("127.0.0.1", standby.port).workers()

            workers = [
                Worker(a_host, int(a_port), worker_id=f"w{i}",
                       heartbeat_s=0.3,
                       standby_masters=[f"127.0.0.1:{standby.port}"],
                       launch_env_extra={"JAX_PLATFORMS": "cpu"}).start()
                for i in range(2)
            ]
            ha_addr = f"{active_addr},127.0.0.1:{standby.port}"
            cl = _client_for(ha_addr)
            # long enough to straddle the failover: 2-process DCN asgd
            app_id = cl.submit(
                ["--quiet", "asgd", "synthetic", "synthetic",
                 "16", "2048", "8", "20000", "0.05", "2147483647", "0.3",
                 "0.5", "1000", "0", "42"],
                num_processes=2,
            )
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if cl.status(app_id)["state"] == "RUNNING":
                    break
                time.sleep(0.2)
            assert cl.status(app_id)["state"] == "RUNNING"
            time.sleep(1.0)  # executors underway

            active.send_signal(signal.SIGKILL)
            active.wait(timeout=10)

            # the standby must take over and report the app still RUNNING
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not standby.active:
                time.sleep(0.1)
            assert standby.active, "standby never won the lease"
            assert cl.status(app_id)["state"] == "RUNNING"

            st = wait_app(ha_addr, app_id, timeout_s=240.0)
            assert st["state"] == "FINISHED", st
            assert len(st["exits"]) == 2
            assert all(rc == 0 for rc in st["exits"].values())
            # workers rotated: the standby sees them alive
            ws = cl.workers()
            assert set(ws) == {"w0", "w1"}
        finally:
            for w in workers:
                w.stop()
            if standby is not None:
                standby.stop()
            if active.poll() is None:
                active.kill()


class TestExitPersistence:
    def test_partial_exits_survive_recovery(self, tmp_path):
        """An executor exit ACKed before a master death must be found on
        disk by the successor -- the worker never resends it."""
        m = Master(persistence_dir=str(tmp_path)).start()
        try:
            with m._lock:
                m.apps["app-0001"] = {
                    "argv": ["x"], "env": {}, "num_processes": 2,
                    "state": "RUNNING", "assignments": [], "exits": {},
                }
                m._persist()
            reply = m._handle({"op": "EXECUTOR_EXIT", "worker_id": "w0",
                               "app_id": "app-0001", "proc_id": 0,
                               "returncode": 0})
            assert reply["op"] == "ACK"
        finally:
            m.stop()
        m2 = Master(persistence_dir=str(tmp_path)).start()
        try:
            # cold restart marks it LOST but the partial exit is retained;
            # the second exit then completes the count
            assert m2.apps["app-0001"]["exits"] == {"0": 0}
        finally:
            m2.stop()


class TestSingleProcessApp:
    def test_one_process_asgd_runs_plain(self, rig):
        """A 1-process asgd placement gets coordinator env from the master
        but must run as a normal single-process solver (DCN mode needs
        peers)."""
        m, _ = rig
        cl = MasterClient("127.0.0.1", m.port)
        app_id = cl.submit(
            ["--quiet", "asgd", "synthetic", "synthetic",
             "16", "1024", "4", "100", "1.0", "2147483647", "0.3",
             "0.5", "50", "0", "42"],
            num_processes=1,
        )
        st = wait_app(f"127.0.0.1:{m.port}", app_id, timeout_s=240.0)
        assert st["state"] == "FINISHED", st
