"""Cluster soak (VERDICT r4 #5): one run composing every fault-tolerance
mechanism the deploy + DCN layers claim.

Parity bar: ``core/src/test/scala/org/apache/spark/DistributedSuite.scala:38``
(kill-things-mid-job integration) + ``deploy/master/Master.scala:41`` (HA).
The composition: HA master pair + 3 worker daemons schedule a DCN **asgd**
app AND a DCN **asaga** app concurrently (each PS + 2 gradient workers,
checkpointing, supervised); mid-run the test

1. SIGKILLs the active master  -> the standby wins the flock lease and
   serves with apps still RUNNING,
2. kill -9s the asgd PS        -> its worker daemon supervises it back up
   on the same coordinator port; it resumes from its checkpoint and the
   gradient workers reconnect,
3. kill -9s an asaga gradient-worker executor -> supervised relaunch
   rejoins the run.

Both apps must reach FINISHED with every (final) exit 0, the asgd summary
must prove the checkpoint resume, and both objectives must converge.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from asyncframework_tpu.deploy import Master, Worker, wait_app
from asyncframework_tpu.deploy.client import _client as client_for


def _wait(pred, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise AssertionError(f"timed out waiting for {what}")


def _find_proc(workers, app_id, proc_id):
    for w in workers:
        with w._procs_lock:
            for p in w._procs.get(app_id, ()):
                if getattr(p, "async_proc_id", None) == proc_id:
                    return p
    return None


@pytest.mark.slow
@pytest.mark.soak
class TestClusterSoak:
    def test_soak_master_failover_ps_kill9_worker_kill9(
        self, tmp_path, capsys
    ):
        ck = str(tmp_path / "ck")
        # active master: real OS process so SIGKILL exercises the kernel's
        # flock release
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
            _wait(lambda: self._master_up(a_host, int(a_port)), 30,
                  "active master serving")

            standby = Master(persistence_dir=str(tmp_path),
                             worker_timeout_s=2.0, ha=True).start()
            workers = [
                Worker(a_host, int(a_port), worker_id=f"w{i}",
                       heartbeat_s=0.3,
                       standby_masters=[f"127.0.0.1:{standby.port}"],
                       launch_env_extra={"JAX_PLATFORMS": "cpu"}).start()
                for i in range(3)
            ]
            ha_addr = f"{active_addr},127.0.0.1:{standby.port}"
            cl = client_for(ha_addr)

            # two concurrent DCN apps, supervised + checkpointing: budgets
            # sized for ~90s of runway so all three faults land mid-run
            asgd_id = cl.submit(
                ["--quiet", "asgd", "synthetic", "synthetic",
                 "16", "4096", "8", "60000", "0.5", "2147483647", "0.3",
                 "0.5", "200", "0", "42", "--checkpoint-dir", ck],
                num_processes=3, supervise=True,
            )
            asaga_id = cl.submit(
                ["--quiet", "asaga", "synthetic", "synthetic",
                 "16", "4096", "8", "60000", "0.35", "2147483647", "0.3",
                 "0.5", "200", "0", "42", "--checkpoint-dir", ck],
                num_processes=3, supervise=True,
            )
            for app in (asgd_id, asaga_id):
                _wait(lambda a=app: cl.status(a)["state"] == "RUNNING",
                      60, f"{app} RUNNING")

            # fault 1 precondition: the asgd PS has checkpointed at least
            # once (so the kill -9 resume has something to resume from)
            ck_file = os.path.join(ck, "ps_asgd.npz")
            _wait(lambda: os.path.exists(ck_file), 120,
                  "first asgd PS checkpoint")

            # ---- fault 1: SIGKILL the active master
            active.send_signal(signal.SIGKILL)
            active.wait(timeout=10)
            _wait(lambda: standby.active, 30, "standby lease takeover")
            assert cl.status(asgd_id)["state"] == "RUNNING"
            assert cl.status(asaga_id)["state"] == "RUNNING"

            # ---- fault 2: kill -9 the asgd PARAMETER SERVER executor
            ps_proc = _find_proc(workers, asgd_id, 0)
            assert ps_proc is not None, "asgd PS executor not found"
            os.kill(ps_proc.pid, signal.SIGKILL)

            # ---- fault 3: kill -9 an asaga GRADIENT WORKER executor
            gw_proc = _find_proc(workers, asaga_id, 1)
            assert gw_proc is not None, "asaga worker executor not found"
            os.kill(gw_proc.pid, signal.SIGKILL)

            # supervision must bring replacements up (same proc ids)
            _wait(lambda: (p := _find_proc(workers, asgd_id, 0)) is not None
                  and p is not ps_proc, 60, "asgd PS supervised relaunch")
            _wait(lambda: (p := _find_proc(workers, asaga_id, 1)) is not None
                  and p is not gw_proc, 60, "asaga worker supervised relaunch")

            # ---- both apps run to FINISHED through all three faults
            st_asgd = wait_app(ha_addr, asgd_id, timeout_s=600.0)
            st_asaga = wait_app(ha_addr, asaga_id, timeout_s=600.0)
            assert st_asgd["state"] == "FINISHED", st_asgd
            assert st_asaga["state"] == "FINISHED", st_asaga
            assert len(st_asgd["exits"]) == 3
            assert len(st_asaga["exits"]) == 3
            assert all(rc == 0 for rc in st_asgd["exits"].values())
            assert all(rc == 0 for rc in st_asaga["exits"].values())

            # give the exit watchers a beat to flush proc-0 stdout
            time.sleep(1.0)
        finally:
            for w in workers:
                w.stop()
            if standby is not None:
                standby.stop()
            if active.poll() is None:
                active.kill()

        # ---- convergence + resume evidence from the PS summaries
        out = capsys.readouterr().out
        summaries = {}
        for ln in out.splitlines():
            if ln.startswith("{"):
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue
                if "driver" in rec:
                    summaries[rec["driver"]] = rec
        asgd = summaries.get("asgd-dcn-ps")
        asaga = summaries.get("asaga-dcn-ps")
        assert asgd is not None and asaga is not None, sorted(summaries)
        assert asgd["done"] is True and asaga["done"] is True
        assert asgd["accepted"] == 60000 and asaga["accepted"] == 60000
        # the killed PS provably resumed from its checkpoint
        assert asgd["resumed_from"] is not None and asgd["resumed_from"] >= 200
        # both objectives converged (synthetic d=16 starts near 1.0)
        assert asgd["final_objective"] is not None
        assert asgd["final_objective"] < 0.05, asgd
        assert asaga["final_objective"] is not None
        assert asaga["final_objective"] < 0.05, asaga

    @staticmethod
    def _master_up(host, port) -> bool:
        from asyncframework_tpu.deploy.client import MasterClient

        try:
            MasterClient(host, port).workers()
            return True
        except (ConnectionError, OSError):
            return False
