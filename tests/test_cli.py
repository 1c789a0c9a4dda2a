"""CLI tests: the 13-positional-arg submit surface.

Parity: the reference's drivers are launched via spark-submit with 13
positional args (``README.md:46``); recipes must be reusable verbatim here
modulo the jar/class prefix.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from asyncframework_tpu import cli


def recipe(driver, path="synthetic", file="x", d=16, N=512, parts=8,
           iters=40, gamma=1.0, taw=2**31 - 1, b=0.3, bucket=0.5,
           pfreq=10, coeff=0.0, seed=42, extra=()):
    return [driver, path, file, str(d), str(N), str(parts), str(iters),
            str(gamma), str(taw), str(b), str(bucket), str(pfreq),
            str(coeff), str(seed), *extra]


def run_cli(capsys, argv):
    rc = cli.main(argv)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    # every summary names the device it ran on, so that a run can always
    # be told apart from one on another platform
    assert summary["platform"] == "cpu" and summary["device_kind"] == "cpu"
    assert summary["n_devices"] == 8
    assert "assigned" in summary  # what a launcher decided (none here)
    return summary, out[:-1]


class TestDrivers:
    @pytest.mark.parametrize("name,expect,accepted", [
        ("SparkASGDThread", "asgd", 30),        # async: accepted updates
        ("asgd-sync", "asgd-sync", 30 * 8),     # sync: rounds x workers
        ("SparkASAGAThread", "asaga", 30),
        ("SparkASAGASync", "asaga-sync", 30 * 8),
    ])
    def test_async_drivers_run(self, capsys, name, expect, accepted):
        summary, traj_lines = run_cli(
            capsys, recipe(name, iters=30, extra=("--quiet",))
        )
        assert summary["driver"] == expect
        assert summary["accepted"] == accepted
        # plumbing test, not a convergence test (those live in test_solvers)
        assert np.isfinite(summary["final_objective"])
        assert not traj_lines  # --quiet

    @pytest.mark.parametrize("name,gamma", [
        ("asgd-fused", 1.0), ("asaga-fused", 0.3),
    ])
    def test_fused_drivers_run(self, capsys, name, gamma):
        summary, _ = run_cli(
            capsys, recipe(name, iters=32, gamma=gamma, extra=("--quiet",))
        )
        assert summary["driver"] == name
        assert summary["accepted"] >= 32
        assert summary["dropped"] == 0
        assert np.isfinite(summary["final_objective"])

    def test_fused_rejects_checkpoint_dir(self, tmp_path):
        with pytest.raises(SystemExit, match="checkpoint"):
            cli.main(recipe("asgd-fused", iters=5,
                            extra=("--checkpoint-dir", str(tmp_path))))

    def test_sgd_mllib_driver(self, capsys, tmp_path):
        # mllib baseline needs host arrays -> write a real libsvm file
        rs = np.random.default_rng(0)
        X = rs.normal(size=(256, 8)).astype(np.float32)
        w = rs.normal(size=(8,)).astype(np.float32)
        y = X @ w
        f = tmp_path / "tiny.libsvm"
        with open(f, "w") as fh:
            for i in range(256):
                feats = " ".join(f"{j+1}:{X[i, j]:.6f}" for j in range(8))
                fh.write(f"{y[i]:.6f} {feats}\n")
        summary, _ = run_cli(
            capsys,
            recipe("SparkSGDMLLIB", path=str(tmp_path), file="tiny.libsvm",
                   d=8, N=256, parts=8, iters=50, gamma=0.5,
                   extra=("--quiet",)),
        )
        assert summary["driver"] == "sgd-mllib"
        assert summary["iterations"] == 50

    def test_trajectory_printed_and_written(self, capsys, tmp_path):
        out_csv = tmp_path / "traj.csv"
        summary, traj_lines = run_cli(
            capsys,
            recipe("asgd", iters=20, extra=("--output", str(out_csv))),
        )
        assert traj_lines and traj_lines[0].startswith("(")
        lines = out_csv.read_text().splitlines()
        assert lines[0] == "ms,objective"
        assert len(lines) - 1 == len(traj_lines)

    def test_unknown_driver_rejected(self):
        with pytest.raises(SystemExit):
            cli.main(recipe("SparkNotADriver"))

    def test_missing_file_rejected(self, tmp_path):
        with pytest.raises(SystemExit, match="no such data file"):
            cli.main(recipe("asgd", path=str(tmp_path), file="nope.libsvm"))

    def test_conf_overlay(self, capsys):
        summary, _ = run_cli(
            capsys,
            recipe("asgd", iters=20, taw=0,
                   extra=("--quiet", "--conf", "async.taw=2147483647")),
        )
        # overlay lifted taw back to infinite: nothing dropped
        assert summary["dropped"] == 0


class TestObservabilityFlags:
    def test_event_log_and_report(self, capsys, tmp_path):
        log = tmp_path / "run.jsonl"
        report = tmp_path / "run.html"
        summary, _ = run_cli(capsys, recipe(
            "asgd", iters=30,
            extra=("--quiet", "--event-log", str(log), "--report", str(report)),
        ))
        assert summary["accepted"] == 30
        assert summary["report"] == str(report)
        assert log.exists()
        html = report.read_text()
        assert "Summary" in html and "Objective" in html

    def test_report_requires_event_log(self, tmp_path):
        with pytest.raises(SystemExit):
            cli.main(recipe("asgd", iters=5,
                            extra=("--report", str(tmp_path / "r.html"))))

    def test_stale_read_flag(self, capsys):
        summary, _ = run_cli(capsys, recipe(
            "asgd", iters=30, extra=("--quiet", "--stale-read", "2"),
        ))
        assert summary["accepted"] == 30

    def test_stale_read_rejected_for_sync(self):
        with pytest.raises(SystemExit):
            cli.main(recipe("asgd-sync", iters=5, extra=("--stale-read", "1")))

    def test_speculation_flag_smoke(self, capsys):
        summary, _ = run_cli(capsys, recipe(
            "asgd-sync", iters=10, extra=("--quiet", "--speculation"),
        ))
        assert summary["accepted"] == 10 * 8


class TestIncompleteRunsExitNonZero:
    """A run that did not do what was asked must not exit 0."""

    def test_async_run_cut_short_by_run_timeout(self, capsys, monkeypatch):
        """ASAGA keeps the reference's filter ``k - staleness <= taw``, so
        with a finite taw nothing is accepted once k passes it: the
        submitter loop ends at run_timeout_s with a NORMAL TrainResult
        (accepted < requested).  The summary still prints; the exit code
        says the run is incomplete."""
        import functools

        import asyncframework_tpu.solvers as solvers

        monkeypatch.setattr(
            solvers, "SolverConfig",
            functools.partial(solvers.SolverConfig, run_timeout_s=1.0))
        rc = cli.main(recipe("asaga", iters=400, taw=16,
                             extra=("--quiet",)))
        captured = capsys.readouterr()
        summary = json.loads(captured.out.strip().splitlines()[-1])
        assert summary["requested"] == 400
        assert summary["accepted"] < 400
        assert rc != 0
        assert "run incomplete" in captured.err

    def test_dcn_server_that_never_reports_done(self, capsys, monkeypatch):
        from asyncframework_tpu.net.frame import free_port
        from asyncframework_tpu.parallel import ps_dcn

        monkeypatch.setenv("ASYNCTPU_COORDINATOR",
                           f"127.0.0.1:{free_port()}")
        monkeypatch.setenv("ASYNCTPU_NUM_PROCESSES", "2")
        monkeypatch.setenv("ASYNCTPU_PROCESS_ID", "0")
        # no worker process ever connects: the wait times out
        monkeypatch.setattr(
            ps_dcn.ParameterServer, "wait_done",
            lambda self, timeout_s, **kw: ps_dcn.WaitDone(
                False, "no worker contacted the PS"))
        monkeypatch.setattr(ps_dcn.ParameterServer, "collect_eval",
                            lambda self, n, timeout_s: None)
        try:
            rc = cli.main(recipe("asgd", iters=20, extra=("--quiet",)))
        finally:
            from asyncframework_tpu.conf import set_global_conf

            set_global_conf(None)  # run_async_cluster's cluster defaults
        captured = capsys.readouterr()
        summary = json.loads(captured.out.strip().splitlines()[-1])
        assert summary["driver"] == "asgd-dcn-ps"
        assert summary["done"] is False and summary["accepted"] == 0
        assert summary["platform"] == "cpu"
        assert rc != 0


class TestLateBootingWorkerProcess:
    def test_server_outlives_a_worker_still_booting_at_done(self):
        """On a cold chip a worker process needs tens of seconds to reach
        its device, generate data and compile, with seconds of skew
        between processes; a short run can be DONE before the last one
        has said HELLO.  The server must still be there to tell it DONE
        (a worker that finds the server gone retries HELLO for the whole
        run timeout).  Here process 2 is started only after process 1 --
        and so the run -- has finished."""
        from asyncframework_tpu.net.frame import free_port

        repo = Path(__file__).parent.parent
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(repo),
                   ASYNCTPU_COORDINATOR=f"127.0.0.1:{free_port()}",
                   ASYNCTPU_NUM_PROCESSES="3")
        argv = recipe("asgd", iters=60, extra=(
            "--quiet", "--conf", "async.elastic.boot.grace.s=1",
            "--conf", "async.elastic.dead.after.s=1"))

        def spawn(pid):
            return subprocess.Popen(
                [sys.executable, "-m", "asyncframework_tpu.cli", *argv],
                env=dict(env, ASYNCTPU_PROCESS_ID=str(pid)), text=True,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE)

        procs = [spawn(0), spawn(1)]
        try:
            out1, _ = procs[1].communicate(timeout=120)  # run is DONE now
            procs.append(spawn(2))
            out2, err2 = procs[2].communicate(timeout=120)
            out0, err0 = procs[0].communicate(timeout=120)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        assert [p.returncode for p in procs] == [0, 0, 0], (err0[-2000:],
                                                             err2[-2000:])
        summary = json.loads(out0.strip().splitlines()[-1])
        assert summary["done"] is True and summary["accepted"] == 60
        assert "still booting at DONE" in err0
        late = json.loads(out2.strip().splitlines()[-1])
        assert late["process_id"] == 2 and late["gradients"] == 0
        assert json.loads(out1.strip().splitlines()[-1])["gradients"] >= 60
