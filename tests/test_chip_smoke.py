"""chip_smoke.py and the device-ownership helpers, on the CPU.

The smoke's full-size run needs a TPU (``chiprun ... python3
chip_smoke.py``); here its ``--dry-run`` drives the same flow -- the
chip-holding child with phases A-F, then the one-process-per-device cluster
-- at tiny shapes, and the contract's failure modes are checked: no chip
means a non-zero exit that names the platform found and prints no result.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from asyncframework_tpu import cluster
from asyncframework_tpu.utils import devices

REPO = Path(__file__).parent.parent
SMOKE = str(REPO / "chip_smoke.py")


def run_smoke(*args, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, SMOKE, *args], env=env, cwd=str(REPO),
        capture_output=True, text=True, timeout=300,
    )


class TestSmoke:
    def test_dry_run_passes_every_phase(self):
        res = run_smoke("--dry-run")
        assert res.returncode == 0, res.stderr[-2000:]
        lines = res.stdout.strip().splitlines()
        # the last line is the verdict: exactly these keys, nothing else
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
        out = json.loads(lines[-2])["report"]
        assert out["dry_run"] is True
        assert out["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 8}
        phases = out["phases"]
        assert set(phases) == {"A", "B", "C", "D", "E", "E.segments",
                               "F.engine", "F.mesh", "F.cluster"}
        for name in ("A", "B", "C", "F.engine", "F.mesh", "F.cluster"):
            p = phases[name]
            assert p["accepted"] == p["requested"], (name, p)
            assert p["last_objective"] <= 0.5 * p["first_objective"]
        assert phases["E"]["interpret"] is True
        seg = phases["E.segments"]
        assert seg["interpret"] is True
        assert seg["rel_err"] <= seg["tolerance"]
        assert phases["F.engine"]["shard_devices"] == list(range(8))
        # every role record names the device its launcher assigned
        workers = phases["F.cluster"]["workers"]
        assert [w["assigned"] for w in workers] == ["cpu"]
        assert phases["F.cluster"]["server"]["assigned"] == "cpu"
        assert out["cache"]["entries_after"] >= out["cache"]["entries_before"]

    def test_no_chip_exits_nonzero_and_names_the_platform(self):
        res = run_smoke()  # the contract's invocation, JAX_PLATFORMS=cpu
        assert res.returncode != 0
        assert res.stdout.strip() == ""  # no result
        assert "platform 'cpu'" in res.stderr


class TestCompileCache:
    def test_env_var_is_left_alone(self, monkeypatch):
        import jax

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(devices.CACHE_ENV, "/somewhere/else")
        assert devices.compile_cache_dir() == "/somewhere/else"
        assert devices.setup_compile_cache() == "/somewhere/else"
        # JAX read the variable itself; the program set no directory
        assert jax.config.jax_compilation_cache_dir == before
        assert os.environ[devices.CACHE_ENV] == "/somewhere/else"

    def test_fixed_in_checkout_path_when_unset(self, monkeypatch):
        monkeypatch.delenv(devices.CACHE_ENV, raising=False)
        first, second = (devices.setup_compile_cache(),
                         devices.setup_compile_cache())
        assert first == second == str(REPO / ".jax_cache")

    def test_launchers_pass_the_directory_to_children(self, monkeypatch):
        monkeypatch.delenv(devices.CACHE_ENV, raising=False)
        for assigned in (devices.CPU, "tpu:0"):
            env = devices.child_env({}, assigned)
            assert env[devices.CACHE_ENV] == str(REPO / ".jax_cache")


class TestDeviceOwnership:
    def test_cpu_rig_puts_everything_on_the_cpu(self):
        roles = devices.process_roles("asgd", 3)
        assert roles == ["server", "worker", "worker"]
        assert devices.assign_devices(roles, 0) == ["cpu"] * 3
        assert devices.process_roles("sgd-mllib", 2) == ["worker"] * 2
        assert devices.process_roles("asgd", 1) == ["worker"]

    def test_workers_first_then_the_server_if_a_chip_is_left(
            self, monkeypatch):
        monkeypatch.setattr(devices, "host_chip_count", lambda: 4)
        five = ["server"] + ["worker"] * 4
        assert devices.assign_devices(five, 4) == [
            "cpu", "tpu:0", "tpu:1", "tpu:2", "tpu:3"]
        four = ["server"] + ["worker"] * 3
        assert devices.assign_devices(four, 4) == [
            "tpu:3", "tpu:0", "tpu:1", "tpu:2"]

    def test_more_chip_holders_than_chips_is_refused(self, monkeypatch):
        monkeypatch.setattr(devices, "host_chip_count", lambda: 4)
        with pytest.raises(ValueError, match="5 worker processes"):
            devices.assign_devices(["worker"] * 5, 4)
        with pytest.raises(ValueError, match="this host has 4"):
            devices.assign_devices(["worker"], 8)

    def test_child_env_confines_a_process_to_its_chip(self):
        env = devices.child_env({"JAX_PLATFORMS": "cpu"}, "tpu:2")
        assert env["TPU_VISIBLE_CHIPS"] == "2"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["JAX_PLATFORMS"] == "tpu,cpu"
        assert env[devices.ASSIGNED_ENV] == "tpu:2"
        cpu = devices.child_env({}, devices.CPU, cpu_devices=2)
        assert cpu["JAX_PLATFORMS"] == "cpu"
        assert "device_count=2" in cpu["XLA_FLAGS"]
        assert "TPU_VISIBLE_CHIPS" not in cpu

    def test_cluster_cli_refuses_chips_this_host_lacks(self, capsys):
        """``bin/async-cluster`` can be pointed at the chip from its
        command line, and refuses -- never hangs -- where there is none."""
        rc = cluster.main(["2", "--chips", "1", "--", "asgd"])
        assert rc == 2
        assert "this host has 0" in capsys.readouterr().err
