"""Two-process DCN bring-up test (VERDICT item 8).

Parity: ``deploy/LocalSparkCluster.scala:36`` -- the reference proves its
cluster story by booting a real Master + Workers inside one machine and
running real jobs over real RPC.  The analog here: two OS processes on
localhost initialize ``jax.distributed`` through ``parallel/multihost.py``
(one coordinator, gRPC over the loopback DCN), fence on the host barrier,
and run a psum that must cross the process boundary to produce the right
answer.  No TPU required: the forced-CPU platform exercises the identical
code path.
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

CHILD = Path(__file__).parent / "dcn_child.py"


def _require_cpu_spmd() -> None:
    """Probed-capability gate (ISSUE 13 tier-1 deflake): cross-process
    SPMD on the CPU backend is a jax-build capability, not a property of
    this repo's code -- a jax build without gloo-capable CPU collectives
    raises "Multiprocess computations aren't implemented on the CPU
    backend".  The session-cached 2-process probe (tests/test_deploy.py,
    ISSUE 12) runs the repo's own bring-up once; on incapable rigs these
    suites SKIP with the probed reason instead of failing as a
    permanent baseline."""
    from test_deploy import cpu_spmd_capability

    reason = cpu_spmd_capability()
    if reason:
        pytest.skip(reason)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_group(script: Path, n: int, timeout: float = 240.0):
    """Boot ``n`` coordinated jax.distributed processes running ``script``
    and return their final-line JSON records."""
    port = free_port()
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)  # child sets its own platform
        env.pop("XLA_FLAGS", None)
        env.update(
            ASYNCTPU_COORDINATOR=f"127.0.0.1:{port}",
            ASYNCTPU_NUM_PROCESSES=str(n),
            ASYNCTPU_PROCESS_ID=str(pid),
        )
        procs.append(subprocess.Popen(
            [sys.executable, str(script)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        ))
    results = []
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"child failed:\nstdout={out}\nstderr={err}"
        results.append(json.loads(out.strip().splitlines()[-1]))
    return results


def _check_bringup(results, n: int):
    by_pid = {r["pid"]: r for r in results}
    assert set(by_pid) == set(range(n))
    for r in results:
        assert r["active"] is True          # multi-process mode detected
        assert r["pc"] == n                 # every process joined
        assert r["devices"] == 2 * n        # n hosts x 2 virtual devices
        assert r["local_devices"] == 2
        # each device contributes (pid+1): total = 2 * sum(pid+1) = n(n+1)
        assert r["psum"] == float(n * (n + 1))
        assert r["mesh_size"] == 2 * n      # global mesh spans all hosts


def test_two_process_bringup_barrier_and_psum():
    _require_cpu_spmd()
    _check_bringup(_spawn_group(CHILD, 2, timeout=150), 2)


@pytest.mark.slow
def test_four_process_bringup_barrier_and_psum():
    """VERDICT r4 #7: the jax.distributed path past 2 processes -- four
    coordinated processes (8 global devices) join, fence, and psum across
    every process boundary (the reference's story is an 8-worker cluster,
    README.md:56)."""
    _require_cpu_spmd()
    _check_bringup(_spawn_group(CHILD, 4), 4)


def _check_training(results, n: int, single_mesh_devices: int):
    import numpy as np

    for r in results:
        assert r["active"] and r["pc"] == n and r["mesh"] == 2 * n
    # all processes computed the identical replicated model
    for r in results[1:]:
        np.testing.assert_allclose(results[0]["w"], r["w"], rtol=1e-6)

    # and it matches a single-process run on an equal-size mesh
    import dcn_train_child as child_mod  # same problem() fixture

    from asyncframework_tpu.parallel import make_mesh
    from asyncframework_tpu.solvers import MiniBatchSGD
    import jax

    X, y = child_mod.problem()
    mesh = make_mesh(single_mesh_devices,
                     devices=jax.devices()[:single_mesh_devices])
    w_local, losses, _ = MiniBatchSGD(
        gamma=0.5, batch_rate=0.5, num_iterations=40, seed=3
    ).run(X, y, mesh=mesh)
    np.testing.assert_allclose(
        results[0]["w"], np.asarray(w_local), rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        results[0]["final_loss"], float(losses[-1]), rtol=1e-4
    )


def test_two_process_distributed_training_matches_local():
    """The cluster story end to end: the SAME MiniBatchSGD code trains over
    a 2-process global mesh (DCN) and produces the same model as one
    process with an equal-size mesh."""
    _require_cpu_spmd()
    results = _spawn_group(
        Path(__file__).parent / "dcn_train_child.py", 2, timeout=150
    )
    _check_training(results, 2, single_mesh_devices=4)


@pytest.mark.slow
def test_four_process_distributed_training_matches_local():
    """VERDICT r4 #7, training half: one step short of the reference's
    8-worker recipe -- 4 processes x 2 devices train over DCN and agree
    with the single-process 8-device mesh."""
    _require_cpu_spmd()
    results = _spawn_group(
        Path(__file__).parent / "dcn_train_child.py", 4
    )
    _check_training(results, 4, single_mesh_devices=8)


class TestLocalClusterLauncher:
    def test_two_process_cluster_matches_single(self):
        """LocalSparkCluster parity: the launcher's 2-process run produces
        the same recipe output as a single-process run of the same CLI."""
        import json

        _require_cpu_spmd()
        from asyncframework_tpu.cluster import launch_local_cluster

        recipe = ["--quiet", "sgd-mllib", "synthetic", "synthetic",
                  "16", "512", "4", "30", "1.0", "0", "0.5", "0.5",
                  "15", "0", "42"]
        rc, out = launch_local_cluster(
            2, recipe, devices_per_process=2, timeout_s=240.0
        )
        assert rc == 0
        summary = json.loads(
            [ln for ln in out if ln.startswith("{")][-1]
        )
        assert summary["driver"] == "sgd-mllib"
        assert summary["iterations"] == 30
        rc1, out1 = launch_local_cluster(
            1, recipe, devices_per_process=4, timeout_s=240.0
        )
        assert rc1 == 0
        s1 = json.loads([ln for ln in out1 if ln.startswith("{")][-1])
        # same global device count (2x2 vs 1x4) and same seed -- but the
        # cross-process psum reduces in a different float order, and 30
        # gamma=1.0 steps amplify the ulp-level drift; both runs must
        # converge into the same band, not match bit-for-bit
        a, b = s1["final_objective"], summary["final_objective"]
        assert a < 0.5 and b < 0.5  # both converged (initial ~ 16)
        assert abs(a - b) / max(a, b) < 0.3

    def test_usage_errors(self):
        from asyncframework_tpu.cluster import main

        assert main([]) == 2
        assert main(["notanint"]) == 2


class TestClusterASGDMode:
    def test_asgd_over_local_cluster(self):
        """VERDICT r2 item 3 end-to-end: `async-cluster 3 -- asgd ...` runs
        the DCN parameter server -- a PS process plus two worker processes,
        every gradient crossing a process boundary -- and converges."""
        import json

        from asyncframework_tpu.cluster import launch_local_cluster

        recipe = ["--quiet", "asgd", "synthetic", "synthetic",
                  "16", "4096", "8", "400", "1.0", "2147483647", "0.3",
                  "0.5", "50", "0", "42"]
        rc, out = launch_local_cluster(
            3, recipe, devices_per_process=2, timeout_s=240.0
        )
        assert rc == 0
        summary = json.loads([ln for ln in out if ln.startswith("{")][-1])
        assert summary["driver"] == "asgd-dcn-ps"
        assert summary["done"] is True
        assert summary["accepted"] == 400
        assert summary["final_objective"] is not None
        assert summary["final_objective"] < 0.05  # initial ~1.0
