"""Auxiliary subsystem tests: pallas kernel, multihost helpers, HBM
planning, HTML report."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from asyncframework_tpu.metrics import (
    EventLogWriter,
    GradientMerged,
    JobEnd,
    JobStart,
    ModelSnapshot,
    TaskEnd,
    WorkerLost,
    render_report,
)
from asyncframework_tpu.ops.pallas_kernels import (
    fused_masked_grad,
    reference_masked_grad,
)
from asyncframework_tpu.parallel import multihost
from asyncframework_tpu.utils import hbm


class TestFusedMaskedGrad:
    """interpret=True: the Pallas kernel runs on the CPU interpreter here;
    chip_smoke.py phase E compiles it natively on the chip.  Shapes cover
    one exact tile, tiles plus a ragged XLA tail, and a tail alone."""

    @pytest.mark.parametrize("n,d", [(256, 128), (300, 100), (64, 17),
                                     (700, 200)])
    def test_matches_oracle(self, rng, n, d):
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = rng.normal(size=(n,)).astype(np.float32)
        w = rng.normal(size=(d,)).astype(np.float32)
        mask = (rng.random(n) < 0.5).astype(np.float32)
        got = fused_masked_grad(X, y, w, mask, interpret=True)
        want = reference_masked_grad(X, y, w, mask)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3
        )

    def test_bf16_shard_read_in_storage_dtype(self, rng):
        """bf16 shards follow mm_f32 (bf16 operands, f32 accumulation):
        the kernel must agree with the main path's own contraction."""
        import jax.numpy as jnp

        from asyncframework_tpu.ops.gradients import least_squares_grad_sum

        X = jnp.asarray(rng.normal(size=(600, 96)), jnp.bfloat16)
        y = rng.normal(size=(600,)).astype(np.float32)
        w = rng.normal(size=(96,)).astype(np.float32)
        mask = (rng.random(600) < 0.5).astype(np.float32)
        got = fused_masked_grad(X, y, w, mask, interpret=True)
        want = least_squares_grad_sum(X, y, w, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)

    def test_no_mask_means_all_rows(self, rng):
        X = rng.normal(size=(64, 32)).astype(np.float32)
        y = rng.normal(size=(64,)).astype(np.float32)
        w = rng.normal(size=(32,)).astype(np.float32)
        got = fused_masked_grad(X, y, w, interpret=True)
        want = reference_masked_grad(X, y, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    def test_row_tile_bigger_than_n(self, rng):
        X = rng.normal(size=(16, 8)).astype(np.float32)
        y = rng.normal(size=(16,)).astype(np.float32)
        w = rng.normal(size=(8,)).astype(np.float32)
        got = fused_masked_grad(X, y, w, row_tile=4096, interpret=True)
        want = reference_masked_grad(X, y, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)


class TestMultihost:
    def test_single_process_noop(self):
        assert multihost.ensure_initialized() is False
        assert not multihost.is_initialized()
        pid, count = multihost.process_info()
        assert pid == 0 and count == 1

    def test_sync_hosts_barrier_passes(self):
        multihost.sync_hosts()  # single host: psum over local devices

    def test_global_mesh_spans_devices(self):
        mesh = multihost.global_mesh()
        assert mesh.devices.size == jax.device_count()
        assert mesh.axis_names == ("dp",)


class TestHbmPlanning:
    def test_nbytes(self):
        assert hbm.nbytes((10, 10)) == 400
        assert hbm.nbytes((4,), np.float64) == 32

    def test_plan_fits_and_overflows(self):
        plan = hbm.plan_dataset(
            n=8_100_000, d=784, num_workers=8, num_devices=8,
            budget_bytes=16 * 1024**3,
        )
        assert plan.fits  # mnist8m sharded 8 ways: ~3.2 GB/device
        assert 0 < plan.utilization < 1
        plan.require_fits()

        too_big = hbm.plan_dataset(
            n=8_100_000, d=784, num_workers=1, num_devices=1,
            budget_bytes=16 * 1024**3,
        )
        assert not too_big.fits  # whole mnist8m on one device: ~25 GB
        with pytest.raises(MemoryError):
            too_big.require_fits()

    def test_history_table_and_versions_accounted(self):
        base = hbm.plan_dataset(1000, 10, 2, 2, budget_bytes=10**9)
        with_hist = hbm.plan_dataset(
            1000, 10, 2, 2, budget_bytes=10**9, history_table=True
        )
        assert with_hist.bytes_per_device > base.bytes_per_device

    def test_device_budget_queryable(self):
        assert hbm.device_hbm_bytes() > 0

    def test_fmt_bytes(self):
        assert hbm.fmt_bytes(512) == "512 B"
        assert hbm.fmt_bytes(2 * 1024**3) == "2.0 GiB"


class TestHtmlReport:
    def test_report_from_event_log(self, tmp_path):
        log = tmp_path / "events.jsonl"
        w = EventLogWriter(log)
        w.on_event(JobStart(0.0, job_id=1, worker_ids=(0, 1)))
        for i in range(20):
            w.on_event(GradientMerged(
                float(i), worker_id=i % 2, staleness=i % 3,
                accepted=i % 5 != 0, iteration=i,
            ))
            w.on_event(ModelSnapshot(float(i), iteration=i,
                                     objective=1.0 / (i + 1)))
        w.on_event(TaskEnd(5.0, job_id=1, worker_id=0, attempt=0,
                           run_ms=12.5, succeeded=True))
        w.on_event(TaskEnd(6.0, job_id=1, worker_id=1, attempt=0,
                           run_ms=20.0, succeeded=False, error="boom"))
        w.on_event(WorkerLost(7.0, worker_id=1, reason="heartbeat timeout"))
        w.on_event(JobEnd(8.0, job_id=1, succeeded=False, error="aborted"))
        w.close()

        out = tmp_path / "report.html"
        doc = render_report(log, out, title="test run")
        assert out.read_text() == doc
        assert "<h1>test run</h1>" in doc
        assert "gradients merged" in doc and "<td>20</td>" in doc
        assert "heartbeat timeout" in doc
        assert "<svg" in doc  # charts rendered
        assert "boom" in doc

    def test_empty_log(self, tmp_path):
        log = tmp_path / "empty.jsonl"
        log.write_text("")
        doc = render_report(log)
        assert "not enough data" in doc
