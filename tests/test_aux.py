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
from asyncframework_tpu.ops import gradients, pallas_kernels
from asyncframework_tpu.parallel import multihost
from asyncframework_tpu.utils import hbm


def _onepass_case(rng, n, d, dtype, saga):
    X = jnp.asarray(rng.normal(size=(n, d)), dtype)
    y = rng.normal(size=n).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    mask = (rng.random(n) < 0.3).astype(np.float32)
    alpha = rng.normal(size=n).astype(np.float32) if saga else None
    return X, y, w, mask, alpha


class TestDenseOnepass:
    """interpret=True: the one-pass kernel of the dense worker step runs on
    the CPU interpreter here (``chip_smoke.py`` phase E runs it compiled on
    the chip, ``tests/test_step_layout.py`` compiles it for a v5e).  The
    oracle is NumPy float64 over the rows the mask selects, from the
    shard's stored values.  The interpreter fills what a block holds beyond
    the array with NaN (``test_the_padding_is_poisoned`` keeps that true),
    so every ragged case also shows the tail selected out of ``Xb`` and
    of ``v``."""

    @pytest.mark.parametrize(
        "n,d,dtype,logistic,saga,block",
        [
            (2048, 32, jnp.float32, False, False, 1024),  # whole blocks
            (2500, 48, jnp.float32, False, False, 1024),  # ragged, n % 128
            (100, 16, jnp.float32, False, False, 1024),   # under one block
            (2500, 48, jnp.bfloat16, False, False, 1024),
            (2048, 32, jnp.float32, True, False, 1024),
            (2500, 48, jnp.bfloat16, True, False, 1024),
            (2500, 48, jnp.float32, False, True, 1024),   # ASAGA's form
            (2500, 48, jnp.bfloat16, False, True, 1024),
            (100, 16, jnp.bfloat16, False, True, 1024),
            (1500, 304, jnp.bfloat16, False, True, None),  # 3 row groups
        ],
        ids=["f32-blocks", "f32-ragged", "f32-small", "bf16-ragged",
             "f32-logistic", "bf16-logistic-ragged", "f32-saga-ragged",
             "bf16-saga-ragged", "bf16-saga-small", "bf16-saga-d304"],
    )
    def test_matches_float64_over_the_sampled_rows(
        self, rng, n, d, dtype, logistic, saga, block
    ):
        X, y, w, mask, alpha = _onepass_case(rng, n, d, dtype, saga)
        g, diff = pallas_kernels.dense_onepass(
            X, y, w, mask, alpha, logistic=logistic, block=block,
            interpret=True,
        )
        X64 = np.asarray(X.astype(jnp.float32), np.float64)
        r = X64 @ w
        want_diff = (1.0 / (1.0 + np.exp(-r)) if logistic else r) - y
        rows = mask > 0
        v = want_diff[rows] - (alpha[rows] if saga else 0.0)
        want = X64[rows].T @ v
        assert g.dtype == jnp.float32 and g.shape == (d,)
        assert np.max(np.abs(np.asarray(g) - want)) <= 2e-6 * np.max(
            np.abs(want))
        if saga:
            assert diff.dtype == jnp.float32 and diff.shape == (n,)
            np.testing.assert_allclose(np.asarray(diff), want_diff,
                                       rtol=0, atol=2e-6 * np.sqrt(d) * 4)
        else:
            assert diff is None

    def test_the_padding_is_poisoned(self, rng, monkeypatch):
        """The ragged cases above mean something only while the interpreter
        hands the kernel NaN beyond the array: read the straddling chunk
        unselected and the gradient must not survive."""
        X, y, w, mask, _ = _onepass_case(rng, 2500, 48, jnp.float32, False)
        real = pallas_kernels._accumulate_grad
        monkeypatch.setattr(
            pallas_kernels, "_accumulate_grad",
            lambda xt, v, g, n_full, tail=0: real(xt, v, g,
                                                  n_full + (tail > 0)))
        g, _ = pallas_kernels.dense_onepass(X, y, w, mask, block=1024,
                                            interpret=True)
        assert not np.isfinite(np.asarray(g)).all()

    @pytest.mark.parametrize(
        "on_tpu,d,dtype,rate,want",
        [
            (False, 784, jnp.bfloat16, None, "two_products"),  # this backend
            (True, 784, jnp.bfloat16, None, "onepass"),
            (True, 2000, jnp.float32, None, "onepass"),
            (True, 1024, jnp.float32, None, "two_products"),  # row-major
            (True, 100, jnp.bfloat16, None, "two_products"),  # 100 % 16 != 0
            (True, 784, jnp.float16, None, "two_products"),
            # the draw's rate picks the one-pass kernel's granule, and
            # nothing but it: ASAGA's 0.01 leaves 27.6% of the lane tiles
            # without a sampled row, ASGD's 0.1 one in a million
            (True, 784, jnp.bfloat16, 0.01, "onepass_tiles"),
            (True, 784, jnp.float32, 0.01, "onepass_tiles"),
            (True, 784, jnp.bfloat16, 0.1, "onepass"),
            (False, 784, jnp.bfloat16, 0.01, "two_products"),
            (True, 1024, jnp.float32, 0.01, "two_products"),
        ],
    )
    def test_the_path_is_chosen_from_backend_width_dtype_and_rate(
        self, monkeypatch, on_tpu, d, dtype, rate, want
    ):
        if on_tpu:
            monkeypatch.setattr(gradients, "_on_tpu", lambda: True)
        X = jax.ShapeDtypeStruct((4096, d), dtype)
        assert gradients.dense_step_path(X, rate) == want
        assert gradients.dense_step_path(
            jax.ShapeDtypeStruct((4096,), dtype), rate) == "two_products"

    def test_the_share_of_tiles_a_draw_hits(self):
        assert gradients.dense_tiles_share(None) == 1.0
        assert gradients.dense_tiles_share(0.0) == 0.0
        assert gradients.dense_tiles_share(1.0) == 1.0
        assert abs(gradients.dense_tiles_share(0.01) - 0.723748) < 1e-6
        assert 1.0 - gradients.dense_tiles_share(0.1) < 2e-6
        # ONE constant decides, between the two recipes' rates
        assert (gradients.dense_tiles_share(0.01)
                < gradients.DENSE_TILES_BREAK_EVEN
                < gradients.dense_tiles_share(0.1))


def _tiles_mask(rng, n, hit):
    """A mask over ``n`` rows that marks one to three rows in each of the
    lane tiles ``hit`` and none elsewhere."""
    mask = np.zeros(n, np.float32)
    for t in hit:
        rows = np.arange(t * 128, min((t + 1) * 128, n))
        mask[rng.choice(rows, size=min(3, rows.size), replace=False)] = 1.0
    return mask


class TestDenseOnepassTiles:
    """``dense_onepass_tiles`` against ``dense_onepass`` on the same
    inputs, both on the interpreter: ``g`` to the order of the sums (the
    same terms lane by lane, cut into blocks elsewhere), ``diff`` EQUAL at
    every row of a tile that holds a sampled row and 0 at the others.  A
    block of 512 columns is four list entries a grid step; 2,500 rows are
    19 whole tiles and a ragged one of 68 rows, which the interpreter
    pads with NaN."""

    @pytest.mark.parametrize(
        "n,dtype,saga,logistic,hit",
        [
            (2500, jnp.float32, True, False, [0, 3, 7, 12]),   # count % 4 == 0
            (2500, jnp.float32, True, False, [1, 2, 5, 9, 17]),  # one over
            (2500, jnp.bfloat16, True, False, [0, 4, 19]),     # the ragged tile
            (2500, jnp.float32, True, False, [19]),            # ... alone
            (2500, jnp.bfloat16, False, False, [2, 3, 11, 18, 19]),
            (2500, jnp.float32, False, True, [0, 1, 2, 3, 4, 5, 6, 19]),
            (2500, jnp.float32, True, False, []),              # NO row drawn
            (2500, jnp.bfloat16, False, False, []),
            (2500, jnp.float32, True, False, list(range(20))),  # every tile
            (2500, jnp.bfloat16, False, True, list(range(20))),
            (2048, jnp.bfloat16, True, False, [0, 5, 15]),     # no ragged tile
            (2048, jnp.float32, False, False, list(range(16))),
            (100, jnp.float32, True, False, [0]),              # under one tile
        ],
        ids=["f32-saga-count4", "f32-saga-count5", "bf16-saga-ragged-hit",
             "f32-saga-ragged-alone", "bf16-plain-ragged-hit",
             "f32-logistic-count8", "f32-saga-none", "bf16-plain-none",
             "f32-saga-all", "bf16-logistic-all", "bf16-saga-aligned",
             "f32-plain-aligned-all", "f32-saga-small"],
    )
    def test_matches_the_whole_shard_kernel(
        self, rng, n, dtype, saga, logistic, hit
    ):
        X, y, w, _, alpha = _onepass_case(rng, n, 48, dtype, saga)
        mask = _tiles_mask(rng, n, hit)
        want_g, want_diff = pallas_kernels.dense_onepass(
            X, y, w, mask, alpha, logistic=logistic, block=512,
            interpret=True)
        g, diff = pallas_kernels.dense_onepass_tiles(
            X, y, w, mask, alpha, logistic=logistic, block=512,
            interpret=True)
        assert g.dtype == jnp.float32 and g.shape == (48,)
        assert np.isfinite(np.asarray(g)).all()
        scale = max(float(np.max(np.abs(want_g))), 1e-30)
        assert np.max(np.abs(np.asarray(g) - np.asarray(want_g))) <= (
            2e-6 * scale)
        if not hit:
            assert not np.asarray(g).any()
        if not saga:
            assert diff is None
            return
        assert diff.dtype == jnp.float32 and diff.shape == (n,)
        listed = np.zeros(-(-n // 128), bool)
        listed[hit] = True
        rows = listed.repeat(128)[:n]
        assert np.array_equal(np.asarray(diff)[rows],
                              np.asarray(want_diff)[rows])
        assert not np.asarray(diff)[~rows].any()

    def test_a_narrow_shard_takes_no_more_entries_than_semaphores(self, rng):
        """A window has a DMA semaphore of its own, and the chip holds 512:
        where the default block of a narrow shard would ask for more
        (16 columns of f32: 106,496 columns, 832 entries a buffer) a grid
        step takes ``_TILES_MAX_ENTRIES``, and the result is the same."""
        n, d = 20_000, 16
        X = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        y = rng.normal(size=n).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32)
        mask = _tiles_mask(rng, n, range(0, 157, 2))
        assert pallas_kernels.onepass_block(d, 4) // 128 > (
            pallas_kernels._TILES_MAX_ENTRIES)
        want_g, _ = pallas_kernels.dense_onepass(X, y, w, mask,
                                                 interpret=True)
        g, _ = pallas_kernels.dense_onepass_tiles(X, y, w, mask,
                                                  interpret=True)
        assert np.max(np.abs(np.asarray(g) - np.asarray(want_g))) <= (
            2e-6 * np.max(np.abs(want_g)))

    def test_a_weighted_mask_lists_every_tile_with_a_weight(self, rng):
        """``mask`` may hold weights: a tile is listed where any is not 0."""
        X, y, w, _, alpha = _onepass_case(rng, 2500, 48, jnp.float32, True)
        mask = _tiles_mask(rng, 2500, [3, 8, 19]) * rng.normal(size=2500)
        mask = mask.astype(np.float32)
        want_g, _ = pallas_kernels.dense_onepass(
            X, y, w, mask, alpha, block=512, interpret=True)
        g, _ = pallas_kernels.dense_onepass_tiles(
            X, y, w, mask, alpha, block=512, interpret=True)
        assert np.max(np.abs(np.asarray(g) - np.asarray(want_g))) <= (
            2e-6 * np.max(np.abs(want_g)))


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


@pytest.mark.parametrize("solver", ["asgd", "asaga"])
def test_a_result_says_which_dense_step_it_timed(tiny_problem, devices8,
                                                 solver):
    """``TrainResult.extras["dense_step_path"]`` of ``run()`` and
    ``run_fused()``: decided where the step is built, from the shard the
    solver holds; on this backend the two XLA products."""
    from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig

    X, y, _ = tiny_problem
    cfg = SolverConfig(
        num_workers=4, num_iterations=40, gamma=0.5, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=20, coeff=0.0,
        seed=1, calibration_iters=4, run_timeout_s=60.0,
    )
    cls = {"asgd": ASGD, "asaga": ASAGA}[solver]
    engine = cls(X, y, cfg, devices=devices8[:1])
    assert gradients.dense_step_path(engine.ds.shard(0).X) == "two_products"
    assert engine.run().extras["dense_step_path"] == "two_products"
    fused = cls(X, y, cfg, devices=devices8[:1]).run_fused()
    assert fused.extras["dense_step_path"] == "two_products"


@pytest.mark.parametrize("platforms,starts", [("cpu", False),
                                              ("tpu,cpu", True),
                                              (None, True)])
def test_kernels_are_preloaded_only_where_a_tpu_may_be_held(
    monkeypatch, platforms, starts
):
    """``setup_compile_cache`` starts the Pallas import on a background
    thread, to finish inside the chip's attach; a process held to the CPU
    starts nothing."""
    import threading

    from asyncframework_tpu.utils import devices

    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    started = []
    real_start = threading.Thread.start

    def spy(self):
        started.append(self.name)
        real_start(self)

    monkeypatch.setattr(threading.Thread, "start", spy)
    devices._preload_kernels()
    assert ("preload-kernels" in started) == starts
    for t in threading.enumerate():
        if t.name == "preload-kernels":
            t.join(timeout=30)
