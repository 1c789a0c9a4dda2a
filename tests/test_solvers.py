"""Solver tests on the 8-device virtual CPU mesh.

Parity with the reference's algorithm test strategy
(``GradientDescentSuite``): loss decreases, exact semantics of the update
rules, plus async-specific properties (staleness bounds, history-table
consistency, straggler injection effects).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from asyncframework_tpu.data import make_classification, make_regression
from asyncframework_tpu.parallel import make_mesh
from asyncframework_tpu.solvers import ASAGA, ASGD, MiniBatchSGD, SolverConfig


@pytest.fixture(scope="module")
def problem():
    return make_regression(2048, 32, seed=3)


def small_cfg(**kw):
    defaults = dict(
        num_workers=8,
        num_iterations=300,
        gamma=1.0,
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


class TestASGDAsync:
    def test_converges_and_bookkeeps(self, devices8, problem):
        X, y, _ = problem
        # Convergence under tau=inf depends on real thread timing: under heavy
        # CPU load a staleness spike can blow one run up (the algorithm is
        # working as specified -- unbounded-staleness ASGD at the stability
        # edge is not almost-surely convergent).  Retry once before failing.
        for attempt in range(2):
            res = ASGD(X, y, small_cfg(), devices=devices8).run()
            first, last = res.trajectory[0][1], res.trajectory[-1][1]
            if last < first * 0.5:
                break
        assert last < first * 0.5, res.trajectory
        assert res.accepted == 300
        assert res.rounds > 0
        assert res.updates_per_sec > 0
        # trajectory times monotonically nondecreasing
        times = [t for t, _ in res.trajectory]
        assert all(b >= a for a, b in zip(times, times[1:]))

    def test_taw_zero_drops_stale(self, devices8, problem):
        X, y, _ = problem
        res = ASGD(X, y, small_cfg(num_iterations=100, taw=0), devices=devices8).run()
        # with 8 concurrent workers and tau=0, some results must be stale
        assert res.accepted == 100
        assert res.dropped > 0

    def test_infinite_taw_drops_nothing(self, devices8, problem):
        X, y, _ = problem
        res = ASGD(X, y, small_cfg(num_iterations=100), devices=devices8).run()
        assert res.dropped == 0

    def test_logistic_loss_mode(self, devices8):
        X, y, _ = make_classification(2048, 16, seed=5)
        res = ASGD(
            X, y, small_cfg(loss="logistic", gamma=2.0, num_iterations=200),
            devices=devices8,
        ).run()
        assert res.trajectory[-1][1] < res.trajectory[0][1]

    def test_failing_worker_aborts_run(self, devices8, problem):
        """A deterministically-failing task must surface as an error, not a
        silent stall until run_timeout (job-abort propagation)."""
        X, y, _ = problem
        solver = ASGD(
            X, y, small_cfg(num_iterations=500, run_timeout_s=30), devices=devices8
        )
        calls = {"n": 0}
        orig = solver._step

        def flaky_step(Xs, ys, w, key):
            calls["n"] += 1
            if calls["n"] > 20:
                raise RuntimeError("injected device failure")
            return orig(Xs, ys, w, key)

        solver._step = flaky_step
        with pytest.raises(RuntimeError):
            solver.run()

    def test_straggler_injection_slows_worker0(self, devices8, problem):
        X, y, _ = problem
        cfg = small_cfg(
            num_iterations=200, coeff=3.0, calibration_iters=40, printer_freq=1000
        )
        res = ASGD(X, y, cfg, devices=devices8).run()
        assert res.avg_delay_ms > 0  # calibration happened
        assert res.accepted == 200


class TestASGDSync:
    def test_sync_converges(self, devices8, problem):
        X, y, _ = problem
        res = ASGD(
            X, y, small_cfg(num_iterations=60, gamma=2.0), devices=devices8
        ).run_sync()
        assert res.rounds == 60
        assert res.trajectory[-1][1] < res.trajectory[0][1] * 0.2
        assert res.max_staleness <= 8  # full drain keeps staleness ~= nw

    def test_sync_deterministic(self, devices8, problem):
        X, y, _ = problem
        cfg = small_cfg(num_iterations=20, gamma=1.0, coeff=0.0)
        r1 = ASGD(X, y, cfg, devices=devices8).run_sync()
        r2 = ASGD(X, y, cfg, devices=devices8).run_sync()
        np.testing.assert_allclose(r1.final_w, r2.final_w, rtol=1e-5)


class TestASAGA:
    def test_async_converges(self, devices8, problem):
        X, y, _ = problem
        cfg = small_cfg(num_iterations=800, gamma=0.02, batch_rate=0.2)
        res = ASAGA(X, y, cfg, devices=devices8).run()
        assert res.accepted == 800
        # threshold calibrated with the pre-run compile warm-up in place:
        # with no compile serialization of early rounds, dispatch runs at
        # full speed (and full staleness) from round 0, which costs a few
        # percent of per-update progress -- the async tradeoff under test
        assert res.trajectory[-1][1] < res.trajectory[0][1] * 0.4

    def test_sync_converges(self, devices8, problem):
        X, y, _ = problem
        cfg = small_cfg(num_iterations=60, gamma=0.5)
        res = ASAGA(X, y, cfg, devices=devices8).run_sync()
        assert res.rounds == 60
        assert res.trajectory[-1][1] < res.trajectory[0][1] * 0.5

    def test_rejects_non_least_squares(self, problem):
        X, y, _ = problem
        with pytest.raises(ValueError, match="least_squares"):
            ASAGA(X, y, small_cfg(loss="logistic"))

    def test_alpha_bar_tracks_table_mean_exactly(self, devices8, problem):
        """The invariant our commit protocol guarantees (and the reference's
        does not, under dispatch overlap): alpha_bar == (1/N) sum_i
        alpha_i * x_i at all times -- checked after a heavily-overlapped run."""
        X, y, _ = problem
        res = ASAGA(
            X, y, small_cfg(num_iterations=500, gamma=0.02, batch_rate=0.2,
                            bucket_ratio=0.25),
            devices=devices8,
        ).run()
        n = X.shape[0]
        expected = np.zeros(X.shape[1], np.float64)
        for wid, alpha_slice in res.extras["alpha"].items():
            lo = wid * (n // 8)
            Xp = X[lo : lo + alpha_slice.shape[0]]
            expected += Xp.T.astype(np.float64) @ alpha_slice.astype(np.float64)
        expected /= n
        np.testing.assert_allclose(
            res.extras["alpha_bar"], expected, rtol=1e-3, atol=1e-4
        )


class TestMiniBatchSGD:
    def test_full_batch_matches_exact_gd(self, devices8, problem):
        X, y, _ = problem
        mesh = make_mesh(8, devices=devices8)
        sgd = MiniBatchSGD(gamma=2.0, batch_rate=1.0, num_iterations=5, seed=0)
        w, losses, snaps = sgd.run(X, y, mesh=mesh)
        # replicate by hand: full-batch GD with lr = gamma/sqrt(i+1)/n
        n = X.shape[0]
        wr = np.zeros(X.shape[1], np.float32)
        for i in range(5):
            g = X.T @ (X @ wr - y)
            wr = wr - 2.0 / np.sqrt(i + 1.0) * g / n
        np.testing.assert_allclose(w, wr, rtol=2e-3, atol=2e-4)

    def test_loss_history_decreasing(self, devices8, problem):
        X, y, _ = problem
        mesh = make_mesh(8, devices=devices8)
        sgd = MiniBatchSGD(gamma=1.0, batch_rate=0.5, num_iterations=40)
        _, losses, _ = sgd.run(X, y, mesh=mesh)
        assert losses[-1] < losses[0]
        assert len(losses) == 40

    def test_padding_rows_do_not_change_result(self, devices8):
        # n=1000 not divisible by 8 -> 24 pad rows; count must exclude them
        X, y, _ = make_regression(1000, 8, seed=9)
        mesh = make_mesh(8, devices=devices8)
        sgd = MiniBatchSGD(gamma=1.0, batch_rate=1.0, num_iterations=3, seed=1)
        w, _, _ = sgd.run(X, y, mesh=mesh)
        n = X.shape[0]
        wr = np.zeros(8, np.float32)
        for i in range(3):
            g = X.T @ (X @ wr - y)
            wr = wr - 1.0 / np.sqrt(i + 1.0) * g / n
        np.testing.assert_allclose(w, wr, rtol=2e-3, atol=2e-4)

    def test_l2_updater(self, devices8, problem):
        X, y, _ = problem
        mesh = make_mesh(8, devices=devices8)
        sgd = MiniBatchSGD(
            gamma=1.0, batch_rate=1.0, num_iterations=10, updater="l2",
            reg_param=0.1,
        )
        w, losses, _ = sgd.run(X, y, mesh=mesh)
        # L2 shrinks weights vs simple
        w_simple, _, _ = MiniBatchSGD(
            gamma=1.0, batch_rate=1.0, num_iterations=10
        ).run(X, y, mesh=mesh)
        assert np.linalg.norm(w) < np.linalg.norm(w_simple)

    def test_l1_updater_sparsifies(self, devices8, problem):
        X, y, _ = problem
        mesh = make_mesh(8, devices=devices8)
        w, _, _ = MiniBatchSGD(
            gamma=1.0, batch_rate=1.0, num_iterations=20, updater="l1",
            reg_param=0.5,
        ).run(X, y, mesh=mesh)
        assert np.mean(np.abs(w) < 1e-6) > 0.1  # some exact zeros

    def test_snapshots_warray_parity(self, devices8, problem):
        X, y, _ = problem
        mesh = make_mesh(8, devices=devices8)
        sgd = MiniBatchSGD(
            gamma=1.0, batch_rate=0.5, num_iterations=25, snapshot_every=10
        )
        _, _, snaps = sgd.run(X, y, mesh=mesh)
        assert [s[0] for s in snaps] == [0, 10, 20]

    def test_convergence_tol_stops_early(self, devices8, problem):
        X, y, _ = problem
        mesh = make_mesh(8, devices=devices8)
        sgd = MiniBatchSGD(
            gamma=0.01, batch_rate=1.0, num_iterations=100, convergence_tol=0.5
        )
        _, losses, _ = sgd.run(X, y, mesh=mesh)
        assert len(losses) < 100


class TestMiniBatchSGD2D:
    """2-D (dp, md) mesh: features shard over md, rows over dp; results
    must match the dp-only layout bit-for-bit up to float association."""

    @pytest.mark.parametrize("updater,reg", [
        ("simple", 0.0), ("l2", 0.01), ("l1", 0.001),
    ])
    def test_md_sharding_matches_dp_only(self, devices8, problem, updater, reg):
        from asyncframework_tpu.parallel import make_mesh

        X, y, _ = problem
        mk = lambda: MiniBatchSGD(
            gamma=0.5, batch_rate=0.5, num_iterations=40, seed=1,
            updater=updater, reg_param=reg,
        )
        m1 = make_mesh(4, axis_names=("dp", "md"), axis_sizes=(4, 1),
                       devices=devices8[:4])
        m2 = make_mesh(8, axis_names=("dp", "md"), axis_sizes=(4, 2),
                       devices=devices8)
        w1, l1, _ = mk().run(X, y, mesh=m1)
        w2, l2, _ = mk().run(X, y, mesh=m2)
        np.testing.assert_allclose(w1, w2, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)

    def test_md_sharding_with_feature_padding(self, devices8):
        """d not divisible by md: padded feature columns must not leak."""
        from asyncframework_tpu.parallel import make_mesh

        rs = np.random.default_rng(3)
        n, d = 256, 13  # 13 % 4 != 0
        X = rs.normal(size=(n, d)).astype(np.float32)
        w_true = rs.normal(size=(d,)).astype(np.float32)
        y = (X @ w_true).astype(np.float32)
        mesh = make_mesh(8, axis_names=("dp", "md"), axis_sizes=(2, 4),
                         devices=devices8)
        w, losses, _ = MiniBatchSGD(
            gamma=0.5, batch_rate=1.0, num_iterations=150, seed=0
        ).run(X, y, mesh=mesh)
        assert w.shape == (d,)
        assert losses[-1] < 0.05 * losses[0]


class TestBF16AndFlops:
    """bf16-with-f32-accumulate data path + counted-flops instrumentation."""

    @pytest.mark.slow
    def test_bf16_dataset_converges(self, devices8):
        from asyncframework_tpu.data.sharded import ShardedDataset

        ds = ShardedDataset.generate_on_device(
            4096, 32, 8, devices=devices8, seed=5, dtype=jnp.bfloat16
        )
        assert ds.shard(0).X.dtype == jnp.bfloat16
        assert ds.shard(0).y.dtype == jnp.float32
        res = ASGD(ds, None, small_cfg(gamma=2.0), devices=devices8).run()
        first, last = res.trajectory[0][1], res.trajectory[-1][1]
        assert last < first * 0.1, res.trajectory
        assert np.isfinite(res.final_w).all()

    def test_bf16_grad_matches_f32_within_tolerance(self, devices8):
        from asyncframework_tpu.ops.gradients import least_squares_grad_sum

        rs = np.random.default_rng(0)
        X = rs.normal(size=(256, 16)).astype(np.float32) / 4.0
        w = rs.normal(size=(16,)).astype(np.float32)
        y = rs.normal(size=(256,)).astype(np.float32)
        mask = (rs.random(256) < 0.5).astype(np.float32)
        g32 = np.asarray(least_squares_grad_sum(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), jnp.asarray(mask)
        ))
        g16 = np.asarray(least_squares_grad_sum(
            jnp.asarray(X, jnp.bfloat16), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(mask),
        ))
        assert g16.dtype == np.float32  # f32 accumulate
        np.testing.assert_allclose(g16, g32, rtol=0.05, atol=0.5)

    def test_host_array_dtype_cast(self, devices8, problem):
        from asyncframework_tpu.data.sharded import ShardedDataset

        X, y, _ = problem
        ds = ShardedDataset(X, y, 8, devices=devices8, dtype=jnp.bfloat16)
        assert all(ds.shard(w).X.dtype == jnp.bfloat16 for w in range(8))

    def test_flops_counted_async(self, devices8, problem):
        from asyncframework_tpu.utils import flops as fl

        X, y, _ = problem
        cfg = small_cfg(num_iterations=50)
        solver = ASGD(X, y, cfg, devices=devices8)
        res = solver.run()
        # the rows that count are the step's own answer: the dense step's
        # two products run over the whole shard at any batch_rate
        rows = solver._task_rows(X.shape[0] // 8)
        assert rows == X.shape[0] // 8
        per_task = fl.dense_task_flops(rows, X.shape[1])
        # every merged gradient (accepted or dropped) was computed
        assert res.total_flops >= (res.accepted + res.dropped) * per_task
        # and no more than the number of submitted rounds could produce
        assert res.total_flops <= res.rounds * 8 * per_task * 1.01 + per_task

    def test_flops_counted_sync(self, devices8, problem):
        from asyncframework_tpu.utils import flops as fl

        X, y, _ = problem
        cfg = small_cfg(num_iterations=20)
        solver = ASGD(X, y, cfg, devices=devices8)
        res = solver.run_sync()
        per_task = fl.dense_task_flops(
            solver._task_rows(X.shape[0] // 8), X.shape[1]
        )
        assert res.total_flops == pytest.approx(20 * 8 * per_task, rel=0.01)


@pytest.mark.parametrize("solver", [ASGD, ASAGA], ids=["asgd", "asaga"])
def test_submitter_does_not_outrun_the_updater(devices8, problem,
                                               monkeypatch, solver):
    """A worker is available again the moment its result is QUEUED, so with
    steps faster than the updater's applies the queue would grow without
    bound (and every gradient in it would be older than its recorded
    staleness says).  The submitter holds back while a whole fleet of
    results is queued: at most ``nw`` wait there, plus those in flight when
    the gate closed."""
    import time

    from asyncframework_tpu.context import AsyncContext

    X, y, _ = problem
    nw = 8
    cfg = small_cfg(num_workers=nw, num_iterations=120, printer_freq=40,
                    calibration_iters=4)
    engine = solver(X, y, cfg, devices=devices8[:1])
    def slow(real):
        def dispatch(*args):
            time.sleep(0.004)  # an updater far slower than the steps
            return real(*args)

        return dispatch

    # whichever dispatch the updater makes: ASGD folds a backlog into one
    engine._apply = slow(engine._apply)
    if solver is ASGD:
        engine._apply_fold = slow(engine._apply_fold)
    sizes = []
    real_merge = AsyncContext.merge_result

    def spy(self, *a, **k):
        res = real_merge(self, *a, **k)
        sizes.append(self.size())
        return res

    monkeypatch.setattr(AsyncContext, "merge_result", spy)
    res = engine.run()
    assert res.accepted == 120
    assert max(sizes) <= 2 * nw, max(sizes)
