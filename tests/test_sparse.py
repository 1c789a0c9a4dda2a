"""Sparse (rcv1-class) end-to-end tests.

Round-2 requirement: CSR shards resident on device in a
static-shape form, the worker step computing sparse gradients without ever
densifying the data, and an ASGD recipe on a 47k-dim ~0.2%-dense problem
converging -- through the CLI as well.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from asyncframework_tpu.data import (
    SparseShardedDataset,
    densify,
    make_sparse_regression,
    parse_libsvm_lines_sparse,
)
from asyncframework_tpu.ops import gradients, steps
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig


def small_sparse(n=512, d=256, density=0.05, seed=0):
    indptr, indices, values, y = make_sparse_regression(n, d, density, seed)
    return indptr, indices, values, y


class TestSparseData:
    def test_parse_libsvm_sparse(self):
        lines = ["1.0 3:2.5 7:1.0", "# comment", "-1 1:0.5"]
        indptr, indices, values, y = parse_libsvm_lines_sparse(lines, 8)
        assert list(indptr) == [0, 2, 3]
        assert list(indices) == [2, 6, 0]  # 0-based
        np.testing.assert_allclose(values, [2.5, 1.0, 0.5])
        np.testing.assert_allclose(y, [1.0, -1.0])

    def test_shards_padded_and_faithful(self, devices8):
        indptr, indices, values, y = small_sparse()
        ds = SparseShardedDataset(indptr, indices, values, y, 256, 8, devices8)
        assert ds.n == 512 and ds.d == 256
        s0 = ds.shard(0)
        assert s0.cols.shape == s0.vals.shape
        assert s0.cols.shape[1] % 8 == 0  # lane-padded
        # densify reproduces the CSR rows
        X, y2 = densify(ds)
        np.testing.assert_allclose(y2, y)
        i = 5  # spot-check one row
        a, b = indptr[i], indptr[i + 1]
        row = np.zeros(256, np.float32)
        row[indices[a:b]] = values[a:b]
        np.testing.assert_allclose(X[i], row)


class TestSparseOps:
    def test_sparse_grad_matches_dense(self, devices8):
        indptr, indices, values, y = small_sparse(128, 64, 0.1, seed=3)
        ds = SparseShardedDataset(indptr, indices, values, y, 64, 1, devices8[:1])
        s = ds.shard(0)
        rs = np.random.default_rng(1)
        w = rs.normal(size=(64,)).astype(np.float32)
        mask = (rs.random(128) < 0.5).astype(np.float32)
        X, _ = densify(ds)

        r = np.asarray(gradients.sparse_residual(s.cols, s.vals, s.y, w))
        np.testing.assert_allclose(r, X @ w - y, rtol=1e-4, atol=1e-5)

        grad_sum = gradients.make_sparse_grad_sum(64)
        g = np.asarray(grad_sum(s.cols, s.vals, mask * r))
        np.testing.assert_allclose(
            g, X.T @ (mask * (X @ w - y)), rtol=1e-3, atol=1e-3
        )

    def test_sparse_saga_step_matches_dense_formula(self, devices8):
        """The compacted sparse SAGA step reproduces the dense masked
        formula exactly: recover the selected-row mask from (idx, valid)
        and compare gradient, candidate scalars, and the commit."""
        indptr, indices, values, y = small_sparse(64, 32, 0.2, seed=5)
        ds = SparseShardedDataset(indptr, indices, values, y, 32, 1, devices8[:1])
        s = ds.shard(0)
        rs = np.random.default_rng(2)
        w = rs.normal(size=(32,)).astype(np.float32)
        alpha = rs.normal(size=(64,)).astype(np.float32)
        step = steps.make_sparse_saga_worker_step(0.5, 32)
        g, diff_sel, idx, valid, c_sel, v_sel, _ = step(
            s.cols, s.vals, s.y, w, alpha, jax.random.PRNGKey(0)
        )
        idx_h = np.asarray(idx)
        valid_h = np.asarray(valid)
        sel = idx_h[valid_h > 0]
        m = np.zeros(64, np.float32)
        m[sel] = 1.0
        X, _ = densify(ds)
        full_diff = X @ w - y
        # candidate scalars for the selected rows match the dense residual
        np.testing.assert_allclose(
            np.asarray(diff_sel)[valid_h > 0], full_diff[sel],
            rtol=1e-4, atol=1e-5,
        )
        expect = X.T @ (m * (full_diff - alpha))
        np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-3, atol=1e-3)
        # the commit writes exactly the selected rows
        commit = steps.make_sparse_saga_commit()
        a2 = np.asarray(commit(jnp.asarray(alpha), diff_sel, idx, valid))
        want = np.where(m > 0, full_diff, alpha)
        np.testing.assert_allclose(a2, want, rtol=1e-4, atol=1e-5)
        # and the exact table delta equals the dense formulation
        delta = steps.make_sparse_table_delta(32)(
            c_sel, v_sel, diff_sel, jnp.asarray(alpha), idx
        )
        np.testing.assert_allclose(
            np.asarray(delta), expect, rtol=1e-3, atol=1e-3
        )


class TestSparseSolvers:
    def cfg(self, **kw):
        defaults = dict(
            num_workers=8, num_iterations=200, gamma=0.3,
            taw=2**31 - 1, batch_rate=0.2, bucket_ratio=0.5,
            printer_freq=50, coeff=0.0, seed=42,
            calibration_iters=10, run_timeout_s=120.0,
        )
        defaults.update(kw)
        return SolverConfig(**defaults)

    def test_asgd_converges_47kdim_sparse(self, devices8):
        # the VERDICT-prescribed shape: 47k dims at ~0.2% density
        indptr, indices, values, y = make_sparse_regression(
            2048, 47_236, density=0.002, seed=11
        )
        ds = SparseShardedDataset(
            indptr, indices, values, y, 47_236, 8, devices8
        )
        res = ASGD(ds, None, self.cfg(gamma=0.5), devices=devices8).run()
        assert res.accepted == 200
        first, last = res.trajectory[0][1], res.trajectory[-1][1]
        assert last < first * 0.7, res.trajectory

    def test_asgd_sync_sparse(self, devices8):
        indptr, indices, values, y = small_sparse(1024, 512, 0.01, seed=7)
        ds = SparseShardedDataset(indptr, indices, values, y, 512, 8, devices8)
        res = ASGD(ds, None, self.cfg(num_iterations=50, gamma=0.5),
                   devices=devices8).run_sync()
        assert res.rounds == 50
        assert res.trajectory[-1][1] < res.trajectory[0][1]

    def test_asaga_sparse_runs_and_converges(self, devices8):
        indptr, indices, values, y = small_sparse(1024, 512, 0.01, seed=9)
        ds = SparseShardedDataset(indptr, indices, values, y, 512, 8, devices8)
        res = ASAGA(ds, None, self.cfg(num_iterations=150, gamma=0.05),
                    devices=devices8).run()
        assert res.accepted == 150
        assert res.trajectory[-1][1] < res.trajectory[0][1]


class TestSparseCLI:
    def test_rcv1_shaped_recipe(self, capsys):
        from asyncframework_tpu import cli

        rc = cli.main([
            "SparkASGDThread", "synthetic", "x", "47236", "1024", "8", "60",
            "0.5", "2147483647", "0.2", "0.5", "20", "0", "42",
            "--quiet", "--sparse",
        ])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()
        summary = json.loads(out[-1])
        assert summary["accepted"] == 60
        assert np.isfinite(summary["final_objective"])

    def test_sparse_rejected_for_mllib(self):
        from asyncframework_tpu import cli

        with pytest.raises(SystemExit):
            cli.main([
                "sgd-mllib", "synthetic", "x", "64", "256", "8", "5",
                "0.5", "0", "0.2", "0.5", "5", "0", "42", "--sparse",
            ])


class TestSparseGenerateOnDevice:
    def test_shapes_conditioning_and_convergence(self, devices8):
        from asyncframework_tpu.data.sparse import SparseShardedDataset

        n, d, nnz = 4096, 512, 12
        ds = SparseShardedDataset.generate_on_device(
            n, d, nnz, 8, devices=devices8, seed=9
        )
        assert ds.n == n and ds.d == d
        s = ds.shard(0)
        K = s.cols.shape[1]
        assert K % 8 == 0 and K >= nnz
        cols = np.asarray(s.cols)
        vals = np.asarray(s.vals)
        # padding slots beyond nnz are exactly (col=0, val=0)
        assert (cols[:, nnz:] == 0).all() and (vals[:, nnz:] == 0).all()
        assert (cols[:, :nnz] < d).all() and (cols >= 0).all()
        # E[x x^T] = I/d conditioning: per-row squared norm ~ 1/nnz * nnz / ...
        row_sq = (vals ** 2).sum(axis=1)
        assert abs(row_sq.mean() - 1.0) < 0.15  # nnz * (1/nnz) = 1
        # the planted problem is learnable by sparse ASGD
        cfg = SolverConfig(
            num_workers=8, num_iterations=400, gamma=0.05 * d,
            batch_rate=0.3, bucket_ratio=0.5, printer_freq=50,
            seed=42, calibration_iters=10, run_timeout_s=120.0,
        )
        res = ASGD(ds, None, cfg, devices=devices8).run()
        first, last = res.trajectory[0][1], res.trajectory[-1][1]
        best = min(obj for _t, obj in res.trajectory)
        # learnability: the run reaches a deep minimum.  The FINAL point
        # rides the 1/sqrt(k) late phase of an async run at this recipe's
        # stability edge and oscillates run-to-run (observed 0.01-0.15x
        # first on the seed tree), so it gets a looser band than the dip
        # -- still tight enough that genuine divergence (>= 0.5x) fails.
        assert best < first * 0.1, res.trajectory
        assert last < first * 0.3, res.trajectory

    def test_deterministic_per_seed(self, devices8):
        from asyncframework_tpu.data.sparse import SparseShardedDataset

        a = SparseShardedDataset.generate_on_device(256, 64, 4, 8, devices=devices8, seed=3)
        b = SparseShardedDataset.generate_on_device(256, 64, 4, 8, devices=devices8, seed=3)
        c = SparseShardedDataset.generate_on_device(256, 64, 4, 8, devices=devices8, seed=4)
        np.testing.assert_array_equal(np.asarray(a.shard(1).cols), np.asarray(b.shard(1).cols))
        np.testing.assert_array_equal(np.asarray(a.shard(1).vals), np.asarray(b.shard(1).vals))
        assert not np.array_equal(np.asarray(a.shard(1).vals), np.asarray(c.shard(1).vals))


def _skewed_csr(n=400, d=1000, base_nnz=5, dense_every=50, dense_nnz=400, seed=0):
    """rcv1-like skew: mostly ~base_nnz rows, a few near-dense outliers."""
    rs = np.random.default_rng(seed)
    indptr = [0]
    indices = []
    values = []
    for i in range(n):
        k = dense_nnz if i % dense_every == 0 else base_nnz
        cols = rs.choice(d, size=k, replace=False)
        indices.extend(cols.tolist())
        values.extend(rs.normal(size=k).tolist())
        indptr.append(len(indices))
    y = rs.normal(size=n).astype(np.float32)
    return (np.asarray(indptr), np.asarray(indices, np.int32),
            np.asarray(values, np.float32), y)


class TestSkewGuard:
    def test_warning_on_skewed_data(self, devices8):
        import warnings

        from asyncframework_tpu.data.sparse import SparseShardedDataset

        indptr, indices, values, y = _skewed_csr()
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            ds = SparseShardedDataset(indptr, indices, values, y, 1000, 8,
                                      devices=devices8)
        assert any("nnz_partition" in str(w.message) for w in rec), (
            [str(w.message) for w in rec]
        )
        rep = ds.skew_report()
        assert rep["pad_overhead"] > SparseShardedDataset.PAD_OVERHEAD_WARN

    def test_nnz_partition_bounds_padding(self, devices8):
        import warnings

        from asyncframework_tpu.data.sparse import SparseShardedDataset, densify

        indptr, indices, values, y = _skewed_csr(dense_every=10)
        with pytest.warns(RuntimeWarning, match="nnz_partition"):
            plain = SparseShardedDataset(indptr, indices, values, y, 1000, 8,
                                         devices=devices8)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            sorted_ds = SparseShardedDataset(
                indptr, indices, values, y, 1000, 8, devices=devices8,
                nnz_partition=True,
            )
        assert not any("nnz_partition" in str(w.message) for w in rec)
        r0, r1 = plain.skew_report(), sorted_ds.skew_report()
        assert r0["nnz"] == r1["nnz"]  # same data, different layout
        # the guard's point: padding collapses from ~max-row-width everywhere
        # to near-true-nnz (dense rows cluster in one shard)
        assert r1["padded_nnz"] < r0["padded_nnz"] / 5
        assert r1["pad_overhead"] < 2.5

    def test_nnz_partition_rows_faithful(self, devices8):
        from asyncframework_tpu.data.sparse import SparseShardedDataset, densify

        indptr, indices, values, y = _skewed_csr(n=64, d=40, dense_nnz=30)
        ds = SparseShardedDataset(indptr, indices, values, y, 40, 8,
                                  devices=devices8, nnz_partition=True)
        Xp, yp = densify(ds)
        # reconstruct the original dense matrix and compare row-by-row via
        # the recorded permutation
        X0 = np.zeros((64, 40), np.float32)
        for i in range(64):
            X0[i, indices[indptr[i]:indptr[i + 1]]] = (
                values[indptr[i]:indptr[i + 1]]
            )
        np.testing.assert_allclose(Xp, X0[ds.row_perm], rtol=1e-6)
        np.testing.assert_allclose(yp, y[ds.row_perm], rtol=1e-6)

    def test_solver_runs_on_nnz_partitioned_data(self, devices8):
        from asyncframework_tpu.data.sparse import SparseShardedDataset, densify

        # planted labels so convergence is meaningful
        indptr, indices, values, _ = _skewed_csr(n=800, d=64, base_nnz=4,
                                                 dense_every=100, dense_nnz=48)
        rs = np.random.default_rng(1)
        w_true = rs.normal(size=64).astype(np.float32)
        X0 = np.zeros((800, 64), np.float32)
        for i in range(800):
            X0[i, indices[indptr[i]:indptr[i + 1]]] = (
                values[indptr[i]:indptr[i + 1]]
            )
        y = (X0 @ w_true + 0.01 * rs.normal(size=800)).astype(np.float32)
        ds = SparseShardedDataset(indptr, indices, values, y, 64, 8,
                                  devices=devices8, nnz_partition=True)
        cfg = SolverConfig(
            num_workers=8, num_iterations=300, gamma=0.5, batch_rate=0.3,
            bucket_ratio=0.5, printer_freq=50, seed=42,
            calibration_iters=10, run_timeout_s=120.0,
        )
        res = ASGD(ds, None, cfg, devices=devices8).run()
        assert res.trajectory[-1][1] < res.trajectory[0][1] * 0.5
