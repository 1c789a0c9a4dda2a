"""Gradient / BLAS / sampling / collective op tests.

Parity with the reference's algorithm-level tests
(``GradientDescentSuite.scala:67-185``): exact gradients against closed form,
plus determinism of the seeded sampling protocol.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from asyncframework_tpu.ops import blas, collectives, gradients, sampling
from asyncframework_tpu.parallel import make_mesh, shard_batch


class TestBlas:
    def test_axpy_inplace(self):
        y = np.array([1.0, 2.0, 3.0])
        x = np.array([1.0, 1.0, 1.0])
        out = blas.axpy_op(2.0, x, y)
        assert out is y  # in place, like BLASUtil.axpyOp
        np.testing.assert_allclose(y, [3.0, 4.0, 5.0])

    def test_axpy_unit_scale(self):
        y = np.ones(4)
        out = blas.axpy_op(1.0, np.arange(4.0), y)
        np.testing.assert_allclose(out, [1, 2, 3, 4])

    def test_dot_scal(self):
        x = np.array([1.0, 2.0])
        assert blas.dot_op(x, x) == pytest.approx(5.0)
        out = blas.scal_op(0.5, x)
        assert out is x
        np.testing.assert_allclose(x, [0.5, 1.0])

    def test_readonly_buffers_fall_back_out_of_place(self):
        # np.asarray(jax_array) exposes device buffers read-only; the updater
        # hot loop must not crash on them.
        g = np.asarray(jnp.arange(4.0))
        assert not g.flags.writeable
        out = blas.scal_op(2.0, g)
        np.testing.assert_allclose(out, [0, 2, 4, 6])
        w = np.asarray(jnp.ones(4))
        out2 = blas.axpy_op(0.5, g, w)
        np.testing.assert_allclose(out2, [1, 1.5, 2, 2.5])

    def test_jax_arrays_supported(self):
        y = jnp.ones(3)
        out = blas.axpy_op(2.0, jnp.arange(3.0), y)
        np.testing.assert_allclose(np.asarray(out), [1, 3, 5])


class TestGradients:
    def test_least_squares_exact(self, tiny_problem):
        X, y, _ = tiny_problem
        w = np.full(X.shape[1], 0.1, np.float32)
        mask = np.ones(X.shape[0], np.float32)
        g = gradients.least_squares_grad_sum(X, y, w, mask)
        expected = X.T @ (X @ w - y)
        np.testing.assert_allclose(np.asarray(g), expected, rtol=2e-4)

    def test_least_squares_masked_equals_subset(self, tiny_problem):
        X, y, _ = tiny_problem
        w = np.full(X.shape[1], -0.3, np.float32)
        mask = np.zeros(X.shape[0], np.float32)
        mask[::3] = 1.0
        g = gradients.least_squares_grad_sum(X, y, w, mask)
        sub = np.flatnonzero(mask)
        expected = X[sub].T @ (X[sub] @ w - y[sub])
        np.testing.assert_allclose(np.asarray(g), expected, rtol=2e-4, atol=1e-3)

    def test_per_sample_gradfun_parity(self):
        # gradfun(p, w) = (x.w - y) * x summed over batch == matmul form
        rs = np.random.default_rng(1)
        X = rs.normal(size=(10, 4)).astype(np.float32)
        y = rs.normal(size=(10,)).astype(np.float32)
        w = rs.normal(size=(4,)).astype(np.float32)
        per_sample = sum((X[i] @ w - y[i]) * X[i] for i in range(10))
        g = gradients.least_squares_grad_sum(X, y, w, np.ones(10, np.float32))
        np.testing.assert_allclose(np.asarray(g), per_sample, rtol=1e-4)

    def test_logistic_grad_matches_autodiff(self, tiny_problem):
        X, y, _ = tiny_problem
        yb = (y > 0).astype(np.float32)
        w = np.full(X.shape[1], 0.05, np.float32)
        mask = np.ones(X.shape[0], np.float32)
        g = gradients.logistic_grad_sum(X, yb, w, mask)
        auto = jax.grad(lambda w_: gradients.logistic_loss(X, yb, w_))(jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(g), np.asarray(auto), rtol=1e-3, atol=1e-3)

    def test_loss_decreases_under_gd(self, tiny_problem):
        # "loss is decreasing" -- GradientDescentSuite parity
        X, y, _ = tiny_problem
        n = X.shape[0]
        w = np.zeros(X.shape[1], np.float32)
        mask = np.ones(n, np.float32)
        losses = []
        for _ in range(20):
            losses.append(float(gradients.least_squares_loss(X, y, w)) / n)
            g = np.asarray(gradients.least_squares_grad_sum(X, y, w, mask))
            w -= 0.01 / n * g
        assert all(b < a for a, b in zip(losses, losses[1:]))

    def test_saga_shard_step(self):
        rs = np.random.default_rng(2)
        X = rs.normal(size=(12, 5)).astype(np.float32)
        y = rs.normal(size=(12,)).astype(np.float32)
        w = rs.normal(size=(5,)).astype(np.float32)
        alpha = rs.normal(size=(12,)).astype(np.float32)
        mask = (rs.random(12) < 0.5).astype(np.float32)
        g, diff = gradients.saga_shard_step(X, y, w, alpha, mask)
        np.testing.assert_allclose(np.asarray(diff), X @ w - y, rtol=1e-4)
        expected = X.T @ (mask * ((X @ w - y) - alpha))
        np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-4, atol=1e-4)
        committed = gradients.saga_commit_history(alpha, diff, mask)
        np.testing.assert_allclose(
            np.asarray(committed), np.where(mask > 0, X @ w - y, alpha), rtol=1e-4
        )


class TestSampling:
    def test_mask_deterministic(self):
        m1 = sampling.host_mask(42, 7, 3, 1000, 0.1)
        m2 = sampling.host_mask(42, 7, 3, 1000, 0.1)
        np.testing.assert_array_equal(m1, m2)

    def test_mask_varies_by_round_and_worker(self):
        base = sampling.host_mask(42, 7, 3, 1000, 0.1)
        assert not np.array_equal(base, sampling.host_mask(42, 8, 3, 1000, 0.1))
        assert not np.array_equal(base, sampling.host_mask(42, 7, 4, 1000, 0.1))

    def test_mask_rate(self):
        m = sampling.host_mask(0, 0, 0, 20000, 0.1)
        assert abs(m.mean() - 0.1) < 0.01

    def test_driver_worker_agreement(self):
        """The driver can reproduce a worker's draw exactly (ASAGA cTime parity)."""
        key = sampling.worker_key(42, 11, 5)
        on_worker = np.asarray(sampling.bernoulli_mask(key, 256, 0.3))
        on_driver = sampling.host_mask(42, 11, 5, 256, 0.3)
        np.testing.assert_array_equal(on_worker, on_driver)


class TestCollectives:
    def test_tree_combine_matches_fold(self):
        xs = [np.full(3, float(i)) for i in range(9)]
        out = collectives.tree_combine(xs, lambda a, b: a + b)
        np.testing.assert_allclose(out, np.full(3, sum(range(9))))

    def test_tree_combine_empty_raises(self):
        with pytest.raises(ValueError):
            collectives.tree_combine([], lambda a, b: a + b)

    def test_data_parallel_grad_matches_single_device(self, devices8, tiny_problem):
        X, y, _ = tiny_problem
        mesh = make_mesh(8, devices=devices8)
        w = np.full(X.shape[1], 0.2, np.float32)
        mask = np.ones(X.shape[0], np.float32)
        fn = collectives.data_parallel_grad_fn(
            gradients.least_squares_grad_sum, mesh
        )
        Xs, ys, ms = shard_batch(mesh, X, y, mask)
        g = fn(Xs, ys, jnp.asarray(w), ms)
        expected = X.T @ (X @ w - y)
        np.testing.assert_allclose(np.asarray(g), expected, rtol=2e-4, atol=1e-2)


#: the fold against the serial path: the same subtractions in the same
#: order, so what may differ is how a compiler contracts ``w - c * g``
#: inside one fused chain: under 1e-6 of the model's largest element (f32
#: eps is 1.2e-7; bit-equal on the CPU backend, 3e-8 of 4.1 measured on
#: the v5e over 32 slots, PR 31)
FOLD_RTOL = 1e-6


class TestFoldedApply:
    ARITY = 8

    def _problem(self, seed, count):
        rs = np.random.default_rng(seed)
        d = 32
        w0 = rs.normal(size=d).astype(np.float32)
        gs = [jnp.asarray(rs.normal(size=d).astype(np.float32))
              for _ in range(count)]
        return w0, gs, jnp.zeros(d, jnp.float32)

    @pytest.mark.parametrize("m", range(ARITY + 1))
    def test_fold_matches_serial(self, m):
        """The first ``m`` slots of the tuple applied in one dispatch give
        the serial path's model and, to the bit, its counter; the slots
        past ``m`` (the updater's zero padding) change nothing."""
        from asyncframework_tpu.ops import steps

        gamma, b, n, nw = 0.7, 0.1, 10_000, self.ARITY
        w0, gs, zero = self._problem(m, m)
        apply_one = steps.make_asgd_apply(gamma, b, n, nw)
        w_seq, k = jnp.asarray(w0), jnp.float32(37.0)
        for g in gs:
            w_seq, k = apply_one(w_seq, jnp.array(g), k)  # g is donated

        fold = steps.make_asgd_apply_fold(gamma, b, n, nw)
        w_fold, k_fold = fold(
            jnp.asarray(w0), tuple(gs) + (zero,) * (nw - m), jnp.float32(m),
            jnp.float32(37.0),
        )
        np.testing.assert_allclose(
            np.asarray(w_fold), np.asarray(w_seq), rtol=0,
            atol=FOLD_RTOL * float(np.max(np.abs(w_seq))),
        )
        assert float(k_fold) == float(k) == 37.0 + m

    def test_fold_ignores_what_lies_past_the_count(self):
        """Which slots count is DATA: the same full tuple with a smaller
        count applies only its first slots, through the same executable."""
        from asyncframework_tpu.ops import steps

        nw = self.ARITY
        w0, gs, _zero = self._problem(1, nw)
        fold = steps.make_asgd_apply_fold(0.5, 0.1, 1000, nw)
        apply_one = steps.make_asgd_apply(0.5, 0.1, 1000, nw)
        w_seq, k = jnp.asarray(w0), jnp.float32(0.0)
        for g in gs[:3]:
            w_seq, k = apply_one(w_seq, jnp.array(g), k)
        w_fold, k_fold = fold(
            jnp.asarray(w0), tuple(gs), jnp.float32(3), jnp.float32(0.0)
        )
        np.testing.assert_allclose(
            np.asarray(w_fold), np.asarray(w_seq), rtol=0,
            atol=FOLD_RTOL * float(np.max(np.abs(w_seq))),
        )
        assert float(k_fold) == 3.0

    def test_fold_never_donates_the_model(self):
        from asyncframework_tpu.ops import steps

        nw = self.ARITY
        w0, gs, _zero = self._problem(2, nw)
        fold = steps.make_asgd_apply_fold(0.5, 0.1, 1000, nw)
        w = jnp.asarray(w0)
        fold(w, tuple(gs), jnp.float32(nw), jnp.float32(0.0))
        # an old handle is a model version: still readable, as are the
        # gradients (the padding repeats one buffer)
        np.testing.assert_array_equal(np.asarray(w), w0)
        assert not any(g.is_deleted() for g in gs)




class TestFoldedSagaApply:
    """``steps.make_saga_apply_fold`` (ISSUE 60): the first ``m`` slots of
    two tuples of handles through the serial recurrence in one dispatch."""

    ARITY = 8

    def _problem(self, seed, count):
        rs = np.random.default_rng(seed)
        d = 48

        def vec():
            return jnp.asarray(rs.normal(size=d).astype(np.float32))

        return (vec(), vec(), [vec() for _ in range(count)],
                [vec() for _ in range(count)], jnp.zeros(d, jnp.float32))

    @staticmethod
    def _close(got, want):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=FOLD_RTOL * float(np.max(np.abs(want))))

    @pytest.mark.parametrize("m", range(ARITY + 1))
    def test_fold_matches_serial(self, m):
        """``alpha_bar`` moves between a drain's steps, and step ``j``
        subtracts it as step ``j - 1`` left it: the fold gives the serial
        path's model AND mean; the slots past ``m`` change neither."""
        from asyncframework_tpu.ops import steps

        gamma, b, n, nw = 0.7, 0.1, 10_000, self.ARITY
        w0, ab0, gs, deltas, zero = self._problem(m, m)
        apply_one = steps.make_saga_apply(gamma, b, n, nw, donate_g=False)
        w_seq, ab_seq = w0, jnp.array(ab0)  # alpha_bar is donated
        for g, delta in zip(gs, deltas):
            w_seq, ab_seq = apply_one(w_seq, ab_seq, g, delta)
        fold = steps.make_saga_apply_fold(gamma, b, n, nw)
        pad = (zero,) * (nw - m)
        w_fold, ab_fold = fold(w0, jnp.array(ab0), tuple(gs) + pad,
                               tuple(deltas) + pad, jnp.float32(m))
        self._close(w_fold, w_seq)
        self._close(ab_fold, ab_seq)
        if m == 0:
            np.testing.assert_array_equal(np.asarray(w_fold), np.asarray(w0))
            np.testing.assert_array_equal(np.asarray(ab_fold),
                                          np.asarray(ab0))

    def test_one_handle_may_ride_in_both_tuples(self):
        """Where an accept reused its step's ``g`` for the table delta the
        same buffer is both operands; which slots count is DATA."""
        from asyncframework_tpu.ops import steps

        nw = self.ARITY
        w0, ab0, gs, _deltas, _zero = self._problem(3, nw)
        apply_one = steps.make_saga_apply(0.5, 0.1, 1000, nw, donate_g=False)
        w_seq, ab_seq = w0, jnp.array(ab0)
        for g in gs[:5]:
            w_seq, ab_seq = apply_one(w_seq, ab_seq, g, g)
        fold = steps.make_saga_apply_fold(0.5, 0.1, 1000, nw)
        w_fold, ab_fold = fold(w0, jnp.array(ab0), tuple(gs), tuple(gs),
                               jnp.float32(5))
        self._close(w_fold, w_seq)
        self._close(ab_fold, ab_seq)

    def test_fold_donates_the_mean_and_nothing_else(self):
        from asyncframework_tpu.ops import steps

        nw = self.ARITY
        w0, ab0, gs, deltas, _zero = self._problem(2, nw)
        fold = steps.make_saga_apply_fold(0.5, 0.1, 1000, nw)
        kept = np.asarray(w0).copy()
        fold(w0, ab0, tuple(gs), tuple(deltas), jnp.float32(nw))
        # an old handle is a model version: still readable, as are the
        # drain's handles (the padding repeats one buffer)
        np.testing.assert_array_equal(np.asarray(w0), kept)
        assert not any(a.is_deleted() for a in gs + deltas)
