"""ASAGA against the benchmark's plain reference (``benchmark/reference_saga``).

The program's history table, its running mean ``alpha_bar`` and its model
are held to a sequential float32 replay that shares no code with it, on
seeded data, with rows stored f32 and bf16.  The guarantee under test:
``alpha_bar`` is the mean history gradient of the table as it stands, after
every update, whatever overlapped in flight.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from asyncframework_tpu.data.sharded import ShardedDataset  # noqa: E402
from asyncframework_tpu.ops import steps  # noqa: E402
from asyncframework_tpu.solvers import ASAGA  # noqa: E402
from asyncframework_tpu.solvers.base import SolverConfig  # noqa: E402
from benchmark import reference_saga  # noqa: E402

N, D, NW, B, SEED = 4096, 32, 4, 0.2, 11
DTYPES = [jnp.float32, jnp.bfloat16]
IDS = ["f32", "bf16"]


def _solve(dtype, devices=None, **cfg):
    """A seeded dataset on ``devices`` (the first, by default) and the
    solver over it."""
    devices = list(devices or jax.devices()[:1])
    ds = ShardedDataset.generate_on_device(
        N, D, NW, devices, seed=SEED, noise=0.01, dtype=dtype,
    )
    return ds, ASAGA(ds, None, _cfg(**cfg), devices=devices)


def _cfg(**kw):
    base = dict(num_workers=NW, num_iterations=10, gamma=0.4, taw=2**31 - 1,
                batch_rate=B, bucket_ratio=0.7, printer_freq=1000, coeff=0.0,
                seed=SEED, run_timeout_s=120.0)
    base.update(kw)
    return SolverConfig(**base)


def _masks(rounds, rows):
    """The masks the program's steps draw: a key chain a worker, folded
    from the run's seed, split once a task (``make_saga_worker_step``)."""
    keys = [jax.random.fold_in(jax.random.PRNGKey(SEED), w) for w in range(NW)]
    out = []
    for _ in range(rounds):
        for w in range(NW):
            keys[w], sub = jax.random.split(keys[w])
            out.append(np.asarray(
                jax.random.bernoulli(sub, B, (rows[w],)), np.float32))
    return out


def _near(got, want, tol, what, scale=None):
    """``max |got - want|`` within ``tol`` of ``scale`` (``max |want|``)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want))) if scale is None else scale
    assert err <= tol * scale, f"{what}: {err:.3e} off at scale {scale:.3e}"


def _gradient_at_zero(shards):
    """``max |X^T y / n|`` by the reference: the unit of ``history_drift``."""
    return float(np.max(np.abs(reference_saga.history_mean(
        shards, [s.y for s in shards], N, block_rows=512))))


#: f32 rows: program and replay do the same f32 arithmetic in another order
#: (whole-shard products against 512-row blocks, a donated accumulator
#: against a sum): 10 rounds of d=32 sums of 1,024 terms sat at 0.7e-7 to
#: 1.2e-7 of the largest entry on the CPU; 5e-6 leaves room for a backend
#: that orders its sums otherwise, and none for a lower precision.
TOL_F32 = 5e-6
#: bf16 rows: the program's ``X w`` casts ``w`` to the rows' dtype
#: (``gradients.mm_f32``: one rounding of relative 2^-9 an entry on a
#: backend that rounds there, as the CPU does; the TPU drops the pair), the
#: replay does not; ``diff`` and through it the table, ``w`` and
#: ``alpha_bar`` inherit that: 0.6e-4 on ``w``, 0.9e-4 on ``alpha_bar``,
#: 1.5e-4 to 1.9e-4 on the slices measured.  Neither ``g`` nor the delta
#: rounds its vector.  This comparison canNOT tell a delta that does
#: (1.6e-4 on ``alpha_bar``): that noise is no larger than the rounding of
#: ``w`` it allows.  What is tight enough for that is the invariant
#: (``DRIFT_TOL`` below), where the rounded delta reads a thousand times
#: the exact one, in ``run_sync`` too (6.6e-8 with bf16 rows).
TOL_BF16 = 1e-3
#: ``alpha_bar`` against the mean of the table it summarises, over the mean
#: gradient at ``w = 0`` (``max |X^T y / n|``: the scale of what
#: ``alpha_bar`` held early in the run, when rounding left its mark; ``max
#: |alpha_bar|`` itself goes to zero with the run).  Both are f32 sums:
#: 1e-7 to 2e-7 measured after 40 and after 300 updates.  A delta that
#: rounds its vector to bf16 reads 1.3e-4 to 2e-4; the reference drivers'
#: ``delta == g`` under overlap more.
DRIFT_TOL = 5e-6


@pytest.mark.parametrize("dtype", DTYPES, ids=IDS)
def test_run_sync_equals_the_sequential_replay(dtype):
    rounds = 10
    ds, solver = _solve(dtype, num_iterations=rounds)
    res = solver.run_sync()
    assert res.accepted == rounds * NW
    shards = [ds.shard(w) for w in range(NW)]
    rows = [int(s.X.shape[0]) for s in shards]
    ref = reference_saga.saga_replay(
        shards, _masks(rounds, rows), list(range(NW)) * rounds,
        gamma=0.4, batch_rate=B, n=N, group=NW, block_rows=512,
    )
    tol = TOL_F32 if dtype == jnp.float32 else TOL_BF16
    _near(res.final_w, ref["w"], tol, "w")
    _near(res.extras["alpha_bar"], ref["alpha_bar"], tol, "alpha_bar")
    for w in range(NW):
        _near(res.extras["alpha"][w], ref["alpha"][w], tol, f"alpha[{w}]")
    # and the replay keeps its own invariant to f32 rounding
    mean = reference_saga.history_mean(shards, ref["alpha"], N, block_rows=512)
    _near(ref["alpha_bar"], mean, 1e-5, "the replay's alpha_bar")
    # so does the sync drain, which takes the workers' ``g`` for the delta
    assert 0.0 <= res.extras["history_drift"] <= DRIFT_TOL


def test_a_delta_that_rounds_its_vector_breaks_the_invariant(monkeypatch):
    """The negative control of ``DRIFT_TOL``: with the table delta's vector
    cast to the shard's bf16 (``mm_f32``), on every accept, ``alpha_bar``
    leaves the table's mean by ten times the tolerance and more, by the
    reference's count and by the program's own."""
    from asyncframework_tpu.ops.gradients import mm_f32

    def rounding_delta():
        @jax.jit
        def saga_table_delta(X, diff, mask, alpha_cur):
            return mm_f32(X.T, mask * (diff - alpha_cur))

        return saga_table_delta

    monkeypatch.setattr(steps, "make_saga_table_delta", rounding_delta)
    ds, solver = _solve(jnp.bfloat16, num_iterations=300)
    # every accept on the side that computes the delta (a task whose slice
    # still stands takes its step's ``g``): no task's commit count matches
    real = solver._make_task
    solver._make_task = lambda wid, w, key, a, _commits, *rest: real(
        wid, w, key, a, -1, *rest)
    res = solver.run()
    assert res.extras["history_recomputed"] == res.accepted
    shards = [ds.shard(w) for w in range(NW)]
    alphas = [res.extras["alpha"][w] for w in range(NW)]
    mean = reference_saga.history_mean(shards, alphas, N, block_rows=512)
    with pytest.raises(AssertionError):
        _near(res.extras["alpha_bar"], mean, 10 * DRIFT_TOL, "alpha_bar",
              _gradient_at_zero(shards))
    assert res.extras["history_drift"] > 10 * DRIFT_TOL


@pytest.mark.parametrize("dtype", DTYPES, ids=IDS)
def test_async_run_keeps_alpha_bar_the_mean_of_its_table(dtype, devices8):
    """The asynchronous engine, tasks overlapping commits (a worker is
    handed its slice again before its last result was committed): the
    final ``alpha_bar`` is the table's mean by the reference's count, on one
    device and with the slices spread over four."""
    for devs in (devices8[:1], devices8[:4]):
        ds, solver = _solve(dtype, devs, num_iterations=300)
        res = solver.run()
        assert res.accepted == 300
        shards = [ds.shard(w) for w in range(NW)]
        alphas = [res.extras["alpha"][w] for w in range(NW)]
        mean = reference_saga.history_mean(shards, alphas, N, block_rows=512)
        _near(res.extras["alpha_bar"], mean, DRIFT_TOL, "alpha_bar",
              _gradient_at_zero(shards))
        # the program's own reading of the same distance
        assert 0.0 <= res.extras["history_drift"] <= DRIFT_TOL
        # every accepted update went through the history path
        assert 0.0 < res.extras["updater_history_s"] <= res.extras[
            "updater_apply_s"]


@pytest.mark.parametrize("dtype", DTYPES, ids=IDS)
def test_delta_equals_g_bit_for_bit_while_the_slice_is_unchanged(dtype):
    """The worker's ``g`` is the table's exact change as long as the slice
    it was computed against is still the current one, whatever the rows are
    stored in (both keep their vector in f32 and promote the shard): the
    shortcut ``delta = g`` of the sync drain, ``run_fused`` and the DCN
    plane rests on it."""
    ds, _solver = _solve(dtype)
    shard = ds.shard(0)
    rs = np.random.default_rng(3)
    rows = int(shard.X.shape[0])
    # a model that bf16 holds exactly: ``X w`` casts ``w`` to the rows'
    # dtype (``mm_f32``; the CPU rounds there), which is not under test
    w = jnp.asarray(rs.standard_normal(D), jnp.bfloat16).astype(jnp.float32)
    alpha = jnp.asarray(rs.standard_normal(rows), jnp.float32)
    g, diff, mask, _key = steps.make_saga_worker_step(B)(
        shard.X, shard.y, w, alpha, jax.random.PRNGKey(5))
    delta = steps.make_saga_table_delta()(shard.X, diff, mask, alpha)
    assert np.array_equal(np.asarray(g), np.asarray(delta))
    # against a slice that moved on, it is not
    moved = steps.make_saga_table_delta()(shard.X, diff, mask, alpha + 1.0)
    assert not np.array_equal(np.asarray(g), np.asarray(moved))
    # and the reference computes the same three vectors
    ref = reference_saga.task(shard, w, alpha, alpha, np.asarray(mask),
                              block_rows=300)
    _near(g, ref["g"], TOL_F32, "g")
    _near(delta, ref["delta"], TOL_F32, "delta")
    _near(diff, ref["diff"], TOL_F32, "diff")
    committed = steps.saga_commit_history(alpha, diff, mask)
    _near(committed, ref["alpha"], TOL_F32, "the committed slice")
