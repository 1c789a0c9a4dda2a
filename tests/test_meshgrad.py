"""Multi-chip mesh gradient plane (ISSUE 11): shard_map batch-parallel
worker steps, the resolve_shard_map compat shim, donated-buffer fused
apply, and the async.mesh.devices knob.

The correctness spine:

- the mesh ASGD worker step is numerically EQUAL (f32 tolerance 0) to
  the single-device computation of the same batch: identical Bernoulli
  draw (replicated full-length mask, device-count-invariant) and a
  ``lax.psum`` whose CPU all-reduce is a sequential device-order fold --
  the oracle reproduces both on one device, bit for bit;
- the mesh ASAGA step's candidate scalars are EXACTLY the single-device
  step's (each sampled slot has one owning device; psum adds zeros);
- ``async.mesh.devices=0`` is byte-identical on the wire and
  step-identical to the knob being absent (per-op frame-byte totals
  under a fixed seed);
- the donated fused-apply kernels are bit-identical to the undonated
  ones (donation changes aliasing, never values);
- mesh workers ride the serial AND pipelined loops to full coverage,
  clamp cleanly when the conf asks for more chips than the rig has, and
  keep exactly-once push semantics under seeded PUSH chaos.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from asyncframework_tpu.conf import AsyncConf, set_global_conf
from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.net import faults, frame, reset_net_totals
from asyncframework_tpu.net.faults import DROP_REPLY, FaultSchedule
from asyncframework_tpu.ops import steps
from asyncframework_tpu.ops.gradients import least_squares_grad_sum, mm_f32
from asyncframework_tpu.parallel import ps_dcn
from asyncframework_tpu.parallel.mesh import (
    make_mesh,
    pad_and_shard,
    resolve_shard_map,
)
from asyncframework_tpu.solvers import SolverConfig

pytestmark = pytest.mark.mesh


def make_cfg(**kw):
    defaults = dict(
        num_workers=2, num_iterations=60, gamma=1.2, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.0, printer_freq=20, seed=42,
        calibration_iters=8, run_timeout_s=120.0,
    )
    defaults.update(kw)
    return SolverConfig(**defaults)


@pytest.fixture(autouse=True)
def _clean_state():
    ps_dcn.reset_pipeline_totals()
    reset_net_totals()
    faults.clear()
    yield
    ps_dcn.reset_pipeline_totals()
    reset_net_totals()
    faults.clear()
    set_global_conf(None)


def run_dcn(devices, cfg, conf, nw=None, n=1024, d=16, seed=11,
            algo="asgd", deadline_s=120.0):
    """One in-process PS + worker-process run under ``conf``."""
    nw = nw if nw is not None else cfg.num_workers
    set_global_conf(conf)
    ds = ShardedDataset.generate_on_device(n, d, nw, devices=devices[:nw],
                                           seed=seed, noise=0.01)
    ps = ps_dcn.ParameterServer(cfg, d, n, device=devices[0], port=0,
                                algo=algo).start()
    try:
        shards = {w: ds.shard(w) for w in range(nw)}
        counts = ps_dcn.run_worker_process(
            "127.0.0.1", ps.port, list(range(nw)), shards, cfg, d, n,
            deadline_s=deadline_s, algo=algo,
        )
        done = ps.wait_done(timeout_s=10.0)
        return ps, counts, done
    finally:
        ps.stop()


# ------------------------------------------------- the shard_map entry point
class TestResolveShardMap:
    def test_resolves_on_this_install(self):
        """One entry point for the whole repo, and it is ``jax.shard_map``."""
        assert resolve_shard_map() is jax.shard_map

    def test_shimmed_psum_program_runs(self, devices8):
        """(The name predates the removal of the legacy shim; kept so the
        test's id is stable.)  A rank-0 output takes ``P()``."""
        import functools

        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(8, devices=devices8)

        @functools.partial(
            resolve_shard_map(), mesh=mesh, in_specs=P("dp"),
            out_specs=P(), check_vma=True,
        )
        def total(x):
            return jax.lax.psum(jnp.sum(x), "dp")

        out = jax.jit(total)(np.arange(64, dtype=np.float32))
        assert float(out) == float(np.arange(64).sum())


# ------------------------------------------------------- make_mesh clamp
class TestMakeMeshClamp:
    def test_default_still_raises_on_overask(self):
        avail = len(jax.devices())
        with pytest.raises(ValueError, match="devices are available"):
            make_mesh(avail + 1)

    def test_clamp_logs_and_degrades(self, caplog):
        avail = len(jax.devices())
        with caplog.at_level(logging.WARNING,
                             logger="asyncframework_tpu.parallel.mesh"):
            mesh = make_mesh(avail + 5, clamp=True)
        assert mesh.devices.size == avail
        assert any("clamping" in r.message for r in caplog.records)


# -------------------------------------------------------- step numerics
class TestMeshStepNumerics:
    def _problem(self, n=1024, d=64, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.standard_normal((n, d)).astype(np.float32)
        y = rng.standard_normal(n).astype(np.float32)
        w = rng.standard_normal(d).astype(np.float32)
        return X, y, w

    @pytest.mark.parametrize("n_dev", [2, 8])
    def test_asgd_mesh_step_equals_single_device_tol0(self, devices8,
                                                      n_dev):
        """The mesh step's gradient == the single-device computation of
        the same batch at f32 tolerance ZERO.  The oracle reproduces the
        two mesh mechanics on one device: (a) the replicated full-length
        Bernoulli draw (so the sampled rows are identical by
        construction -- and identical to make_asgd_worker_step's dense
        mask on an unpadded shard), and (b) psum's reduction order,
        which on this backend is a sequential device-order fold of the
        per-block partials (each partial computed by the SAME grad_sum
        XLA program at the block shape)."""
        X, y, w = self._problem()
        n = X.shape[0]
        assert n % n_dev == 0  # unpadded: draw identical to serial step
        mesh = make_mesh(n_dev, devices=devices8[:n_dev])
        Xs, ys, vs, _n = pad_and_shard(mesh, X, y)
        key = jax.random.fold_in(jax.random.PRNGKey(42), 7)
        step = steps.make_mesh_asgd_worker_step(0.3, mesh)
        g, key_out = step(Xs, ys, vs, jnp.asarray(w), key)
        g = np.asarray(g)

        # single-device oracle: same draw, per-block partials, seq fold
        key_ref, sub = jax.random.split(key)
        mask = np.asarray(
            jax.random.bernoulli(sub, 0.3, (n,))
        ).astype(np.float32)
        blk = n // n_dev
        parts = [
            np.asarray(least_squares_grad_sum(
                X[p * blk:(p + 1) * blk], y[p * blk:(p + 1) * blk], w,
                mask[p * blk:(p + 1) * blk],
            ))
            for p in range(n_dev)
        ]
        acc = parts[0].copy()
        for part in parts[1:]:
            acc = (acc + part).astype(np.float32)
        np.testing.assert_array_equal(g, acc)  # tolerance 0
        # the PRNG chain advances exactly like the single-device step
        np.testing.assert_array_equal(np.asarray(key_out),
                                      np.asarray(key_ref))
        # sanity: the fold is the full-batch gradient up to f32
        # reassociation noise
        g_full = np.asarray(least_squares_grad_sum(X, y, w, mask))
        np.testing.assert_allclose(g, g_full, rtol=5e-5, atol=5e-4)

    def test_saga_mesh_step_matches_single_device(self, devices8):
        """Candidate scalars are EXACT (one owner per sampled slot; the
        psum adds zeros to the owner's value) and the fused gradient
        matches the single-device step to f32 reassociation noise."""
        X, y, w = self._problem(n=1024, d=32, seed=3)
        n = X.shape[0]
        rng = np.random.default_rng(5)
        cap = 160
        idx = np.sort(rng.choice(n, cap, replace=False)).astype(np.int32)
        alpha = rng.standard_normal(cap).astype(np.float32)
        n_valid = np.int32(130)
        mesh = make_mesh(8, devices=devices8)
        Xs, ys, _vs, _n = pad_and_shard(mesh, X, y)
        mstep = steps.make_mesh_saga_dcn_worker_step(mesh)
        g, diff = mstep(Xs, ys, jnp.asarray(w), jnp.asarray(idx),
                        jnp.asarray(alpha), n_valid)
        ref = steps.make_saga_dcn_worker_step()
        g_ref, diff_ref = ref(X, y, w, idx, alpha, n_valid)
        np.testing.assert_array_equal(np.asarray(diff),
                                      np.asarray(diff_ref))
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=5e-4, atol=5e-4)
        # padding slots (>= n_valid) contribute exactly nothing
        assert not np.any(np.asarray(diff)[int(n_valid):])

    def test_mesh_step_sampling_is_device_count_invariant(self, devices8):
        """The replicated full-length draw makes the sampled row set a
        function of (key, padded length) alone: dp=2 and dp=8 meshes on
        an unpadded batch produce gradients from the SAME sample (both
        fold the same per-row terms, so they agree to reassociation
        noise -- a different sample would diverge at O(1))."""
        X, y, w = self._problem(n=512, d=16, seed=9)
        key = jax.random.fold_in(jax.random.PRNGKey(1), 0)
        outs = []
        for n_dev in (2, 8):
            mesh = make_mesh(n_dev, devices=devices8[:n_dev])
            Xs, ys, vs, _n = pad_and_shard(mesh, X, y)
            step = steps.make_mesh_asgd_worker_step(0.2, mesh)
            g, _ = step(Xs, ys, vs, jnp.asarray(w), key)
            outs.append(np.asarray(g))
        np.testing.assert_allclose(outs[0], outs[1], rtol=5e-5, atol=5e-4)


# ------------------------------------------------------- donated kernels
class TestDonatedApply:
    def test_asgd_merge_donated_bit_identical_to_undonated(self):
        rng = np.random.default_rng(0)
        d, m, n = 96, 8, 4096
        w = rng.standard_normal(d).astype(np.float32)
        G = rng.standard_normal((m, d)).astype(np.float32)
        mask = (rng.random(m) < 0.75).astype(np.float32)
        plain = steps.make_asgd_apply_merge(0.5, 0.1, n, 4)
        donated = steps.make_asgd_apply_merge(0.5, 0.1, n, 4,
                                              donate_model=True)
        w1, k1 = plain(jnp.asarray(w), jnp.asarray(G), jnp.asarray(mask),
                       jnp.float32(17.0))
        w2, k2 = donated(jnp.asarray(w), jnp.asarray(G),
                         jnp.asarray(mask), jnp.float32(17.0))
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        assert float(k1) == float(k2) == 17.0 + float(mask.sum())

    def test_saga_merge_donated_bit_identical_to_undonated(self):
        rng = np.random.default_rng(1)
        d, m, n = 64, 6, 2048
        w = rng.standard_normal(d).astype(np.float32)
        ab = rng.standard_normal(d).astype(np.float32)
        G = rng.standard_normal((m, d)).astype(np.float32)
        mask = (rng.random(m) < 0.75).astype(np.float32)
        plain = steps.make_saga_apply_merge(0.3, 0.1, n, 4)
        donated = steps.make_saga_apply_merge(0.3, 0.1, n, 4,
                                              donate_model=True)
        r1 = plain(jnp.asarray(w), jnp.asarray(ab), jnp.asarray(G),
                   jnp.asarray(mask))
        r2 = donated(jnp.asarray(w), jnp.asarray(ab), jnp.asarray(G),
                     jnp.asarray(mask))
        for a, b in zip(r1, r2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fused_drain_engages_on_contended_run(self, devices8):
        """A contended run must still exercise the (now donated) fused
        merge path -- and serve pulls / finish exactly -- proving the
        basis-redirect donation discipline holds on a live PS."""
        conf = (AsyncConf().set("async.push.merge", 8)
                .set("async.trace.sample", 0.0))
        cfg = make_cfg(num_workers=4, num_iterations=200,
                       bucket_ratio=0.5)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=4)
        assert done and ps.accepted == 200
        assert ps.merge_merged == 200
        assert ps.merge_batch_max >= 2, "fused path never engaged"


# ------------------------------------------------- knob=0 byte identity
class TestMeshKnobZeroIdentity:
    def test_devices0_conf_set_matches_unset_byte_identical(self,
                                                            devices8):
        """``async.mesh.devices=0`` is byte-identical on the wire and
        step-identical (accepted/dropped/staleness/clock) to the knob
        being absent, under a fixed seed -- the mesh plane off IS the
        legacy worker, not a lookalike."""
        results = []
        for mesh_conf in (None, "0"):
            conf = (AsyncConf().set("async.pull.mode", "full")
                    .set("async.trace.sample", 0.0))
            if mesh_conf is not None:
                conf.set("async.mesh.devices", mesh_conf)
            reset_net_totals()
            cfg = make_cfg(num_workers=1, num_iterations=40,
                           calibration_iters=10**9)
            ps, counts, done = run_dcn(devices8, cfg, conf, nw=1)
            assert done, "run did not finish"
            results.append({
                "accepted": ps.accepted,
                "dropped": ps.dropped,
                "max_staleness": ps.max_staleness,
                "clock": ps._clock,
                "pull_replies": dict(ps.pull_replies),
                "bytes": frame.bytes_totals(),
            })
        unset, zero = results
        assert unset["accepted"] == zero["accepted"] == 40
        assert unset["dropped"] == zero["dropped"]
        assert unset["max_staleness"] == zero["max_staleness"]
        assert unset["clock"] == zero["clock"]
        assert unset["pull_replies"] == zero["pull_replies"]
        assert unset["bytes"] == zero["bytes"], (unset["bytes"],
                                                 zero["bytes"])


# ------------------------------------------------------------ mesh runs
class TestMeshRuns:
    def test_serial_mesh_run_full_coverage(self, devices8):
        """Mesh workers on the serial loop: run completes exactly, every
        logical worker contributed accepted gradients, and the model
        stays finite."""
        conf = (AsyncConf().set("async.mesh.devices", 8)
                .set("async.trace.sample", 0.0))
        cfg = make_cfg(num_workers=4, num_iterations=160,
                       bucket_ratio=0.5)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=4, d=32)
        assert done and ps.accepted == 160
        for w in range(4):
            assert ps.accepted_by_wid.get(w, 0) > 0, ps.accepted_by_wid
        _times, W = ps.snapshot_stack()
        assert np.all(np.isfinite(W[-1]))

    def test_asaga_mesh_run_full_coverage(self, devices8):
        conf = (AsyncConf().set("async.mesh.devices", 8)
                .set("async.trace.sample", 0.0))
        cfg = make_cfg(num_workers=2, num_iterations=60, gamma=0.5)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=2, n=512,
                                   d=12, algo="asaga")
        assert done and ps.accepted == 60
        for w in range(2):
            assert ps.accepted_by_wid.get(w, 0) > 0, ps.accepted_by_wid

    def test_overask_clamps_and_still_completes(self, devices8):
        """A conf asking for more chips than the rig has (the dead-TPU /
        small-rig reality) must clamp and run, not crash the worker."""
        conf = (AsyncConf().set("async.mesh.devices", 64)
                .set("async.trace.sample", 0.0))
        cfg = make_cfg(num_workers=2, num_iterations=50)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=2)
        assert done and ps.accepted == 50

    def test_pipelined_mesh_run_full_coverage(self, devices8):
        """Mesh x pipelining (the PR 5 interaction): prefetched pulls
        stage the replicated model over the mesh while the previous
        step's psum runs; the run completes exactly with every worker
        contributing and the pipeline counters engaged."""
        conf = (AsyncConf().set("async.pull.mode", "delta")
                .set("async.pipeline.depth", 2)
                .set("async.mesh.devices", 8)
                .set("async.trace.sample", 0.0))
        cfg = make_cfg(num_workers=4, num_iterations=200,
                       bucket_ratio=0.5)
        ps, counts, done = run_dcn(devices8, cfg, conf, nw=4, d=32)
        assert done, "pipelined mesh run did not finish"
        assert ps.accepted == 200
        for w in range(4):
            assert ps.accepted_by_wid.get(w, 0) > 0, ps.accepted_by_wid
        pl = ps_dcn.pipeline_totals()
        assert pl.get("pushes_async", 0) >= 200
        assert (pl.get("prefetch_hits", 0)
                + pl.get("prefetch_waits", 0)) >= 200


class TestMeshConvergenceTelemetry:
    def test_conv_samples_fold_with_mesh_on(self, devices8):
        """Regression (review finding): the convergence sampler's
        shard-loss eval runs on the shard's own device -- handing it the
        mesh-replicated model handle raised an incompatible-devices
        error that conv_sample's protective except swallowed, silently
        blanking the PR 7 loss curves for every mesh run.  A mesh run
        with sampling on must fold a non-empty convergence history."""
        from asyncframework_tpu.metrics import timeseries as ts_mod

        ts_mod.convergence().reset()
        conf = (AsyncConf().set("async.mesh.devices", 8)
                .set("async.convergence.sample", 5)
                .set("async.trace.sample", 0.0))
        cfg = make_cfg(num_workers=2, num_iterations=60)
        try:
            ps, counts, done = run_dcn(devices8, cfg, conf, nw=2, d=32)
            assert done and ps.accepted == 60
            curves = ts_mod.convergence().curves()
            pts = curves.get("loss_vs_version") or curves.get(
                next(iter(curves), ""), [])
            assert pts, f"no convergence samples folded: {curves}"
            assert all(np.isfinite(p[1]) for p in pts)
        finally:
            ts_mod.convergence().reset()


# -------------------------------------------------------------- chaos
class TestMeshChaos:
    def test_push_drop_reply_exactly_once_with_mesh_worker(self,
                                                           devices8):
        """Seeded drop_reply on PUSH against a mesh worker: the retried
        push must be answered from the dedup window, never re-applied --
        the mesh plane changes WHERE the gradient is computed, not the
        wire's exactly-once contract."""
        conf = (AsyncConf().set("async.mesh.devices", 8)
                .set("async.trace.sample", 0.0))
        set_global_conf(conf)
        n, d, nw = 1024, 16, 2
        cfg = make_cfg(num_workers=nw, num_iterations=80)
        ds = ShardedDataset.generate_on_device(
            n, d, nw, devices=devices8[:nw], seed=11, noise=0.01,
        )
        ps = ps_dcn.ParameterServer(cfg, d, n, device=devices8[0],
                                    port=0).start()
        ep = f"127.0.0.1:{ps.port}"
        sched = (FaultSchedule(seed=13)
                 .add(ep, "PUSH", 4, DROP_REPLY)
                 .add(ep, "PUSH", 11, DROP_REPLY)
                 .add(ep, "PUSH", 17, DROP_REPLY))
        try:
            with faults.injected(sched) as inj:
                shards = {w: ds.shard(w) for w in range(nw)}
                counts = ps_dcn.run_worker_process(
                    "127.0.0.1", ps.port, list(range(nw)), shards, cfg,
                    d, n, deadline_s=120.0,
                )
                done = ps.wait_done(timeout_s=10.0)
                assert done, "mesh chaos run did not finish"
                assert ps.accepted == 80
                # exactly-once: every merged push maps to one computed
                # gradient (a double-applied retry would break this)
                assert ps._clock <= sum(counts.values()), (
                    ps._clock, counts,
                )
                # dropped ACKs forced retries of already-applied pushes:
                # the dedup window must have answered them
                assert ps.dedup_hits >= 1
                assert inj.remaining() == [], "all faults must fire"
        finally:
            ps.stop()
