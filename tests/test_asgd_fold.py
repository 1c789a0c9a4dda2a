"""ASGD's updater applies a drain in ONE dispatch (ISSUE 31): whatever is
queued when it wakes is folded into one jitted chain of the serial path's
subtractions, split only where a snapshot is due.

The cases build their own backlog: ``held_updater`` (``conftest.py``) keeps
the updater asleep until a whole fleet of results is queued (the
submitter's backlog bound stops there), and spies on the two apply callables record, in drain order, what
every dispatch was given and what it returned."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.context import AsyncContext
from asyncframework_tpu.data import make_regression
from asyncframework_tpu.ops import steps
from asyncframework_tpu.solvers import ASGD, SolverConfig, engine_loop
from asyncframework_tpu.solvers.instrumentation import compiles_so_far

#: the fold runs the serial path's subtractions in the serial path's order:
#: on the CPU backend the replay of a whole run comes out bit-equal; the
#: tolerance is the one stated for a single dispatch where a compiler
#: contracts ``w - c * g`` otherwise (``tests/test_ops.py: FOLD_RTOL``,
#: relative to the model's largest element; 7e-9 measured on the v5e)
RUN_RTOL = 1e-6


@pytest.fixture(scope="module")
def problem():
    X, y, _ = make_regression(2048, 16, seed=11)
    return X, y


def _cfg(**kw):
    base = dict(
        num_workers=8, num_iterations=100, gamma=0.5, taw=2**31 - 1,
        batch_rate=0.3, bucket_ratio=0.5, printer_freq=10, seed=5,
        calibration_iters=4, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


@pytest.fixture()
def engine_runs(monkeypatch):
    """Every ``EngineRun`` built during the test (its ``snapshots`` hold
    the trajectory's model handles)."""
    seen = []
    real_init = engine_loop.EngineRun.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        seen.append(self)

    monkeypatch.setattr(engine_loop.EngineRun, "__init__", init)
    return seen


class Dispatches:
    """Spies on ``engine._apply`` and ``engine._apply_fold``: one record a
    dispatch of the timed run, in order: ``(kind, gradients that counted,
    k before, w after, k after)``, all on the host.  Where the model lives
    on several devices every drain is the same dispatch on each of them:
    the records are the driver's device's (that the others hold the same
    bits is ``tests/test_model_replicas.py``'s)."""

    def __init__(self, engine, monkeypatch):
        self.records = []
        self.armed = False
        real_apply, real_fold = engine._apply, engine._apply_fold
        driver = engine.driver_device

        def apply(w, g, k):
            if not self.armed or w.device != driver:
                return real_apply(w, g, k)
            g_host, k0 = np.array(g), float(k)  # g and k are donated
            w2, k2 = real_apply(w, g, k)
            self.records.append(
                ("apply", [g_host], k0, np.asarray(w2), float(k2))
            )
            return w2, k2

        def fold(w, gs, m, k):
            if not self.armed or w.device != driver:
                return real_fold(w, gs, m, k)
            assert len(gs) == engine.cfg.num_workers  # ONE arity
            live, k0 = [np.array(g) for g in gs[:int(m)]], float(k)
            # what pads a short drain is zeros, and one handle
            assert all(g is gs[-1] for g in gs[int(m):])
            if int(m) < len(gs):
                assert not np.any(np.asarray(gs[-1]))
            w2, k2 = real_fold(w, gs, m, k)
            self.records.append(
                ("fold", live, k0, np.asarray(w2), float(k2))
            )
            return w2, k2

        engine._apply, engine._apply_fold = apply, fold
        # the solver's own warm-up dispatches both before the clock starts
        real_clock = engine_loop.EngineRun.start_clock

        def start_clock(run):
            self.armed = True
            return real_clock(run)

        monkeypatch.setattr(engine_loop.EngineRun, "start_clock", start_clock)

    @property
    def updates(self):
        return sum(len(r[1]) for r in self.records)


def _serial_replay(cfg, n, d, records):
    """The serial path over the recorded gradients: the model after every
    update (index u holds the model after update u; index 0 is w = 0) and
    the device counter after each."""
    apply_one = steps.make_asgd_apply(
        cfg.gamma, cfg.batch_rate, n, cfg.num_workers
    )
    w, k = jnp.zeros(d, jnp.float32), jnp.float32(0.0)
    models, ks = [np.asarray(w)], [0.0]
    for _kind, gs, _k0, _w2, _k2 in records:
        for g in gs:
            w, k = apply_one(w, jnp.asarray(g), k)
            models.append(np.asarray(w))
            ks.append(float(k))
    return models, ks


def _close(a, b):
    np.testing.assert_allclose(
        a, b, rtol=0, atol=RUN_RTOL * max(1.0, float(np.max(np.abs(b))))
    )


# ---------------------------------------------------------- (i) the arithmetic
@pytest.mark.parametrize("nw,freq,taw", [
    (8, 10, 2**31 - 1),   # every result accepted; a boundary every 10
    (8, 5, 2**31 - 1),    # printer_freq under nw: a drain may cross two
    (8, 10, 5),           # the tau filter drops some slots of a drain
    (4, 7, 2**31 - 1),
    (16, 12, 11),
])
def test_a_folded_drain_is_the_serial_path(nw, freq, taw, problem, held_updater,
                                           monkeypatch):
    X, y = problem
    cfg = _cfg(num_workers=nw, printer_freq=freq, taw=taw,
               num_iterations=12 * nw)
    engine = ASGD(X, y, cfg)
    spies = Dispatches(engine, monkeypatch)
    held_updater(nw)
    res = engine.run()
    assert res.accepted == cfg.num_iterations == spies.updates
    if taw < 2**31 - 1:
        assert res.dropped > 0  # a dropped slot lay among the accepted
    models, ks = _serial_replay(cfg, X.shape[0], X.shape[1], spies.records)
    kinds = [r[0] for r in spies.records]
    assert "fold" in kinds
    at = 0
    for kind, gs, k0, w2, k2 in spies.records:
        m = len(gs)
        assert (kind == "apply") == (m == 1)  # a drain of one: as ever
        assert 1 <= m <= nw
        assert k0 == float(at) == ks[at]      # k to the bit
        at += m
        assert k2 == float(at) == ks[at]
        _close(w2, models[at])
        # a dispatch may END on a snapshot's update j * freq + 1 and never
        # reaches past one
        assert not any((u - 1) % freq == 0 for u in range(int(k0) + 1, at))
    _close(res.final_w, models[-1])
    # a drain that crossed a boundary was two dispatches, the first ending
    # ON the boundary's update: every such update ends a dispatch
    ends = {int(r[4]) for r in spies.records}
    assert all(j * freq + 1 in ends
               for j in range((cfg.num_iterations - 1) // freq + 1))


# ------------------------------------------------------------ (ii) snapshots
@pytest.mark.parametrize("nw,freq", [(8, 10), (8, 3), (16, 20), (4, 4)])
def test_a_snapshot_holds_the_model_after_its_update_folded_or_not(
        nw, freq, problem, held_updater, engine_runs, monkeypatch):
    X, y = problem
    cfg = _cfg(num_workers=nw, printer_freq=freq, num_iterations=10 * nw + 3)
    engine = ASGD(X, y, cfg)
    spies = Dispatches(engine, monkeypatch)
    held_updater(nw)
    res = engine.run()
    accepted = cfg.num_iterations
    assert res.accepted == accepted
    want = ([0] + [j * freq + 1 for j in range((accepted - 1) // freq + 1)]
            + [accepted])
    # what benchmark/target.py: snapshot_updates reckons, exactly
    assert res.snapshot_updates == want
    assert len(res.trajectory) == len(want)
    assert max(len(r[1]) for r in spies.records) > 1  # drains were folded
    models, _ks = _serial_replay(cfg, X.shape[0], X.shape[1], spies.records)
    (run,) = engine_runs
    assert len(run.snapshots) == len(want)
    for updates, (_t_ms, w) in zip(want, run.snapshots):
        _close(np.asarray(w), models[updates])


# ------------------------------------------------- (iii) dispatches, counted
def test_without_a_backlog_every_update_is_its_own_apply(problem, monkeypatch):
    """Results that come one at a time are applied as ever: ``_apply``, one
    dispatch an update, never the fold."""
    X, y = problem
    cfg = _cfg(num_workers=1, num_iterations=40, bucket_ratio=1.0)
    engine = ASGD(X, y, cfg)
    spies = Dispatches(engine, monkeypatch)
    res = engine.run()
    assert res.accepted == 40
    assert [r[0] for r in spies.records] == ["apply"] * 40
    assert res.extras["apply_dispatches"] == 40
    assert res.accepted / res.extras["apply_dispatches"] == 1.0


@pytest.mark.parametrize("nw", [4, 8, 32])
def test_under_a_backlog_a_drain_is_one_dispatch(nw, problem, held_updater,
                                                 monkeypatch):
    X, y = problem
    cfg = _cfg(num_workers=nw, num_iterations=10 * nw, printer_freq=4 * nw)
    engine = ASGD(X, y, cfg)
    spies = Dispatches(engine, monkeypatch)
    held_updater(nw)
    res = engine.run()
    ex = res.extras
    assert ex["apply_dispatches"] == len(spies.records)
    assert res.accepted / ex["apply_dispatches"] > 1
    assert ex["drain_items_max"] <= nw  # the fold's arity bounds a drain
    # one dispatch a drain, one more where a snapshot split it
    boundaries = (res.accepted - 1) // cfg.printer_freq + 1
    assert ex["drains"] <= ex["apply_dispatches"] <= ex["drains"] + boundaries
    # nothing dropped, nothing applied twice
    assert spies.updates == res.accepted
    assert sum(res.staleness_hist.values()) == res.accepted + res.dropped


def test_the_knob_has_no_say(problem, held_updater, monkeypatch):
    """``SolverConfig.drain_batch`` waits for its deletion (ISSUE 31): the
    updater folds what it finds whatever the field says."""
    X, y = problem
    cfg = _cfg(drain_batch=1, num_iterations=64)
    engine = ASGD(X, y, cfg)
    spies = Dispatches(engine, monkeypatch)
    held_updater(8)
    res = engine.run()
    assert res.extras["drain_items_max"] > 1
    assert "fold" in {r[0] for r in spies.records}


# ------------------------------------------------- (iv) compiled exactly once
def test_the_fold_compiles_once_for_every_drain_size(problem, monkeypatch):
    """Drains of every size 2..nw go through the executable the warm-up
    built: no compile after it."""
    X, y = problem
    nw = 8
    cfg = _cfg(num_workers=nw, num_iterations=400, printer_freq=1000)
    # (one device: a drain is ONE dispatch there, so the sizes asked for
    # below are the sizes folded; over several a drain is a dispatch a
    # device and more results queue behind it)
    engine = ASGD(X, y, cfg, devices=jax.devices()[:1])
    spies = Dispatches(engine, monkeypatch)
    sizes = iter(list(range(2, nw + 1)) * 6)
    real = AsyncContext.collect_all
    lock = threading.Lock()

    def collect_all(self, timeout=None):
        if timeout:
            with lock:
                want = next(sizes, 1)
            deadline = time.monotonic() + 1.0
            while self.size() < want and time.monotonic() < deadline:
                time.sleep(0.0005)
        return real(self, timeout=timeout)

    monkeypatch.setattr(AsyncContext, "collect_all", collect_all)
    res = engine.run()
    assert res.accepted == 400
    assert res.extras["compiles_in_run"] == 0
    folded = {len(r[1]) for r in spies.records if r[0] == "fold"}
    assert folded >= set(range(2, nw + 1)), folded
    # and the fold itself holds one executable
    before = compiles_so_far()
    d = X.shape[1]
    zero = jax.device_put(jnp.zeros(d, jnp.float32), engine.driver_device)
    for m in range(2, nw + 1):
        engine._apply_fold(
            jax.device_put(jnp.zeros(d, jnp.float32), engine.driver_device),
            (zero,) * nw,
            jax.device_put(jnp.float32(m), engine.driver_device),
            jax.device_put(jnp.float32(0.0), engine.driver_device),
        )
    assert compiles_so_far() == before
