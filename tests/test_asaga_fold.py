"""ASAGA's updater merges a DRAIN, not a result (ISSUE 60): the tau filter
for everything queued under one hold of the state lock, then, with no lock
held, the accepted results' history paths a result at a time in drain order
(the commit, and the exact table delta where the slice moved) and ONE apply
for all of them, split only where a snapshot is due.

The cases build their own backlog (``held_updater``, ``conftest.py``) and
spy on every program the updater calls: one record a dispatch, with, for
each of its slots, what its history path read and committed.  What a drain
did is then replayed twice: the serial apply over the recorded ``(g,
delta)`` pairs in float32 (the fold's arithmetic), and the whole recurrence
from the recorded payloads alone in float64 on the host, table and all (the
deltas, the commits, their order)."""

import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.context import AsyncContext
from asyncframework_tpu.data import (
    SparseShardedDataset,
    densify,
    make_sparse_regression,
)
from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.metrics import trace
from asyncframework_tpu.ops import steps
from asyncframework_tpu.solvers import ASAGA, SolverConfig, asaga, engine_loop
from asyncframework_tpu.solvers.instrumentation import compiles_so_far

N, D, SEED = 2048, 16, 11
KINDS = pytest.mark.parametrize("kind", ["dense", "padded-ell"])
FOREVER = 2**31 - 1
#: the fold is the serial recurrence, the serial expression in the serial
#: order: relative to the vector's largest element, as ``test_asgd_fold``
RUN_RTOL = 1e-6
#: float32 sums on the program's side against float64 on the host's, over a
#: hundred updates
HOST_RTOL = 2e-5


def _cfg(**kw):
    base = dict(
        num_workers=8, num_iterations=96, gamma=0.05, taw=FOREVER,
        batch_rate=0.2, bucket_ratio=0.5, printer_freq=10, seed=5,
        calibration_iters=4, run_timeout_s=60.0,
    )
    base.update(kw)
    return SolverConfig(**base)


def _solver(kind, devices=None, **cfg):
    """``(solver, rows)``: an ASAGA solver over seeded data and each
    shard's rows on the host, float64."""
    cfg = _cfg(**cfg)
    nw = cfg.num_workers
    devices = devices or jax.devices()[:1]
    if kind == "dense":
        ds = ShardedDataset.generate_on_device(
            N, D, nw, devices, seed=SEED, noise=0.01)
        rows = [np.asarray(ds.shard(w).X, np.float64) for w in range(nw)]
    else:
        ds = SparseShardedDataset(
            *make_sparse_regression(N, D, 0.2, SEED), D, nw, devices)
        X = densify(ds)[0].astype(np.float64)
        rows = [X[ds.shard(w).start:ds.shard(w).start + ds.shard(w).size]
                for w in range(nw)]
    return ASAGA(ds, None, cfg, devices=devices), rows


class Slot:
    """One accepted result's history path, as the updater ran it: whose,
    the slice it found, its payload's commit operands (host copies), the
    slice it committed, and whether it paid the exact delta; the apply that
    took it adds ``g``, ``delta`` and whether both were ONE handle."""

    def __init__(self, wid, alpha, operands, exact):
        self.wid, self.alpha, self.operands = wid, alpha, operands
        self.exact = exact
        self.handle = self.committed = None
        self.g = self.delta = self.g_is_delta = None


class Dispatches:
    """Spies on every callable of the updater's accept path: the table
    delta, the commit (``steps.saga_commit_history`` or the compacted
    payload's ``_commit``), both instances of the one-accept apply and the
    fold.  ``records``: one a dispatch of the timed run, in order, ``(kind,
    slots, w after, alpha_bar after)``, on the host.  ``held``: every call
    one of them got while the updater's thread held the state lock or the
    slices' lock (the engine's clocked locks name their holder), as
    ``(lock, "delta" | "commit" | "apply", results in the drain)``.
    ``abandoned``: the paths the updater made again."""

    def __init__(self, engine, monkeypatch):
        self.records, self.held, self.pending = [], [], []
        self.runs, self.tables, self.counts = [], [], []
        self.abandoned = []
        #: a test's hook: called with every commit's slot, on the
        #: updater's thread, between the dispatch and its publication
        self.on_commit = lambda slot: None
        self.armed = False
        self.drain_size = 0
        self._exact = False
        nw = engine.cfg.num_workers

        def on_updater(what):
            if not (self.armed
                    and threading.current_thread().name == "saga-updater"):
                return False
            run = self.runs[-1]
            for lock in (run.state_lock, run.key_lock):
                if lock.holder() == trace.UPDATER:
                    self.held.append((lock.name, what, self.drain_size))
            return True

        real_delta = engine._table_delta

        def table_delta(*args):
            if on_updater("delta"):
                self._exact = True
            return real_delta(*args)

        def settle():
            """The newest pending path was abandoned where the slot does
            not hold what it committed (a shard re-homed between its read
            and its publication: the updater went again)."""
            last = self.pending[-1] if self.pending else None
            if last is not None and (
                    self.tables[-1][last.wid] is not last.handle):
                self.abandoned.append(self.pending.pop())

        def commit_spy(real):
            def commit(alpha_cur, *operands):
                if not on_updater("commit"):
                    return real(alpha_cur, *operands)
                settle()
                (wid,) = [w for w, a in self.tables[-1].items()
                          if a is alpha_cur]
                # (the dense commit donates ``diff``)
                slot = Slot(wid, np.array(alpha_cur),
                            [np.array(a) for a in operands], self._exact)
                self._exact = False
                slot.handle = real(alpha_cur, *operands)
                slot.committed = np.array(slot.handle)
                self.pending.append(slot)
                self.on_commit(slot)
                return slot.handle
            return commit

        def took(kind, gs, deltas, out):
            settle()
            slots, self.pending = self.pending, []
            assert len(slots) == len(gs) == len(deltas)
            for slot, (g, g_is_delta), delta in zip(slots, gs, deltas):
                slot.g, slot.delta, slot.g_is_delta = g, delta, g_is_delta
            self.records.append(
                (kind, slots, np.asarray(out[0]), np.asarray(out[1])))
            return out

        def apply_spy(real, kind):
            def apply(w, ab, g, delta):
                if not on_updater("apply"):
                    return real(w, ab, g, delta)
                # (g and alpha_bar are donated)
                gs, deltas = [(np.array(g), g is delta)], [np.array(delta)]
                return took(kind, gs, deltas, real(w, ab, g, delta))
            return apply

        real_fold = engine._apply_fold

        def fold(w, ab, gs, deltas, m):
            if not on_updater("apply"):
                return real_fold(w, ab, gs, deltas, m)
            assert len(gs) == len(deltas) == nw  # ONE arity
            live = int(m)
            assert 2 <= live <= nw
            # what pads a short drain is zeros, and ONE handle in both
            pad = gs[live:] + deltas[live:]
            assert all(z is pad[0] for z in pad)
            if pad:
                assert not np.any(np.asarray(pad[0]))
            host_g = [(np.array(g), g is delta)
                      for g, delta in zip(gs[:live], deltas[:live])]
            host_delta = [np.array(delta) for delta in deltas[:live]]
            return took("fold", host_g, host_delta,
                        real_fold(w, ab, gs, deltas, m))

        engine._table_delta = table_delta
        if engine._compacted:
            engine._commit = commit_spy(engine._commit)
        else:
            monkeypatch.setattr(steps, "saga_commit_history",
                                commit_spy(steps.saga_commit_history))
        engine._apply = apply_spy(engine._apply, "apply")
        engine._apply_g_is_delta = apply_spy(
            engine._apply_g_is_delta, "apply-g")
        engine._apply_fold = fold
        real_follows = engine._history_follows

        def follows(run, alpha, commits):
            self.runs.append(run)
            self.tables.append(alpha)
            self.counts.append(commits)
            real_drained = run.inst.on_drained

            def on_drained(results):
                self.drain_size = len(results)
                return real_drained(results)

            run.inst.on_drained = on_drained
            return real_follows(run, alpha, commits)

        engine._history_follows = follows
        # the solver's own warm-up dispatches all of them before the clock
        real_clock = engine_loop.EngineRun.start_clock

        def start_clock(run):
            self.armed = True
            return real_clock(run)

        monkeypatch.setattr(engine_loop.EngineRun, "start_clock", start_clock)

    @property
    def slots(self):
        return [slot for _kind, slots, _w, _ab in self.records
                for slot in slots]


def _close(a, b, rtol=RUN_RTOL):
    np.testing.assert_allclose(
        a, b, rtol=0, atol=rtol * max(1.0, float(np.max(np.abs(b)))))


def _serial_replay(cfg, records):
    """The serial apply over the recorded ``(g, delta)`` pairs, float32 on
    the device: ``(w, alpha_bar)`` after every update (index u: after
    update u; index 0: zeros)."""
    apply_one = steps.make_saga_apply(
        cfg.gamma, cfg.batch_rate, N, cfg.num_workers, donate_g=False)
    w, ab = jnp.zeros(D, jnp.float32), jnp.zeros(D, jnp.float32)
    out = [(np.asarray(w), np.asarray(ab))]
    for _kind, slots, _w2, _ab2 in records:
        for slot in slots:
            w, ab = apply_one(w, ab, jnp.asarray(slot.g),
                              jnp.asarray(slot.delta))
            out.append((np.asarray(w), np.asarray(ab)))
    return out


def _host_replay(cfg, kind, rows, records):
    """The whole recurrence from the recorded payloads, float64 on the
    host: the table from zeros, every delta against the table as its
    commit finds it.  Returns ``(w, alpha_bar)`` after every dispatch and
    the table; holds each slot's recorded delta and slices to it on the
    way."""
    par_recs = cfg.batch_rate * N / cfg.num_workers
    w, ab = np.zeros(D), np.zeros(D)
    table = {wid: np.zeros(len(X)) for wid, X in enumerate(rows)}
    out = []
    for _kind, slots, _w2, _ab2 in records:
        for slot in slots:
            mine = table[slot.wid]
            # the path read the slice the commits before it left
            assert np.array_equal(slot.alpha, mine.astype(np.float32))
            v, new = np.zeros(len(mine)), mine.copy()
            if kind == "dense":
                diff, mask = slot.operands
                v = mask * (diff - mine)
                new = np.where(mask > 0, diff, mine)
            else:
                diff, idx, valid = slot.operands
                sel = idx[valid > 0]
                v[sel] = diff[valid > 0] - mine[sel]
                new[sel] = diff[valid > 0]
            delta = rows[slot.wid].T @ v
            _close(slot.delta, delta, HOST_RTOL)
            assert np.array_equal(slot.committed, new.astype(np.float32))
            w = w - (cfg.gamma / par_recs) * slot.g - cfg.gamma * ab
            ab = ab + delta / N
            table[slot.wid] = new
        out.append((w.copy(), ab.copy()))
    return out, table


def _dispatched_outside_the_locks(spies):
    """No dispatch of the updater under the state lock, and none under the
    slices' lock but the history path of a result that came ALONE (a drain
    of one keeps that lock over its path, as every accept did before the
    fold: the worker's next task, which is being made about then, waits
    the commit out and reads the slice that stands)."""
    assert all(lock == "key" and what != "apply" and size == 1
               for lock, what, size in spies.held), spies.held
    alone = sum(len(slots) for _kind, slots, _w, _ab in spies.records
                if len(slots) == 1)
    commits_held = sum(what == "commit" for _lock, what, _n in spies.held)
    assert commits_held <= alone


def _holds_the_serial_path(res, cfg, kind, rows, spies):
    """Every recorded dispatch left what the serial path leaves, by both
    replays; the run's final model, ``alpha_bar`` and table are the last
    dispatch's."""
    serial = _serial_replay(cfg, spies.records)
    host, table = _host_replay(cfg, kind, rows, spies.records)
    at = 0
    for (kind_, slots, w2, ab2), (w_h, ab_h) in zip(spies.records, host):
        at += len(slots)
        _close(w2, serial[at][0])
        _close(ab2, serial[at][1])
        _close(w2, w_h, HOST_RTOL)
        _close(ab2, ab_h, HOST_RTOL)
        for slot in slots:
            # one handle through both arguments where the slice stood,
            # and only there
            assert slot.g_is_delta == (not slot.exact)
        if len(slots) == 1:  # a drain of one: the serial path's program
            assert kind_ == ("apply" if slots[0].exact else "apply-g")
        else:
            assert kind_ == "fold"
    assert at == res.accepted
    _close(res.final_w, serial[-1][0])
    _close(res.extras["alpha_bar"], serial[-1][1])
    for wid, a in res.extras["alpha"].items():
        assert np.array_equal(a, table[wid].astype(np.float32))
    assert 0.0 <= res.extras["history_drift"] <= 5e-6
    ex = res.extras
    assert ex["history_reused"] == sum(not s.exact for s in spies.slots)
    assert ex["history_recomputed"] == sum(s.exact for s in spies.slots)
    assert ex["history_reused"] + ex["history_recomputed"] == res.accepted
    _dispatched_outside_the_locks(spies)


# ---------------------------------------------------------- (i) the arithmetic
@KINDS
@pytest.mark.parametrize("nw,freq", [
    (8, 10),   # a boundary every 10
    (8, 5),    # printer_freq under nw: a drain may cross two
    (4, 7),
    (16, 12),
])
def test_a_folded_drain_is_the_serial_path(kind, nw, freq, held_updater,
                                           monkeypatch):
    iters = 12 * nw
    solver, rows = _solver(kind, num_workers=nw, printer_freq=freq,
                           num_iterations=iters)
    cfg = solver.cfg
    spies = Dispatches(solver, monkeypatch)
    held_updater(nw)
    res = solver.run()
    assert res.accepted == iters == len(spies.slots)
    _holds_the_serial_path(res, cfg, kind, rows, spies)
    assert "fold" in {r[0] for r in spies.records}
    at = 0
    for _kind, slots, _w2, _ab2 in spies.records:
        first, at = at, at + len(slots)
        assert 1 <= len(slots) <= nw
        # a dispatch may END on a snapshot's update j * freq + 1 and never
        # reaches past one
        assert not any((u - 1) % freq == 0 for u in range(first + 1, at))
    ends = set(np.cumsum([len(r[1]) for r in spies.records]))
    assert all(j * freq + 1 in ends for j in range((iters - 1) // freq + 1))


@KINDS
def test_over_two_devices_the_fold_takes_its_operands_to_the_drivers(
        kind, held_updater, devices8, monkeypatch):
    """Shards on two devices: ``g`` and the recomputed deltas are copied
    to the driver's before the one dispatch, as a drain of one copies
    them."""
    solver, rows = _solver(kind, devices=devices8[:2], num_workers=4,
                           num_iterations=64)
    spies = Dispatches(solver, monkeypatch)
    held_updater(4)
    res = solver.run()
    assert res.accepted == 64
    assert {solver._shard_device(w) for w in range(4)} == set(devices8[:2])
    _holds_the_serial_path(res, solver.cfg, kind, rows, spies)
    assert "fold" in {r[0] for r in spies.records}


# ------------------------------------------------------------ (ii) snapshots
@KINDS
@pytest.mark.parametrize("nw,freq", [(8, 10), (8, 3), (4, 4)])
def test_a_snapshot_holds_the_model_after_its_update_folded_or_not(
        kind, nw, freq, held_updater, monkeypatch):
    solver, _rows = _solver(kind, num_workers=nw, printer_freq=freq,
                            num_iterations=10 * nw + 3)
    spies = Dispatches(solver, monkeypatch)
    held_updater(nw)
    res = solver.run()
    accepted = solver.cfg.num_iterations
    assert res.accepted == accepted
    want = ([0] + [j * freq + 1 for j in range((accepted - 1) // freq + 1)]
            + [accepted])
    # what benchmark/target.py: snapshot_updates reckons, exactly
    assert res.snapshot_updates == want
    assert len(res.trajectory) == len(want)
    assert max(len(r[1]) for r in spies.records) > 1  # drains were folded
    models = _serial_replay(solver.cfg, spies.records)
    (run,) = spies.runs
    assert len(run.snapshots) == len(want)
    for updates, (_t_ms, w) in zip(want, run.snapshots):
        _close(np.asarray(w), models[updates][0])


# ------------------------------------------------- (iii) dispatches, counted
@KINDS
def test_without_a_backlog_every_update_is_its_own_apply(kind, monkeypatch):
    """Results that come one at a time are applied as ever: the serial
    path's three programs, one apply an update, never the fold."""
    solver, rows = _solver(kind, num_workers=1, num_iterations=40,
                           bucket_ratio=1.0)
    spies = Dispatches(solver, monkeypatch)
    res = solver.run()
    assert res.accepted == 40
    assert {r[0] for r in spies.records} <= {"apply", "apply-g"}
    assert [len(r[1]) for r in spies.records] == [1] * 40
    assert res.extras["apply_dispatches"] == 40
    _holds_the_serial_path(res, solver.cfg, kind, rows, spies)
    # each came alone: its path kept the slices' lock, its apply none
    assert [h for h in spies.held if h[1] == "commit"] == [
        ("key", "commit", 1)] * 40


@KINDS
@pytest.mark.parametrize("nw", [4, 8, 32])
def test_under_a_backlog_a_drain_is_one_apply(kind, nw, held_updater,
                                              monkeypatch):
    solver, _rows = _solver(kind, num_workers=nw, num_iterations=10 * nw,
                            printer_freq=4 * nw)
    spies = Dispatches(solver, monkeypatch)
    held_updater(nw)
    res = solver.run()
    ex = res.extras
    assert ex["apply_dispatches"] == len(spies.records)
    # the counter that says how often the mechanism engages
    assert res.accepted / ex["apply_dispatches"] > 2
    assert ex["drain_items_max"] <= nw  # the fold's arity bounds a drain
    # one apply a drain, one more where a snapshot split it
    boundaries = (res.accepted - 1) // solver.cfg.printer_freq + 1
    assert ex["drains"] <= ex["apply_dispatches"] <= ex["drains"] + boundaries
    # nothing dropped, nothing applied twice, every accept committed once
    assert len(spies.slots) == res.accepted
    assert sum(res.staleness_hist.values()) == res.accepted + res.dropped
    assert ex["history_reused"] + ex["history_recomputed"] == res.accepted
    _dispatched_outside_the_locks(spies)


# ------------------------------------------- (iv) the history path in a drain
def _until(ready, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not ready():
        assert time.monotonic() < deadline
        time.sleep(0.0005)


@KINDS
def test_two_results_of_one_worker_in_a_drain_commit_in_order(
        kind, monkeypatch):
    """Two workers.  Behind the first round (a run's first job blocks),
    worker 1's second task waits until two results are queued, and so does
    the updater: they are worker 0's second and third, the third made
    while the second was still queued.  One drain, one fold: they commit
    in order, the later one against the slice the earlier one committed,
    which its step did not read: the exact delta."""
    solver, rows = _solver(kind, num_workers=2, num_iterations=40,
                           printer_freq=1000)
    spies = Dispatches(solver, monkeypatch)
    made, held = [], []
    real_make = solver._make_task

    def make_task(wid, *a):
        fn = real_make(wid, *a)
        made.append(wid)
        nth = made.count(wid)
        if nth == 1:
            return fn
        run = spies.runs[-1]

        def gated():
            # round two begins behind the first round's merges
            _until(lambda: run.state["k"] >= 2)
            if (wid, nth) == (1, 2):
                _until(lambda: run.ctx.size() >= 2)
            return fn()

        return gated

    solver._make_task = make_task
    real_collect = AsyncContext.collect_all

    def collect_all(self, timeout=None):
        if timeout and not held and spies.runs[-1].state["k"] >= 2:
            held.append(True)
            _until(lambda: self.size() >= 2)
        return real_collect(self, timeout=timeout)

    monkeypatch.setattr(AsyncContext, "collect_all", collect_all)
    res = solver.run()
    assert res.accepted == 40
    twice = [slots for _kind, slots, _w, _ab in spies.records
             if len(slots) == 2 and slots[0].wid == slots[1].wid]
    assert twice and spies.records[2][1] is twice[0]
    assert spies.records[2][0] == "fold"
    one, two = twice[0]
    assert one.wid == 0 and two.exact
    assert np.array_equal(two.alpha, one.committed)
    assert not np.array_equal(one.alpha, one.committed)
    _holds_the_serial_path(res, solver.cfg, kind, rows, spies)


@KINDS
def test_a_task_made_under_a_lone_results_path_reads_the_committed_slice(
        kind, monkeypatch):
    """One worker, so every result comes alone and its path keeps the
    slices' lock.  A capture that starts between the commit's dispatch and
    its publication (here: from another thread, as the submitter's would)
    waits the path out and gets the committed slice WITH its count; in a
    drain of several it would have read the old pair."""
    solver, _rows = _solver(kind, num_workers=1, num_iterations=12,
                            bucket_ratio=1.0)
    spies = Dispatches(solver, monkeypatch)
    captured, readers = [], []

    def on_commit(slot):
        run, table, counts = spies.runs[-1], spies.tables[-1], spies.counts[-1]
        # (this thread holds the lock: the pair as the path found it)
        found = (table[0], counts[0])

        def capture():
            with run.history_lock:
                captured.append((found, (table[0], counts[0])))

        readers.append(threading.Thread(target=capture))
        readers[-1].start()
        time.sleep(0.005)  # the reader stands at the lock meanwhile
        assert len(captured) < len(readers)

    spies.on_commit = on_commit
    res = solver.run()
    for reader in readers:
        reader.join(timeout=10.0)
        assert not reader.is_alive()
    assert res.accepted == 12 == len(captured)
    for (old, old_count), (new, new_count) in captured:
        # never the pair the path found: its publication, or a later one
        assert new is not old and new_count > old_count
    assert res.extras["history_reused"] == 12


@KINDS
def test_a_shard_rehomed_between_the_read_and_the_publication_goes_again(
        kind, serialised, held_updater, devices8, monkeypatch):
    """In a drain of several the history path holds no lock while it
    dispatches, so a shard can re-home under it: worker 1's shard moves
    right behind the commit of its third result, in front of the
    publication.  The slot's count has moved: nothing is published, and
    that one result goes again against the slice at its new home, with the
    exact delta (the dense commit has donated ``diff`` by then: the slice
    it made stands in for it)."""
    solver, rows = _solver(kind, devices=devices8[:2], num_workers=4,
                           num_iterations=80, printer_freq=1000)
    spies = Dispatches(solver, monkeypatch)
    hooks, seen = [], []
    real_follows = solver._history_follows

    def follows(*a):
        hooks.append(real_follows(*a))
        return hooks[-1]

    def on_commit(slot):
        seen.append(slot.wid)
        if slot.wid == 1 and seen.count(1) == 3:
            hooks[-1](1, solver._recovery.move_shard(1, 0))

    solver._history_follows, spies.on_commit = follows, on_commit
    home = solver._recovery.shard(1).device
    held_updater(4)  # whole cohorts: a drain of several holds no lock
    res = solver.run()
    assert solver._recovery.shard(1).device != home
    assert res.accepted == 80 == len(spies.slots)
    (gone,) = spies.abandoned
    assert gone.wid == 1 and not gone.exact
    (again,) = [s for s in spies.slots if s.wid == 1][2:3]
    assert again.exact and np.array_equal(again.alpha, gone.alpha)
    assert np.array_equal(again.committed, gone.committed)
    assert res.extras["history_recomputed"] == 1
    assert res.extras["history_reused"] == 79
    _holds_the_serial_path(res, solver.cfg, kind, rows, spies)


@KINDS
def test_the_standing_sample_falls_on_the_update_whose_own_index_asks(
        kind, serialised, held_updater, monkeypatch):
    """No overlap (``serialised``) and whole fleets folded: every accept
    reuses its ``g`` but the one whose OWN index, not its drain's first,
    is the period's."""
    mine, other = ("EXACT_DELTA_EVERY", "EXACT_SPARSE_DELTA_EVERY")[
        ::1 if kind == "dense" else -1]
    monkeypatch.setattr(asaga, mine, 13)
    monkeypatch.setattr(asaga, other, 5)
    solver, rows = _solver(kind, num_workers=8, num_iterations=96,
                           printer_freq=1000)
    spies = Dispatches(solver, monkeypatch)
    held_updater(8)
    res = solver.run()
    assert res.accepted == 96
    # (the first drain is split behind update 1, the first snapshot's)
    assert [len(r[1]) for r in spies.records] == [1, 7] + [8] * 11
    exact = [u for u, slot in enumerate(spies.slots) if slot.exact]
    assert exact == [u for u in range(96) if (u + 1) % 13 == 0]
    assert any(u % 8 for u in exact)  # inside a drain, not at its head
    _holds_the_serial_path(res, solver.cfg, kind, rows, spies)


@KINDS
@pytest.mark.parametrize("nw,taw", [(8, 60), (16, 40)])
def test_a_finite_taw_drops_slots_inside_a_folded_drain(
        kind, nw, taw, held_updater, monkeypatch):
    """ASAGA's filter is ``k - staleness <= taw`` with ``k`` the update's
    OWN index, so it ends a run: once ``k`` is past ``taw`` a result is
    accepted only as far as it is stale.  A fleet's results come back with
    staleness 0, 1, 2, ... in the order they are drained, so the first
    drain that starts past ``taw`` drops its head and accepts the slots
    behind it, each moving ``k`` on for the next; after it everything is
    dropped and the run ends at its time limit.  A dropped result commits
    nothing and is on neither side of the two counters."""
    solver, rows = _solver(kind, num_workers=nw, taw=taw,
                           num_iterations=10_000, run_timeout_s=1.5)
    spies = Dispatches(solver, monkeypatch)
    held_updater(nw)
    res = solver.run()
    # accepts past taw + 1: behind a dropped head, in one drain
    assert res.dropped > 0 and res.accepted > taw + 1
    assert len(spies.slots) == res.accepted
    _holds_the_serial_path(res, solver.cfg, kind, rows, spies)
    assert "fold" in {r[0] for r in spies.records}


# ------------------------------------------------- (v) compiled exactly once
@KINDS
def test_the_fold_compiles_once_for_every_drain_size(kind, monkeypatch):
    """Drains of every size 2..nw go through the executable the warm-up
    built, and so does either side of the history path: a warmed solver
    compiles nothing inside its window."""
    nw = 8
    solver, _rows = _solver(kind, num_workers=nw, num_iterations=400,
                            printer_freq=1000)
    spies = Dispatches(solver, monkeypatch)
    # every drain is cut to the size its turn asks for: the blocking take
    # waits for that many, and the drain behind it hands out the rest of
    # them and no more (ASAGA's drain takes long enough for more to queue)
    sizes = iter(list(range(2, nw + 1)) * 6)
    want = [1]
    real, real_drain = AsyncContext.collect_all, AsyncContext.drain

    def collect_all(self, timeout=None):
        if timeout:
            want[0] = next(sizes, 1)
            deadline = time.monotonic() + 1.0
            while self.size() < want[0] and time.monotonic() < deadline:
                time.sleep(0.0005)
        return real(self, timeout=timeout)

    def drain(self):
        return itertools.islice(real_drain(self), want[0] - 1)

    monkeypatch.setattr(AsyncContext, "collect_all", collect_all)
    monkeypatch.setattr(AsyncContext, "drain", drain)
    res = solver.run()
    assert res.accepted == 400
    assert res.extras["compiles_in_run"] == 0
    assert res.extras["history_reused"] > 0 < res.extras["history_recomputed"]
    folded = {len(r[1]) for r in spies.records if r[0] == "fold"}
    assert folded >= set(range(2, nw + 1)), folded
    # and the fold itself holds one executable
    before = compiles_so_far()
    drv = solver.driver_device
    zero = jax.device_put(jnp.zeros(D, jnp.float32), drv)
    for m in range(2, nw + 1):
        spies.armed = False
        solver._apply_fold(
            jax.device_put(jnp.zeros(D, jnp.float32), drv),
            jax.device_put(jnp.zeros(D, jnp.float32), drv),
            (zero,) * nw, (zero,) * nw,
            jax.device_put(jnp.float32(m), drv),
        )
    assert compiles_so_far() == before
