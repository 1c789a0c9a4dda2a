"""The storage format lives behind the shard (ISSUE 43).

``data/`` says what a shard IS (dense ``(X, y)``, padded ELL ``(cols, vals,
y)`` read at a live width), ``ops/steps.py`` says what is COMPUTED on it and
what that costs (``worker_programs``: a dataset's programs, built once, one
record), and everything above passes shards through by four members
(``operands``, ``shape``, ``nbytes``, ``on``) without looking inside.  The
fixtures are small and at the cells' true widths: mnist8m's 784 columns,
criteo's 39 values in 40 slots, kdd2012's 11 in 16, and rows of unequal
length dealt into three lane-tiled shapes (webspam's kind).
"""

import ast
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.data.sharded import Shard, ShardedDataset
from asyncframework_tpu.data.sparse import SparseShardedDataset
from asyncframework_tpu.engine.recovery import ShardRecovery
from asyncframework_tpu.ops import steps
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig
from asyncframework_tpu.utils import flops
from asyncframework_tpu.utils.hbm import dataset_residency_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B = 0.05


# ------------------------------------------- (1) nobody above ops/ looks inside
#: reads of a shard's arrays by name, or of "is it sparse?", outside
#: ``data/`` and ``ops/``: (file, function) -> (the names it may read, why)
ALLOWED = {
    ("solvers/asaga.py", "ASAGA.run"): (
        {"X"}, "the accept path's dense-PAYLOAD branch: a (diff, mask) "
        "payload's exact table delta reads the shard's rows again; the "
        "branch is on ASAGA's own payload format and stays ONE frame "
        "(a helper there cost a third of the rate, PR 23)"),
    ("solvers/asaga.py", "ASAGA._warm_hot_path"): (
        {"X"}, "the warm-up of that branch: the same call, so that the "
        "same executable is compiled before the clock starts"),
    **{("parallel/ps_dcn.py", f"PSClient.{name}"): (
        {"sparse"}, "the GRADIENT's wire encoding (enc='sparse'), a "
        "keyword of the client's push methods: not a shard's storage")
       for name in ("_encode_push", "push", "push_start", "push_saga")},
}
WATCHED_ATTRS = {"cols", "vals", "X", "is_sparse", "_sparse", "sparse"}
WATCHED_NAMES = {"_sparse", "sparse"}


def _storage_reads(path):
    """``(function, name, line)`` of every read of a watched attribute or
    name in ``path``; ``function`` is ``Class.method`` or the top-level
    function (closures count for the function that holds them)."""
    found = []

    def walk(node, where):
        for child in ast.iter_child_nodes(node):
            inner = where
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                inner = where + [child.name]
            name = None
            if isinstance(child, ast.Attribute) and child.attr in WATCHED_ATTRS:
                name = child.attr
            elif isinstance(child, ast.Name) and child.id in WATCHED_NAMES:
                name = child.id
            if name is not None and isinstance(child.ctx, ast.Load):
                found.append((".".join(where[:2]), name, child.lineno))
            walk(child, inner)

    with open(path) as f:
        walk(ast.parse(f.read()), [])
    return found


def test_nothing_above_ops_reads_a_shards_arrays_by_name():
    """``solvers/``, ``engine/``, ``utils/`` and ``parallel/ps_dcn.py``
    call a program with ``*shard.operands`` and ask nobody "dense or
    sparse?".  At the parent this found 109 reads in seven files."""
    pkg = os.path.join(ROOT, "asyncframework_tpu")
    files = [p for sub in ("solvers", "engine", "utils")
             for p in sorted(glob.glob(os.path.join(pkg, sub, "*.py")))]
    files.append(os.path.join(pkg, "parallel", "ps_dcn.py"))
    assert len(files) > 25
    bad, used = [], set()
    for path in files:
        rel = os.path.relpath(path, pkg)
        for func, name, line in _storage_reads(path):
            names, _why = ALLOWED.get((rel, func), (set(), ""))
            if name in names:
                used.add((rel, func))
            else:
                bad.append(f"{rel}:{line} {func or '<module>'} reads {name!r}")
    assert not bad, "\n".join(bad)
    assert used == set(ALLOWED), set(ALLOWED) - used  # no stale allowance


def test_the_walk_sees_what_it_is_meant_to(tmp_path):
    src = tmp_path / "m.py"
    src.write_text(
        "class S:\n"
        "    def a(self, shard, sparse=False):\n"
        "        self._sparse = sparse\n"       # a read of the name
        "        def inner():\n"
        "            return shard.cols, shard.operands\n"
        "        return hasattr(shard, 'cols') or shard.X\n"
        "def f(ds):\n"
        "    ds.vals = 1\n"                     # a store: not a read
        "    return ds.is_sparse and f(sparse=ds)\n")
    assert _storage_reads(str(src)) == [
        ("S.a", "sparse", 3), ("S.a", "cols", 5), ("S.a", "X", 6),
        ("f", "is_sparse", 9)]


# --------------------------------------------------------------- the fixtures
DEV = jax.devices()[:1]


def _dense():
    return ShardedDataset.generate_on_device(1_003, 784, 2, DEV, seed=3)


def _ell(nnz):
    return SparseShardedDataset.generate_on_device(
        1_003, 4_096, nnz, 2, DEV, seed=3, noise=0.01)


def _ragged():
    return SparseShardedDataset.generate_on_device(
        6_144, 40_007, 180, 3, DEV, seed=3, noise=0.0, column_skew=0.5,
        row_nnz={"law": "lognormal", "sigma": 0.8, "min": 122, "max": 384},
        row_values={"law": "lognormal", "sigma": 0.5})


#: ``solver._path_extras`` (and ASGD's ``_widths``, ``_step_nonzeros``,
#: ``_step_walked``) as the PARENT (PR 41's tree) reckoned them on these
#: fixtures at ``batch_rate`` 0.05, written down from its output (a dense
#: record also says, since PR 49, what share of its lane tiles a step
#: fetches: all of them on every path but the tile-list kernel's)
PARENT = {
    "dense-784": dict(
        extras={"dense_step_path": "two_products",
                "dense_tiles_read_share": 1.0},
        widths=(None, None), nonzeros=(), walked=()),
    "ell-39-of-40": dict(
        extras={
            "sparse_step_capacity": 56, "sampled_slots_per_step": 2240,
            "sparse_live_width": 39, "live_slots_per_step": 2184,
            "sparse_gather_path": "elements",
            "sparse_scatter_path": "scatter", "sparse_width_min": 39,
            "sparse_width_max": 39, "sparse_step_shapes": 2,
            "sparse_stored_slots": 40120, "sparse_nonzero_slots": 39117,
            "walked_slots_share_max": 0.975},
        widths=(39, 39), nonzeros=(978.9000000000001, 976.95),
        walked=(2184.0, 2184.0)),
    "ell-11-of-16": dict(
        extras={
            "sparse_step_capacity": 56, "sampled_slots_per_step": 896,
            "sparse_live_width": 11, "live_slots_per_step": 616,
            "sparse_gather_path": "elements",
            "sparse_scatter_path": "scatter", "sparse_width_min": 11,
            "sparse_width_max": 11, "sparse_step_shapes": 2,
            "sparse_stored_slots": 16048, "sparse_nonzero_slots": 11033,
            "walked_slots_share_max": 0.6875},
        widths=(11, 11), nonzeros=(276.1, 275.55), walked=(616.0, 616.0)),
    "ragged-3-shapes": dict(
        extras={
            "sparse_step_capacity": 168, "sampled_slots_per_step": 64512,
            "sparse_live_width": 384, "live_slots_per_step": 64512,
            "sparse_gather_path": "elements",
            "sparse_scatter_path": "scatter", "sparse_width_min": 128,
            "sparse_width_max": 384, "sparse_step_shapes": 3,
            "sparse_stored_slots": 1572864, "sparse_nonzero_slots": 1105920,
            "walked_slots_share_max": 0.7619047619047619},
        widths=(128, 256, 384),
        nonzeros=(12492.800000000001, 13842.400000000001,
                  28960.800000000003),
        walked=(16384.0, 32768.0, 49152.0)),
}
BUILD = {"dense-784": _dense, "ell-39-of-40": lambda: _ell(39),
         "ell-11-of-16": lambda: _ell(11), "ragged-3-shapes": _ragged}
#: the parent's ASAGA reported two of a padded-ELL dataset's eleven keys
#: (twelve since PR 52: ``sparse_scatter_path``, which program adds the
#: products into ``g``, ``"scatter"`` on the CPU)
PARENT_ASAGA_KEYS = {"sparse_live_width", "sparse_gather_path"}


@pytest.fixture(scope="module", params=list(BUILD))
def placed(request):
    return request.param, BUILD[request.param]()


def _cfg(ds, **kw):
    return SolverConfig(num_workers=ds.num_workers, num_iterations=8,
                        batch_rate=B, **kw)


# ------------------------------------------ (2) the record says what was said
def test_asgds_record_says_what_the_parents_solver_reckoned(placed):
    name, ds = placed
    programs = ASGD(ds, None, _cfg(ds), devices=DEV)._programs
    want = PARENT[name]
    assert dict(programs.extras) == want["extras"]
    assert programs.widths == want["widths"]
    assert programs.step_nonzeros == want["nonzeros"]
    assert programs.step_walked == want["walked"]


@pytest.mark.parametrize("name", list(BUILD)[:3])
def test_asaga_reports_what_asgd_reports_of_a_one_shape_dataset(name):
    ds = BUILD[name]()
    programs = ASAGA(ds, None, _cfg(ds), devices=DEV)._programs
    want = dict(PARENT[name]["extras"])
    if "sparse_live_width" in want:  # every key where it said two
        assert PARENT_ASAGA_KEYS < set(want) and len(want) == 12
        assert programs.compacted and programs.commit is not None
        # and, since PR 46, what a result carries beside ``g``: diff_sel,
        # idx and valid a packed row, a column and a value a live slot
        cap, live = want["sparse_step_capacity"], want["sparse_live_width"]
        want["history_payload_bytes"] = cap * (12 + 8 * live)
    else:
        assert not programs.compacted and programs.commit is None
    assert dict(programs.extras) == want
    assert programs.widths == PARENT[name]["widths"]
    assert programs.step_walked == PARENT[name]["walked"]


def test_asagas_step_reads_its_sample_whole_where_asgds_walks_it():
    """Rows of unequal length in lane-tiled shards: ASGD's step walks its
    packed sample in row tiles (``walked_slots_share_max`` 0.76 above);
    ASAGA's hands ``sparse_margins`` no walk, and its account says so:
    capacity x the width read, every worker's."""
    ds = BUILD["ragged-3-shapes"]()
    programs = ASAGA(ds, None, _cfg(ds), devices=DEV)._programs
    want = PARENT["ragged-3-shapes"]
    caps = [steps.sparse_step_capacity(B, ds.shard(w).size)
            for w in range(ds.num_workers)]
    assert programs.step_walked == tuple(
        float(c * k) for c, k in zip(caps, want["widths"]))
    assert programs.extras["walked_slots_share_max"] == 1.0
    assert programs.extras["history_payload_bytes"] == max(
        c * (12 + 8 * k) for c, k in zip(caps, want["widths"]))
    same = set(want["extras"]) - {"walked_slots_share_max"}
    assert {k: programs.extras[k] for k in same} == {
        k: want["extras"][k] for k in same}


def test_the_record_accounts_for_a_step_and_an_evaluation(placed):
    """``task_flops``, ``eval_account``, the stack of an evaluation call
    and the steps' temporaries, against the formulas the solvers, the
    engine loop and ``solvers/base.py`` spelled out at the parent."""
    name, ds = placed
    programs = steps.worker_programs(ds, B, "least_squares")
    shards = [ds.shard(w) for w in range(ds.num_workers)]
    if name == "dense-784":
        assert [programs.task_flops(s) for s in shards] == [
            flops.dense_task_flops(s.size, 784) for s in shards]
        assert programs.eval_account(shards[0]) == {"eval_blocks": 1}
        assert (programs.eval_stack_rows, programs.workspace_bytes) == (
            None, 0)
        return
    caps = [steps.sparse_step_capacity(B, s.size) for s in shards]
    stored = [s.vals.shape[1] for s in shards]
    assert [programs.task_flops(s) for s in shards] == [
        flops.sparse_task_flops(c, k) for c, k in zip(caps, stored)]
    ev = programs.evaluate
    for s, k, lw in zip(shards, stored, programs.widths):
        walked = ev.blocks(s.size, k) * ev.block_rows(s.size, k)
        assert programs.eval_account(s) == {
            "eval_blocks": ev.blocks(s.size, k), "eval_slots": walked * k,
            "eval_live_slots": walked * lw}
    assert programs.eval_stack_rows == steps.SPARSE_EVAL_SNAPSHOTS
    assert programs.workspace_bytes == sum(
        20 * c * k for c, k in zip(caps, stored))


# -------------------------------- (3) one program, reached two ways
def _spelled_out(shard):
    if isinstance(shard, Shard):
        return (shard.X, shard.y)
    return (shard.cols, shard.vals, shard.y)


def test_operands_reach_the_program_the_fields_reach(placed):
    """``solver._step`` and ``solver._eval`` called with
    ``*shard.operands`` lower to the text of the factory, built by hand
    as the parent's solver built it, called with the fields by name."""
    name, ds = placed
    solver = ASGD(ds, None, _cfg(ds, loss="logistic"), devices=DEV)
    if name == "dense-784":
        step = steps.make_asgd_worker_step(B, "logistic")
        ev = steps.make_trajectory_loss_eval("logistic")
    else:
        live = ds.live_widths
        step = steps.make_sparse_asgd_worker_step(
            B, ds.d, "logistic", live_width=live)
        ev = steps.make_sparse_trajectory_loss_eval("logistic",
                                                    live_width=live)
    w = jnp.zeros(ds.d, jnp.float32)
    W = jnp.zeros((8, ds.d), jnp.float32)
    key = jax.random.PRNGKey(0)
    texts = set()
    for wid in range(ds.num_workers):
        shard = ds.shard(wid)
        fields = _spelled_out(shard)
        assert len(fields) == len(shard.operands) and all(
            a is b for a, b in zip(fields, shard.operands))
        assert shard.shape == fields[0].shape
        mine = solver._step.lower(*shard.operands, w, key).as_text()
        assert mine == step.lower(*fields, w, key).as_text()
        assert (solver._eval.lower(*shard.operands, W).as_text()
                == ev.lower(*fields, W).as_text())
        texts.add(mine)
    assert len(texts) == len({ds.shard(w).shape
                              for w in range(ds.num_workers)})


@pytest.mark.parametrize("name", list(BUILD)[:3])
def test_asagas_step_is_the_factorys_too(name):
    ds = BUILD[name]()
    solver = ASAGA(ds, None, _cfg(ds), devices=DEV)
    step = (steps.make_saga_worker_step(B) if name == "dense-784" else
            steps.make_sparse_saga_worker_step(
                B, ds.d, live_width=ds.live_widths))
    shard = ds.shard(1)
    rest = (jnp.zeros(ds.d, jnp.float32),
            jnp.zeros(shard.size, jnp.float32), jax.random.PRNGKey(0))
    assert (solver._step.lower(*shard.operands, *rest).as_text()
            == step.lower(*_spelled_out(shard), *rest).as_text())
    # the drift's two passes: X^T v over the whole shard, both storages
    *rows, y = shard.operands
    dense_X = (np.asarray(shard.X, np.float64) if name == "dense-784" else
               _densified(shard, ds.d))
    got = solver._programs.table_mean_grad(*rows, y)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), dense_X.T @ np.asarray(y, np.float64),
        rtol=2e-5, atol=1e-5)


def _densified(shard, d):
    X = np.zeros((shard.size, d), np.float64)
    cols, vals = np.asarray(shard.cols), np.asarray(shard.vals, np.float64)
    for j in range(shard.size):
        np.add.at(X[j], cols[j], vals[j])
    return X


# ------------------------------------------------- (4) a shard moves whole
@pytest.mark.parametrize("through", ["on", "recovery"])
@pytest.mark.parametrize("name", ["dense-784", "ell-11-of-16"])
def test_a_moved_shard_keeps_every_field_and_moves_every_operand(
        name, through, devices8):
    ds = BUILD[name]()
    cur = ds.shard(1)
    there = devices8[3]
    if through == "on":
        moved = cur.on(there)
    else:  # a re-homed shard keeps its live width (the PR 38 case)
        moved = ShardRecovery(ds, [DEV[0], there]).move_shard(1, 1)
    assert type(moved) is type(cur) and moved is not cur
    assert cur.device == DEV[0] and moved.device == there
    assert all(a.device == there for a in moved.operands)
    arrays = set(type(cur)._ARRAYS)
    for f in dataclasses.fields(cur):
        a, b = getattr(cur, f.name), getattr(moved, f.name)
        if f.name in arrays:
            assert np.array_equal(np.asarray(a), np.asarray(b)), f.name
        else:
            assert a is b or a == b, f.name
    if name != "dense-784":
        assert moved.live_width == 11 and moved.nnz == cur.nnz
    assert (moved.shape, moved.nbytes) == (cur.shape, cur.nbytes)
    assert cur.on(DEV[0]) is cur  # at home nothing is copied


def test_the_memory_plan_sums_what_the_shards_say(placed):
    _name, ds = placed
    shards = [ds.shard(w) for w in range(ds.num_workers)]
    want = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for s in shards for a in _spelled_out(s))
    assert dataset_residency_bytes(ds) == {DEV[0]: want}
    assert sum(s.nbytes for s in shards) == want


# --------------------------- the DCN worker: shards, and no dataset to ask
@pytest.mark.parametrize("history", [False, True], ids=["asgd", "asaga"])
@pytest.mark.parametrize("name", ["dense-784", "ell-11-of-16"])
def test_the_dcn_workers_programs_take_the_operands(name, history):
    ds = BUILD[name]()
    shards = [ds.shard(w) for w in range(ds.num_workers)]
    step, evaluate, sparse_gradients, meshable = steps.dcn_worker_programs(
        shards, ds.d, 0.2, "least_squares", history=history)
    assert sparse_gradients == (name != "dense-784") == (not meshable)
    shard = shards[0]
    w = jnp.zeros(ds.d, jnp.float32).at[3].set(1.0)
    if history:
        cap = steps.sparse_step_capacity(0.2, shard.size)
        g, diff = step(*shard.operands, w, jnp.arange(cap, dtype=jnp.int32),
                       jnp.zeros(cap, jnp.float32), jnp.int32(cap))
        assert diff.shape == (cap,)
    else:
        g, _key = step(*shard.operands, w, jax.random.PRNGKey(1))
    assert g.shape == (ds.d,) and float(jnp.max(jnp.abs(g))) > 0
    from asyncframework_tpu.parallel.ps_dcn import (
        evaluate_snapshots_on_shards,
    )

    W = np.stack([np.zeros(ds.d, np.float32), np.asarray(w)])
    total = evaluate_snapshots_on_shards(
        dict(enumerate(shards)), [0.0, 1.0], W, evaluate)
    y = np.concatenate([np.asarray(s.y, np.float64) for s in shards])
    assert total[0] == pytest.approx(float(y @ y), rel=1e-5)
    assert total.shape == (2,) and total[1] != total[0]
