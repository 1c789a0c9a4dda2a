"""CPU rehearsal of what ISSUE 25 gave the benchmark: the ``mnist8m-asaga``
configuration and its cell, the plain ASAGA reference
(``benchmark/reference_saga.py``), the table delta's byte count
(``benchmark/roofline_saga.py``), the per-layer metrics of the history
path and the comparison at a cell's size (``benchmark/check_saga.py``), on
the existing ``tiny-asaga`` configuration; and of what ISSUE 29 made of
them: ``correct`` reads the history in every run of an ASAGA cell, the
control rounds what advances ``alpha_bar`` on every accept,
``history_device_ms`` is the history path's device time an update, and
``history_roofline`` is gone (its fusion runs on 0.6% of the accepts); and
of ISSUE 45: ``reference_saga`` reads padded-ELL shards, so ``correct``
and ``check_saga`` hold a sparse ASAGA run (``tiny-sparse-asaga``) to its
table as they hold a dense one."""

import json
import os

import numpy as np
import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    MANIFEST,
    PER_LAYER,
    _run,
    on_cpu,
)

from benchmark import manifest as manifest_mod
from benchmark import reference_saga, roofline_saga, run

CELL = "mnist8m-asaga.steady"
NEW = ["history_device_ms", "merge_history_p50_ms", "updater_history",
       "history_drift"]


def test_the_manifest_appends_one_configuration_one_cell_four_metrics():
    # found by name: later PRs append behind these, so no tail is pinned
    assert "mnist8m-asaga" in [c["name"] for c in MANIFEST["configs"]]
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mnist8m-asaga", "steady", 1)
    # the four (five until PR 29 took ``history_roofline`` out), together
    # and in this order, behind the sixteen there were
    first = PER_LAYER.index(NEW[0])
    assert first >= 16 and PER_LAYER[first:first + 4] == NEW
    for entry in MANIFEST["per_layer"][first:first + 4]:
        assert entry["workloads"] == [CELL]
    assert "history_roofline" not in PER_LAYER
    with pytest.raises(FileNotFoundError):
        manifest_mod.Manifest().metric_reader("history_roofline")
    man = manifest_mod.Manifest()
    config = man.config("mnist8m-asaga")
    asgd = man.config("mnist8m-asgd")
    assert (config["solver"], config["batch_rate"]) == ("asaga", 0.01)
    # the dataset, its generator's pins and the seed rule are mnist8m-asgd's
    for key in ("kind", "n", "d", "storage_dtype", "noise", "loss",
                "num_workers", "bucket_ratio", "target_fraction", "pins"):
        assert config[key] == asgd[key], key
    # every accepted metric without a cell list reports in the new cell
    # too, and the four in no other
    cells = {m["name"] for m in man.metric_entries("per_layer", CELL)}
    assert set(PER_LAYER[:16]) | set(NEW) <= cells
    assert not set(NEW) & {m["name"] for m in man.metric_entries(
        "per_layer", "mnist8m-asgd.steady")}


def test_table_delta_bytes_count_each_sampled_row_once():
    # mnist8m-asaga: 10,125 sampled rows of 1,568 bytes, a mask byte a row,
    # diff and alpha at the sampled rows, delta out
    b = roofline_saga.table_delta_bytes(1_012_500, 784, 2, 0.01)
    assert b == pytest.approx(
        10_125 * 784 * 2 + 1_012_500 + 2 * 10_125 * 4 + 784 * 4)
    data = {"kind": "dense", "shard_rows": [10, 12], "d": 4, "itemsize": 4}
    assert roofline_saga.delta_bytes(data, 0.5) == (
        roofline_saga.table_delta_bytes(12, 4, 4, 0.5))
    with pytest.raises(ValueError):
        roofline_saga.delta_bytes({"kind": "sparse"}, 0.5)


def _record(extras=None, stages=None):
    return {
        "program_trace": {"stages_ms": stages or {}},
        "result": {"elapsed_s": 20.0, "extras": extras or {}},
        "plan": {"batch_rate": 0.01},
        "data": {"kind": "dense", "shard_rows": [1_012_500], "d": 784,
                 "itemsize": 2},
        "peaks": {"hbm_bytes_per_s": 819e9},
    }


def _modules(**seconds_and_counts):
    """``trace_reduce``'s record of a module from (median_s, count)."""
    return {"modules": {
        name: {"count": n, "median_s": s, "total_s": s * n}
        for name, (s, n) in seconds_and_counts.items()
    }}


def test_the_readers_read_the_history_paths_spans_counters_and_modules():
    man = manifest_mod.Manifest()
    read = lambda name, run, trace=None: (  # noqa: E731
        man.metric_reader(name).read(run, trace))
    # a 3 s window of the cell since PR 28: 1,240 accepts, 7 of which paid
    # the second read
    trace = _modules(jit_step=(2.34e-3, 1240),
                     jit_saga_table_delta=(2.0e-3, 7),
                     jit_saga_commit_history=(2.5e-5, 1240))
    run = _record(
        extras={"updater_history_s": 1.0, "history_drift": 3e-6},
        stages={"merge.history": {"count": 5, "p50": 0.4}},
    )
    # an update's share of the deltas, plus its commit: not one delta's time
    assert read("history_device_ms", run, trace) == pytest.approx(
        (7 * 2.0 + 1240 * 0.025) / 1240)
    assert read("history_device_ms", run, trace) < 0.04
    # ``history_roofline`` left the manifest with PR 29; its arithmetic, for
    # whoever times ONE recomputed delta (``check_saga``'s builder), stays
    # in ``roofline_saga``: one read of the whole shard for a hundredth of
    # its rows is about 1% of the HBM peak
    need = roofline_saga.delta_bytes(run["data"], run["plan"]["batch_rate"])
    share = 100 * need / 2.0e-3 / run["peaks"]["hbm_bytes_per_s"]
    assert 0.9 < share < 1.2
    assert read("merge_history_p50_ms", run) == 0.4
    assert read("updater_history", run) == 5.0
    assert read("history_drift", run) == 3e-6


def test_history_device_ms_needs_no_table_delta_in_the_window():
    """With the standing sample gone a 3 s window can hold no
    ``jit_saga_table_delta`` at all: the reader then reports the commit
    alone, not nothing (PR 28's first traced run reported nothing, and the
    program has spent 0.35% of its rate on a sample since)."""
    man = manifest_mod.Manifest()
    read = man.metric_reader("history_device_ms").read
    none = _modules(jit_step=(2.34e-3, 1240),
                    jit_saga_commit_history=(2.5e-5, 1240))
    assert read(_record(), none) == pytest.approx(0.025)
    one = _modules(jit_step=(2.34e-3, 1240),
                   jit_saga_table_delta=(2.1e-3, 1),
                   jit_saga_commit_history=(2.5e-5, 1240))
    assert read(_record(), one) == pytest.approx(0.025 + 2.1 / 1240)
    # from the trace recorded on the chip (an ASGD cell): no commit, nothing
    import os

    from benchmark import trace_reduce

    here = os.path.dirname(os.path.abspath(__file__))
    asgd = trace_reduce.reduce_file(
        os.path.join(here, "fixtures", "mnist8m-asgd.steady.xplane.pb"))
    assert "jit_step" in asgd["modules"]
    assert read(_record(), asgd) is None
    # the same window with a history path's modules put in
    asgd["modules"]["jit_saga_commit_history"] = {
        "count": 40, "median_s": 2.4e-5, "total_s": 40 * 2.4e-5}
    assert read(_record(), asgd) == pytest.approx(0.024)


def test_the_readers_find_nothing_on_the_parent_or_in_an_asgd_cell():
    """PR 25's parent records neither the stage nor the counters, and its
    delta is the XLA module ``jit_delta``; an ASGD run has no history path
    at all: each reader returns None and the line leaves the metric out.
    The one reading that parent does give since the re-pointing is its
    commit, whose module has the name it has today: the commit's time an
    update, with no delta counted in."""
    man = manifest_mod.Manifest()
    parent_trace = _modules(jit_step=(4.26e-3, 466), jit_delta=(2.1e-3, 466),
                            jit_saga_commit_history=(2.4e-5, 466))
    asgd_trace = _modules(jit_step=(4.26e-3, 466), jit_apply=(9e-7, 466))
    run = _record(stages={"merge.apply": {"count": 3, "p50": 0.5}},
                  extras={"updater_apply_s": 1.7})
    for name in NEW:
        for trace in (asgd_trace, None):
            assert man.metric_reader(name).read(run, trace) is None, name
    for name in NEW[1:]:
        assert man.metric_reader(name).read(run, parent_trace) is None, name
    assert man.metric_reader(NEW[0]).read(run, parent_trace) == (
        pytest.approx(0.024))
    run["program_trace"] = None  # an untraced record
    for name in NEW:
        assert man.metric_reader(name).read(run, None) is None, name


#: the ASAGA rehearsal cells by how their shards are stored; the sparse one
#: is the next ``model_config`` PR's deployment at a CPU test's size, added
#: as a configuration file and two entries (ISSUE 45)
STORAGES = {"dense": "tiny-asaga.steady", "ell": "tiny-sparse-asaga.steady"}


@pytest.fixture(scope="module")
def saga_manifest(tmp_path_factory):
    """The real manifest's metrics over the two ASAGA rehearsal cells, with
    the history path's limited to them as the real ones are to theirs."""
    doc = dict(MANIFEST)
    cells = sorted(STORAGES.values())
    doc["configs"] = [
        {"name": c.split(".")[0], "source": "rehearsal", "reduced": [],
         "why": "rehearsal",
         "file": f"tests/benchmark/configs/{c.split('.')[0]}.json"}
        for c in cells
    ]
    doc["workloads"] = [
        {"name": c, "config": c.split(".")[0], "traffic": "steady",
         "chips": 1, "why": "rehearsal"} for c in cells
    ]
    doc["per_layer"] = [
        dict(m, workloads=cells) if m["name"] in NEW else m
        for m in MANIFEST["per_layer"]
    ]
    path = tmp_path_factory.mktemp("bench_saga") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


@pytest.mark.parametrize("storage", sorted(STORAGES))
def test_traced_rehearsal_reports_the_history_metrics_and_keeps_the_invariant(
        storage, saga_manifest, on_cpu, capsys, monkeypatch):
    from asyncframework_tpu import solvers

    cell = STORAGES[storage]

    runs = []
    real = solvers.ASAGA.run

    def spy(self):
        res = real(self)
        runs.append((self, res))
        return res

    monkeypatch.setattr(solvers.ASAGA, "run", spy)
    on_cpu(1)
    rc, lines = _run(capsys, saga_manifest, cell, trace=1)
    assert rc == 0
    got = json.loads(lines[-1])["metrics"]
    # the program's span and counters are read; on the CPU there is no
    # device plane, so the reader of the device trace finds nothing
    assert {"merge_history_p50_ms", "updater_history",
            "history_drift"} <= set(got), sorted(got)
    assert "history_device_ms" not in got
    assert got["merge_history_p50_ms"]["value"] >= 0.0
    assert 0.0 < got["updater_history"]["value"] <= got[
        "updater_busy"]["value"]
    assert got["history_drift"]["unit"] == "ratio"
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    extras = record["result"]["extras"]
    assert 0.0 < extras["updater_history_s"] <= extras["updater_apply_s"]
    # the checked run's final state against the benchmark's own reference:
    # alpha_bar is the mean of the table the run left (warm-up, the checked
    # run, the profiled run)
    assert len(runs) == 3
    solver, res = runs[1]
    shards = [solver.ds.shard(w) for w in range(solver.ds.num_workers)]
    alphas = [res.extras["alpha"][w] for w in range(len(shards))]
    d = solver.ds.d
    mean = reference_saga.history_mean(shards, alphas, solver.ds.n, d=d)
    unit = np.max(np.abs(reference_saga.history_mean(
        shards, [s.y for s in shards], solver.ds.n, d=d)))
    err = np.max(np.abs(np.asarray(res.extras["alpha_bar"], np.float64) - mean))
    # f32 sums on both sides, in units of the mean gradient at w = 0: 1e-7
    # to 5e-7 seen at the end of a 1.5 s run of this cell (thousands of
    # updates, converged), 1e-4 with a delta that rounds its vector
    assert err <= 5e-6 * unit
    assert res.extras["history_drift"] == pytest.approx(err / unit,
                                                        rel=0.05, abs=2e-7)
    assert got["history_drift"]["value"] == res.extras["history_drift"]


@pytest.fixture()
def control(monkeypatch):
    """``check_saga``'s negative control, undone after the test: it
    replaces ``steps.make_saga_apply`` for every solver built after it."""
    from asyncframework_tpu.ops import steps
    from benchmark import check_saga

    monkeypatch.setattr(steps, "make_saga_apply", steps.make_saga_apply)
    return check_saga._round_what_advances_alpha_bar


@pytest.mark.parametrize("rounded", [False, True], ids=["exact", "rounded"])
@pytest.mark.parametrize("storage", sorted(STORAGES))
def test_check_saga_holds_the_run_to_the_reference(
        storage, rounded, saga_manifest, on_cpu, capsys, control):
    """``benchmark/check_saga.py``: correct on the program as it is, NOT
    correct (its negative control) when the vector that advances
    ``alpha_bar`` is rounded to bf16 on every accept.  The control touches
    the apply alone: the one task's step, delta and commit stay exact.
    Over padded ELL the solver's step, table delta and commit are called
    with the shard's own operands and the packed payload."""
    from benchmark import check_saga

    assert control  # in place before main() applies the patch
    on_cpu(1)
    argv = ["--workload", STORAGES[storage], "--seed", "5",
            "--seconds", "1.0"] + (["--round-delta"] if rounded else [])
    rc = check_saga.main(argv, manifest_path=saga_manifest)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])["check_saga"]
    assert out["objective"]["within"], out
    assert out["task"]["within"] and out["task"]["sampled"] > 0, out
    assert out["history"]["limit"] == run.DRIFT_LIMIT
    assert ("by_column" in out["history"]) == (storage == "ell")
    if rounded:
        assert rc == 1 and not out["correct"]
        assert out["history"]["drift"] > 10 * run.DRIFT_LIMIT
    else:
        assert rc == 0 and out["correct"], out
        assert out["history"]["drift"] <= run.DRIFT_LIMIT
    if storage == "ell":
        assert out["history"]["by_column_limit"] == 5e-6
        assert (out["history"]["by_column"] > 5e-5) == rounded
    assert out["history"]["program_history_drift"] == pytest.approx(
        out["history"]["drift"], rel=0.05, abs=2e-7)


@pytest.mark.parametrize("rounded", [False, True], ids=["exact", "rounded"])
@pytest.mark.parametrize("storage", sorted(STORAGES))
def test_correct_reads_the_history(storage, rounded, saga_manifest, on_cpu,
                                   capsys, control):
    """A whole run of ``run.py`` with the timed path broken underneath: the
    final objective still crosses the target and agrees with the
    reference's, and ``correct`` comes out false by ``history_within``
    alone; without the control the same run is correct."""
    if rounded:
        control()
    on_cpu(1)
    rc, lines = _run(capsys, saga_manifest, STORAGES[storage], seed=11)
    assert rc == 0
    last = json.loads(lines[-1])
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    failing = sorted(k for k, ok in record["checks"].items() if not ok)
    drift = last["compared"]["history_within"]
    assert drift["limit"] == run.DRIFT_LIMIT
    # the sparse configuration asks for the gap column by column as well
    history = (["history_by_column", "history_within"] if storage == "ell"
               else ["history_within"])
    assert [k for k in last["compared"] if k.startswith("history")] == (
        history[::-1])
    if rounded:
        assert last["correct"] is False and failing == history
        assert drift["value"] > 10 * run.DRIFT_LIMIT
    else:
        assert last["correct"] is True and failing == []
        assert 0.0 < drift["value"] <= run.DRIFT_LIMIT
    if storage == "ell":
        by_column = last["compared"]["history_by_column"]
        assert by_column["limit"] == 5e-6
        assert (by_column["value"] > 10 * 5e-6) == rounded
    assert drift["value"] == pytest.approx(
        record["result"]["extras"]["history_drift"], rel=0.05, abs=2e-7)


def test_the_compared_numbers_end_stderr(saga_manifest, on_cpu, capfd,
                                         control):
    """Each number compared beside its limit is the end of a run's stderr,
    where the record of a refused run keeps it; the one that failed says
    so."""
    control()
    on_cpu(1)
    rc = run.main(["--workload", "tiny-asaga.steady", "--seed", "12",
                   "--seconds", "1.5", "--trace", "0"],
                  manifest_path=saga_manifest)
    assert rc == 0
    out, err = capfd.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    tail = [ln for ln in err.splitlines() if "cpu_aot_loader" not in ln]
    tail = tail[-len(last["compared"]):]
    assert [ln.split()[1].rstrip(":") for ln in tail] == list(last["compared"])
    assert all(ln.startswith("compared ") for ln in tail)
    off = [ln for ln in tail if ln.endswith("NOT WITHIN")]
    assert len(off) == 1 and off[0].startswith("compared history_within: ")


def test_the_reference_task_equals_float64_arithmetic():
    """The reference itself against numpy in float64, blocks that do not
    divide the shard (the last block is clamped and masked)."""
    import jax.numpy as jnp

    rs = np.random.default_rng(7)
    rows, d = 1000, 24
    X = rs.standard_normal((rows, d)).astype(np.float32)
    y = rs.standard_normal(rows).astype(np.float32)
    w = rs.standard_normal(d).astype(np.float32)
    a_read = rs.standard_normal(rows).astype(np.float32)
    a_cur = rs.standard_normal(rows).astype(np.float32)
    mask = (rs.random(rows) < 0.3).astype(np.float32)

    class Shard:
        pass

    shard = Shard()
    shard.X, shard.y = jnp.asarray(X), jnp.asarray(y)
    out = reference_saga.task(shard, w, a_read, a_cur, mask, block_rows=384)
    X64 = X.astype(np.float64)
    diff = X64 @ w - y
    np.testing.assert_allclose(out["diff"], diff, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out["g"], X64.T @ (mask * (diff - a_read)),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(out["delta"], X64.T @ (mask * (diff - a_cur)),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(out["alpha"], np.where(mask > 0, diff, a_cur),
                               rtol=1e-5, atol=1e-5)
    mean = reference_saga.history_mean([shard, shard], [a_cur, a_read],
                                       2 * rows, block_rows=384)
    np.testing.assert_allclose(mean, X64.T @ (a_cur + a_read) / (2 * rows),
                               rtol=1e-5, atol=1e-6)
    # a replay of one group of one task is that task's update
    rep = reference_saga.saga_replay([shard], [mask], [0], gamma=0.5,
                                     batch_rate=0.3, n=rows, block_rows=384)
    g0 = X64.T @ (mask * (0.0 - y))  # w = 0 and an empty table
    np.testing.assert_allclose(rep["w"], -0.5 / (0.3 * rows) * g0,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(rep["alpha_bar"], g0 / rows,
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError):
        reference_saga.saga_replay([shard], [mask], [0], 0.5, 0.3, rows,
                                   group=2)


# ------------------------------------------------ padded ELL (ISSUE 45)


class _Shard:
    pass


def _ell_shard(rs, rows, d, width, hot=5):
    """A seeded padded-ELL shard and its densified float64 matrix: rows of
    1 to ``width`` values packed to the left, padding slots ``col=0,
    val=0`` behind them, every third row with one column twice, and column
    ``hot`` in half of the rows (a long chain of one column's terms)."""
    import jax.numpy as jnp

    cols = np.zeros((rows, width), np.int32)
    vals = np.zeros((rows, width), np.float32)
    for i in range(rows):
        k = int(rs.integers(1, width + 1))
        c = rs.integers(0, d, k)
        if k >= 2 and i % 3 == 0:
            c[1] = c[0]
        if i % 2 == 0:
            c[-1] = hot
        cols[i, :k] = c
        vals[i, :k] = rs.standard_normal(k)
    dense = np.zeros((rows, d), np.float64)
    np.add.at(dense, (np.repeat(np.arange(rows), width), cols.ravel()),
              vals.ravel().astype(np.float64))
    y = rs.standard_normal(rows).astype(np.float32)
    ell, full = _Shard(), _Shard()
    ell.cols, ell.vals, ell.y = (jnp.asarray(a) for a in (cols, vals, y))
    full.X, full.y = jnp.asarray(dense.astype(np.float32)), ell.y
    assert (vals[:, -1] == 0).any() and (np.diff(cols, axis=1) == 0).any()
    return ell, full, dense


def _close(got, want, tol=1e-6):
    """Within ``tol`` of the largest entry, as the limits are stated."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


ELL_CASES = {
    # rows and stored width of each shard
    "one_width": [(1000, 8), (1000, 8)],
    "unequal_widths": [(1000, 8), (700, 24)],
}


@pytest.mark.parametrize("case", sorted(ELL_CASES))
@pytest.mark.parametrize("fn", ["history_mean", "history_drift", "task",
                                "saga_replay"])
def test_the_ell_reference_equals_float64_arithmetic(fn, case):
    """Each function of ``reference_saga`` over seeded padded-ELL shards
    (padding slots, a column twice in a row, blocks that do not divide the
    shard) against float64 numpy on the densified matrix, and against its
    own dense path on the densified shards."""
    rs = np.random.default_rng(45)
    d = 64
    built = [_ell_shard(rs, rows, d, width) for rows, width in ELL_CASES[case]]
    ell, full, X64 = ([b[i] for b in built] for i in range(3))
    n = sum(x.shape[0] for x in X64)
    alphas = [rs.standard_normal(x.shape[0]).astype(np.float32) for x in X64]
    want_mean = sum(x.T @ a for x, a in zip(X64, alphas)) / n
    if fn == "history_mean":
        got = reference_saga.history_mean(ell, alphas, n, 384, d=d)
        _close(got, want_mean)
        _close(got, reference_saga.history_mean(full, alphas, n, 384))
        with pytest.raises(ValueError, match="pass d="):
            reference_saga.history_mean(ell, alphas, n)
    elif fn == "history_drift":
        unit = np.max(np.abs(sum(x.T @ np.asarray(s.y, np.float64)
                                 for x, s in zip(X64, ell)) / n))
        assert reference_saga.history_drift(
            ell, alphas, want_mean, n, 384, d=d) < 1e-6
        off = want_mean.copy()
        off[17] += 1e-3 * unit
        for shards in (ell, full):
            assert reference_saga.history_drift(
                shards, alphas, off, n, 384, d=d) == pytest.approx(
                    1e-3, rel=1e-3)
    elif fn == "task":
        w = rs.standard_normal(d).astype(np.float32)
        for shard, dense_shard, x in zip(ell, full, X64):
            rows = x.shape[0]
            a_read = rs.standard_normal(rows).astype(np.float32)
            a_cur = rs.standard_normal(rows).astype(np.float32)
            mask = (rs.random(rows) < 0.3).astype(np.float32)
            out = reference_saga.task(shard, w, a_read, a_cur, mask, 384)
            diff = x @ w - np.asarray(shard.y, np.float64)
            want = {"diff": diff, "g": x.T @ (mask * (diff - a_read)),
                    "delta": x.T @ (mask * (diff - a_cur)),
                    "alpha": np.where(mask > 0, diff, a_cur)}
            same = reference_saga.task(dense_shard, w, a_read, a_cur, mask,
                                       384)
            for key in want:
                _close(out[key], want[key], 2e-6)
                _close(out[key], same[key], 2e-6)
    else:
        order = [0, 1, 1, 0, 1, 0]
        masks = [(rs.random(X64[k].shape[0]) < 0.2).astype(np.float32)
                 for k in order]
        for group in (1, 2):
            kw = dict(gamma=0.3, batch_rate=0.2, n=n, group=group,
                      block_rows=384)
            got = reference_saga.saga_replay(ell, masks, order, d=d, **kw)
            want = reference_saga.saga_replay(full, masks, order, **kw)
            _close(got["w"], want["w"], 5e-6)
            _close(got["alpha_bar"], want["alpha_bar"], 5e-6)
            for a, b in zip(got["alpha"], want["alpha"]):
                _close(a, b, 5e-6)
            # the replay keeps its own invariant over padded ELL
            assert reference_saga.history_drift(
                ell, got["alpha"], got["alpha_bar"], n, 384, d=d) < 2e-6
        with pytest.raises(ValueError, match="pass d="):
            reference_saga.saga_replay(ell, masks, order, 0.3, 0.2, n)


def test_the_gap_by_column_is_in_each_columns_own_unit():
    """``history_by_column``: the drift as ``history_drift`` reads it, and
    the same gap over each column's mean absolute value, from one pass: a
    gap on a light column counts in that column's own unit.  Padded ELL
    only."""
    rs = np.random.default_rng(12)
    d = 64
    built = [_ell_shard(rs, rows, d, width) for rows, width in
             ELL_CASES["unequal_widths"]]
    ell, full, X64 = ([b[i] for b in built] for i in range(3))
    n = sum(x.shape[0] for x in X64)
    alphas = [rs.standard_normal(x.shape[0]).astype(np.float32) for x in X64]
    mean = sum(x.T @ a for x, a in zip(X64, alphas)) / n
    # slot by slot: a column twice in a row weighs twice, whatever the signs
    mass = np.zeros(d)
    for shard in ell:
        np.add.at(mass, np.asarray(shard.cols).ravel(),
                  np.abs(np.asarray(shard.vals, np.float64)).ravel())
    mass /= n
    light = int(np.argmin(np.where(mass > 0, mass, np.inf)))
    assert mass[light] < 0.2 * mass.max()
    off = mean.copy()
    off[light] += 1e-3 * mass[light]
    got = reference_saga.history_by_column(ell, alphas, off, n, 384, d=d)
    assert got["by_column"] == pytest.approx(1e-3, rel=1e-3)
    assert got["drift"] == pytest.approx(
        reference_saga.history_drift(ell, alphas, off, n, 384, d=d),
        rel=1e-6)
    unit = np.max(np.abs(sum(x.T @ np.asarray(s.y, np.float64)
                             for x, s in zip(X64, ell)) / n))
    assert got["drift"] == pytest.approx(1e-3 * mass[light] / unit, rel=1e-3)
    exact = reference_saga.history_by_column(ell, alphas, mean, n, 384, d=d)
    assert exact["by_column"] < 2e-6 and exact["drift"] < 1e-6
    with pytest.raises(ValueError, match="padded ELL only"):
        reference_saga.history_by_column(full, alphas, mean, n, 384, d=d)


def test_an_ell_block_is_bounded_in_slots(monkeypatch):
    """A shard thousands of slots wide is walked a few rows at a time: a
    block holds ``ELL_BLOCK_SLOTS`` slots at the most, whatever
    ``block_rows`` says, and the sums do not depend on the blocks."""
    rs = np.random.default_rng(3)
    ell, _full, x = _ell_shard(rs, 300, 64, 24)
    a = rs.standard_normal(300).astype(np.float32)
    monkeypatch.setattr(reference_saga, "ELL_BLOCK_SLOTS", 24 * 50)
    block, starts = reference_saga._shard_blocks(ell, 65536)
    assert (block, list(starts)) == (50, [0, 50, 100, 150, 200, 250])
    _close(reference_saga.history_mean([ell], [a], 300, d=64), x.T @ a / 300)
    monkeypatch.setattr(reference_saga, "ELL_BLOCK_SLOTS", 7)
    assert reference_saga._shard_blocks(ell, 65536)[0] == 1


@pytest.mark.parametrize("hot", [1, 128])
def test_the_hot_columns_are_summed_apart(hot, monkeypatch):
    """The columns that fill the most slots of a shard's first block are
    summed by the row, not by the scatter-add's chain, and written over its
    entries; how many are set apart does not change the answer."""
    rs = np.random.default_rng(9)
    ell, _full, x = _ell_shard(rs, 600, 64, 8, hot=11)
    monkeypatch.setattr(reference_saga, "ELL_HOT_COLUMNS", hot)
    got = np.asarray(reference_saga._hot_columns(ell, 64, 600))
    # column 0 holds the padding slots too; 11 is in half of the rows
    assert got.shape == (min(hot, 64),) and set(got[:2]) <= {0, 11}
    a = rs.standard_normal(600).astype(np.float32)
    _close(reference_saga.history_mean([ell], [a], 600, d=64), x.T @ a / 600)
    mask = (rs.random(600) < 0.3).astype(np.float32)
    out = reference_saga.task(ell, np.ones(64), a, a, mask)
    diff = x.sum(axis=1) - np.asarray(ell.y, np.float64)
    _close(out["g"], x.T @ (mask * (diff - a)), 2e-6)


def _parents_history_mean(shards, alphas, n, block_rows):
    """PR 43's ``history_mean``, letter for letter: what the dense path of
    today's has to equal to the bit."""
    from benchmark.reference import _f32

    total = None
    for shard, alpha in zip(shards, alphas):
        rows = int(shard.X.shape[0])
        a = _f32(alpha, shard.X.device)
        block = min(block_rows, rows)
        for start in range(0, rows, block):
            part = np.asarray(reference_saga._mean_block(
                shard.X, a, start, block=block), np.float64)
            total = part if total is None else total + part
    return total / n


def test_the_dense_path_is_the_parents_to_the_bit():
    """The fixtures of ``test_the_reference_task_equals_float64_arithmetic``
    through today's functions and through the parent's loop (the same
    blocks, the same order of sums, two passes): equal to the bit, as
    ``mnist8m-asaga.steady``'s ``history_within`` has to be on one seed.
    The pinned numbers are the parent's on this installation, to the digits
    a float32 sum in another instruction order would already move."""
    import jax.numpy as jnp

    rs = np.random.default_rng(7)
    rows, d = 1000, 24
    X = rs.standard_normal((rows, d)).astype(np.float32)
    y = rs.standard_normal(rows).astype(np.float32)
    rs.standard_normal(d)  # w of the other test: the same stream
    a_read = rs.standard_normal(rows).astype(np.float32)
    a_cur = rs.standard_normal(rows).astype(np.float32)
    shard = _Shard()
    shard.X, shard.y = jnp.asarray(X), jnp.asarray(y)
    shards, alphas, n = [shard, shard], [a_cur, a_read], 2 * rows
    mean = reference_saga.history_mean(shards, alphas, n, block_rows=384)
    want = _parents_history_mean(shards, alphas, n, 384)
    assert mean.dtype == np.float64 and mean.tobytes() == want.tobytes()
    unit = np.max(np.abs(_parents_history_mean(shards, [y, y], n, 384)))
    ab = np.asarray(mean, np.float32)
    drift = reference_saga.history_drift(shards, alphas, ab, n, 384)
    assert drift == float(np.max(np.abs(ab.astype(np.float64) - want)) / unit)
    assert drift == reference_saga.history_drift(shards, alphas, ab, n, 384,
                                                 d=d)
    np.testing.assert_allclose(
        [mean[0], mean[23], np.max(np.abs(mean)), unit, drift],
        PARENT_PINS, rtol=1e-6)


#: ``mean[0]``, ``mean[23]``, ``max |mean|``, the unit and the drift of
#: the float32-rounded mean, from PR 43's tree (commit 662c65b) on the CPU
PARENT_PINS = [0.028301922082901002, 0.021538020849227905,
               0.05926655673980713, 0.07578340911865235,
               1.120781182017785e-08]


def test_one_rounded_delta_on_an_ell_table_reads_over_the_limit():
    """On the ``tiny-sparse-asaga`` dataset: a table (a replay of 24
    accepts) and its exact mean read under 1e-6; the same mean moved on by
    one more accept's exact delta stays there, and by that delta rounded to
    bf16 reads over ``run.DRIFT_LIMIT``."""
    import jax

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "tiny-sparse-asaga.json")) as f:
        cfg = json.load(f)
    assert (cfg["kind"], cfg["solver"]) == ("sparse", "asaga")
    ds = run.build_dataset(cfg, 8, jax.devices()[:1], seed=2_147_483_659)
    shards = [ds.shard(w) for w in range(8)]
    drift = lambda alphas, ab: reference_saga.history_drift(  # noqa: E731
        shards, alphas, ab, ds.n, d=ds.d)
    rs = np.random.default_rng(1)
    order = [k % 8 for k in range(24)]
    masks = [(rs.random(512) < cfg["batch_rate"]).astype(np.float32)
             for _ in order]
    rep = reference_saga.saga_replay(shards, masks, order, cfg["gamma"],
                                     cfg["batch_rate"], ds.n, d=ds.d)
    mean = reference_saga.history_mean(shards, rep["alpha"], ds.n, d=ds.d)
    assert drift(rep["alpha"], mean) < 1e-6
    assert drift(rep["alpha"], rep["alpha_bar"]) < 1e-6
    mask = (rs.random(512) < cfg["batch_rate"]).astype(np.float32)
    out = reference_saga.task(shards[3], rep["w"], rep["alpha"][3],
                              rep["alpha"][3], mask)
    alphas = list(rep["alpha"])
    alphas[3] = out["alpha"]
    exact = np.asarray(out["delta"], np.float64)
    rounded = np.asarray(jax.lax.reduce_precision(out["delta"], 8, 7),
                         np.float64)
    assert drift(alphas, mean + exact / ds.n) < 1e-6
    assert drift(alphas, mean + rounded / ds.n) > 10 * run.DRIFT_LIMIT


def test_a_configuration_may_state_its_own_drift_limit(
        saga_manifest, on_cpu, capsys, control, tmp_path):
    """``pins["history_drift_limit"]`` is ``history_within``'s limit where a
    configuration states it (the readings over padded ELL at a chip's size
    lie elsewhere than a dense shard's: PERF.md section 7); absent, it is
    ``run.DRIFT_LIMIT``, as in every cell of the tree today."""
    man = manifest_mod.Manifest()
    for cell in MANIFEST["workloads"]:
        assert not [k for k in man.config(cell["config"])["pins"]
                    if k.startswith("history")]
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "tiny-sparse-asaga.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-sparse-asaga-own-limit"
    cfg["pins"]["history_drift_limit"] = 1.0  # the control passes under it
    del cfg["pins"]["history_by_column_limit"]
    doc = json.load(open(saga_manifest))
    rel = os.path.relpath(tmp_path / "own.json", manifest_mod.ROOT)
    (tmp_path / "own.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": cfg["name"], "source": "rehearsal",
                           "reduced": [], "why": "rehearsal", "file": rel})
    doc["workloads"].append({"name": cfg["name"] + ".steady",
                             "config": cfg["name"], "traffic": "steady",
                             "chips": 1, "why": "rehearsal"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    control()
    on_cpu(1)
    rc, lines = _run(capsys, str(path), cfg["name"] + ".steady", seed=13)
    last = json.loads(lines[-1])
    assert rc == 0 and last["correct"] is True
    drift = last["compared"]["history_within"]
    assert drift["limit"] == 1.0 and drift["value"] > 10 * run.DRIFT_LIMIT
    assert "history_by_column" not in last["compared"]  # no limit, no pass
