"""CPU rehearsal of what ISSUE 25 gave the benchmark: the ``mnist8m-asaga``
configuration and its cell, the plain ASAGA reference
(``benchmark/reference_saga.py``), the table delta's byte count
(``benchmark/roofline_saga.py``), the per-layer metrics of the history
path and the comparison at a cell's size (``benchmark/check_saga.py``), on
the existing ``tiny-asaga`` configuration; and of what ISSUE 29 made of
them: ``correct`` reads the history in every run of an ASAGA cell, the
control rounds what advances ``alpha_bar`` on every accept,
``history_device_ms`` is the history path's device time an update, and
``history_roofline`` is gone (its fusion runs on 0.6% of the accepts)."""

import json

import numpy as np
import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    MANIFEST,
    PER_LAYER,
    TINY_CELLS,
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


@pytest.fixture(scope="module")
def saga_manifest(tmp_path_factory):
    """The real manifest's metrics over ``tiny-asaga.steady``, with the
    history path's limited to that cell as the real ones are to theirs."""
    doc = dict(MANIFEST)
    doc["configs"] = [
        {"name": "tiny-asaga", "source": "rehearsal", "reduced": [],
         "why": "rehearsal", "file": "tests/benchmark/configs/tiny-asaga.json"}
    ]
    doc["workloads"] = [
        {"name": "tiny-asaga.steady", "config": "tiny-asaga",
         "traffic": "steady", "chips": 1, "why": "rehearsal"}
    ]
    doc["per_layer"] = [
        dict(m, workloads=["tiny-asaga.steady"]) if m["name"] in NEW else m
        for m in MANIFEST["per_layer"]
    ]
    path = tmp_path_factory.mktemp("bench_saga") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_traced_rehearsal_reports_the_history_metrics_and_keeps_the_invariant(
        saga_manifest, on_cpu, capsys, monkeypatch):
    from asyncframework_tpu import solvers

    runs = []
    real = solvers.ASAGA.run

    def spy(self):
        res = real(self)
        runs.append((self, res))
        return res

    monkeypatch.setattr(solvers.ASAGA, "run", spy)
    on_cpu(TINY_CELLS["tiny-asaga.steady"][2])
    rc, lines = _run(capsys, saga_manifest, "tiny-asaga.steady", trace=1)
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
    mean = reference_saga.history_mean(shards, alphas, solver.ds.n)
    unit = np.max(np.abs(reference_saga.history_mean(
        shards, [s.y for s in shards], solver.ds.n)))
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
def test_check_saga_holds_the_run_to_the_reference(
        rounded, saga_manifest, on_cpu, capsys, control):
    """``benchmark/check_saga.py``: correct on the program as it is, NOT
    correct (its negative control) when the vector that advances
    ``alpha_bar`` is rounded to bf16 on every accept.  The control touches
    the apply alone: the one task's step, delta and commit stay exact."""
    from benchmark import check_saga

    assert control  # in place before main() applies the patch
    on_cpu(TINY_CELLS["tiny-asaga.steady"][2])
    argv = ["--workload", "tiny-asaga.steady", "--seed", "5",
            "--seconds", "1.0"] + (["--round-delta"] if rounded else [])
    rc = check_saga.main(argv, manifest_path=saga_manifest)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])["check_saga"]
    assert out["objective"]["within"], out
    assert out["task"]["within"] and out["task"]["sampled"] > 0, out
    assert out["history"]["limit"] == run.DRIFT_LIMIT
    if rounded:
        assert rc == 1 and not out["correct"]
        assert out["history"]["drift"] > 10 * run.DRIFT_LIMIT
    else:
        assert rc == 0 and out["correct"], out
        assert out["history"]["drift"] <= run.DRIFT_LIMIT
    assert out["history"]["program_history_drift"] == pytest.approx(
        out["history"]["drift"], rel=0.05, abs=2e-7)


@pytest.mark.parametrize("rounded", [False, True], ids=["exact", "rounded"])
def test_correct_reads_the_history(rounded, saga_manifest, on_cpu, capsys,
                                   control):
    """A whole run of ``run.py`` with the timed path broken underneath: the
    final objective still crosses the target and agrees with the
    reference's, and ``correct`` comes out false by ``history_within``
    alone; without the control the same run is correct."""
    if rounded:
        control()
    on_cpu(TINY_CELLS["tiny-asaga.steady"][2])
    rc, lines = _run(capsys, saga_manifest, "tiny-asaga.steady", seed=11)
    assert rc == 0
    last = json.loads(lines[-1])
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    failing = sorted(k for k, ok in record["checks"].items() if not ok)
    drift = last["compared"]["history_within"]
    assert drift["limit"] == run.DRIFT_LIMIT
    if rounded:
        assert last["correct"] is False and failing == ["history_within"]
        assert drift["value"] > 10 * run.DRIFT_LIMIT
    else:
        assert last["correct"] is True and failing == []
        assert 0.0 < drift["value"] <= run.DRIFT_LIMIT
    assert drift["value"] == pytest.approx(
        record["result"]["extras"]["history_drift"], rel=0.05, abs=2e-7)


def test_the_compared_numbers_end_stderr(saga_manifest, on_cpu, capfd,
                                         control):
    """Each number compared beside its limit is the end of a run's stderr,
    where the record of a refused run keeps it; the one that failed says
    so."""
    control()
    on_cpu(TINY_CELLS["tiny-asaga.steady"][2])
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
