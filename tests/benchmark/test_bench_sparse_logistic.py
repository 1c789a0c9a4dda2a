"""CPU rehearsal of what ISSUE 32 gave the benchmark: the
``criteo-logistic-asgd`` configuration and its cell, three per-layer metrics
read from the program's counters, the comparison at a cell's size
(``benchmark/check_sparse.py``), and the rehearsal configuration
``tiny-sparse-logistic`` (skewed columns, unit rows, {0,1} labels, the
logistic loss on padded ELL) through ``run.py`` as it stands."""

import json

import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    E2E,
    MANIFEST,
    PER_LAYER,
    RESULT_KEYS,
    _run,
    on_cpu,
)

from benchmark import check_sparse, manifest as manifest_mod, roofline, run

CONFIG = "criteo-logistic-asgd"
CELL = CONFIG + ".steady"
NEW = ["trajectory_eval_s", "step_slot_ns", "eval_slot_ns"]
TINY = "tiny-sparse-logistic.steady"


def test_the_manifest_appends_one_configuration_one_cell_three_metrics():
    # found by name: later PRs append behind these, so no tail is pinned
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["n"] and len(entry["source"]) <= 200
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "steady", 1)
    first = PER_LAYER.index(NEW[0])
    assert first >= 22 and PER_LAYER[first:first + 3] == NEW
    for m in MANIFEST["per_layer"][first:first + 3]:
        assert m["workloads"] == [CELL] and m["better"] == "lower"
    man = manifest_mod.Manifest()
    # every accepted metric without a cell list reports in the new cell
    # too; the three in no other; ``updates_per_apply`` lists its cells
    cells = {m["name"] for m in man.metric_entries("per_layer", CELL)}
    assert set(PER_LAYER[:16]) | set(NEW) <= cells
    assert "updates_per_apply" not in cells
    for other in MANIFEST["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m["name"] for m in man.metric_entries(
                "per_layer", other["name"])}


def test_the_configuration_keeps_its_sources_shape_and_fills_a_chip():
    """(That ``d`` and the non-zeros a row are the published ones, ``n`` is
    listed and accounted for, and the generator's keys bind is what
    ``test_bench_harness`` holds every configuration of the manifest to.)"""
    config = manifest_mod.Manifest().config(CONFIG)
    pub = config["published"]
    assert (pub["n"], pub["d"], pub["nnz_per_row"]) == (45_840_617, 1_000_000, 39)
    assert (config["kind"], config["storage_dtype"]) == ("sparse", "float32")
    assert (config["solver"], config["loss"], config["num_workers"],
            config["batch_rate"], config["bucket_ratio"]) == (
        "asgd", "logistic", 8, 0.05, 0.7)
    # half of the set, in eight shards of ONE shape (one compile of a step)
    assert config["n"] % 8 == 0 and abs(config["n"] - pub["n"] / 2) < 8
    # f32 values + int32 columns in 40 slots a row, a label a row: over the
    # floor of a quarter of the chip's 16 GB
    held = config["n"] * (40 * 8 + 4)
    assert held >= 0.25 * 16e9 and held < 0.6 * 16e9
    pins = config["pins"]
    assert (pins["ell_width"], pins["nnz_per_row"], pins["shard_dtype"]) == (
        40, 39, "float32")
    share = config["generator"]["bernoulli_labels"]["positive_share"]
    # y^2 = y: the labels' second moment is the share of clicks
    assert pins["label_second_moment_min"] < share < pins["label_second_moment_max"]
    assert config["generator"]["column_skew"] == 1.0
    assert config["noise"] == 0.0 and config["generator"]["unit_values"] is True
    # the target lies over the entropy of the click share (what no feature
    # at all reaches) ... and under f(0) = ln 2
    import math

    h = -(share * math.log(share) + (1 - share) * math.log(1 - share))
    assert h / math.log(2) < config["target_fraction"] < 1.0
    for key in ("gamma", "data", "noise", "target_fraction", "printer_freq",
                "sizes", "column_skew", "bernoulli_labels"):
        assert len(config["assumed"][key]) > 20, key
    # the step's needed bytes: 143,252 sampled rows of 40 slots x 8 B
    need = roofline.sparse_step_bytes(config["n"] // 8, 40, config["d"],
                                      0.05, 4, 4)
    assert need == pytest.approx(143_251.95 * 320 + 2_865_039
                                 + 143_251.95 * 4 + 8_000_000)


def _record(extras):
    return {"result": {"elapsed_s": 20.0, "accepted": 80, "extras": extras}}


def test_the_readers_read_the_programs_counters():
    man = manifest_mod.Manifest()
    read = lambda name, run, trace=None: (  # noqa: E731
        man.metric_reader(name).read(run, trace))
    extras = {"trajectory_eval_s": 14.0, "eval_slots": 2 * 88 * 262_144 * 40,
              "eval_blocks": 176, "eval_snapshots": 16,
              "sampled_slots_per_step": 145_472 * 40}
    trace = {"modules": {"jit_step": {"count": 12, "median_s": 0.25,
                                      "total_s": 3.0}}}
    assert read("trajectory_eval_s", _record(extras)) == 14.0
    assert read("eval_slot_ns", _record(extras)) == pytest.approx(
        14.0 / (2 * 88 * 262_144 * 40) * 1e9)
    assert read("step_slot_ns", _record(extras), trace) == pytest.approx(
        0.25 / (145_472 * 40) * 1e9)
    # a program without the counters (the parent commit), a dense cell, a
    # run without a device trace: nothing, and no raise
    for name in NEW:
        assert read(name, _record({}), trace) is None
    assert read("step_slot_ns", _record(extras), None) is None
    assert read("step_slot_ns", _record(extras), {"modules": {}}) is None
    assert read("eval_slot_ns", _record({"trajectory_eval_s": 0.4})) is None


@pytest.fixture(scope="module")
def sparse_manifest(tmp_path_factory):
    """The real manifest's metrics over the rehearsal configuration: its
    cell is an entry, and the three new metrics list it."""
    doc = json.loads(json.dumps(MANIFEST))
    doc["configs"] = [{
        "name": "tiny-sparse-logistic", "source": "rehearsal", "reduced": [],
        "why": "rehearsal",
        "file": "tests/benchmark/configs/tiny-sparse-logistic.json"}]
    doc["workloads"] = [{"name": TINY, "config": "tiny-sparse-logistic",
                         "traffic": "steady", "chips": 1, "why": "rehearsal"}]
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = [TINY]
    path = tmp_path_factory.mktemp("bench_sparse") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_rehearsal_cell_runs_the_sparse_logistic_path(
        sparse_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, sparse_manifest, TINY, seed=2_147_483_659)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS and set(last["metrics"]) == set(E2E)
    assert last["correct"] is True, lines[-2]
    for name, c in last["compared"].items():
        assert c["value"] <= c["limit"], name
    assert "nnz_per_row" in last["compared"]
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    assert record["target"]["f0"] == pytest.approx(0.693147, rel=1e-5)
    assert 0.2 < record["pins"]["label_second_moment"] < 0.32
    assert record["pins"]["row_second_moment"] == pytest.approx(1.0, abs=1e-5)
    extras = record["result"]["extras"]
    assert extras["eval_snapshots"] == record["snapshots"]
    assert extras["model_bytes"] == 4 * 512
    # the program's reading and the harness's own (which also holds the
    # teardown) stand side by side
    said = json.loads(lines[-2])["info"]
    assert 0 < extras["trajectory_eval_s"] <= said["trajectory_eval_s"] + 0.05


def test_traced_rehearsal_reports_the_new_metrics(
        sparse_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, sparse_manifest, TINY, trace=1, seed=11)
    assert rc == 0
    last = json.loads(lines[-1])
    got = last["metrics"]
    # on the CPU there is no device plane: ``step_slot_ns`` finds nothing
    assert {"trajectory_eval_s", "eval_slot_ns"} <= set(got)
    assert "step_slot_ns" not in got and "step_device_ms" not in got
    assert got["trajectory_eval_s"]["value"] > 0
    assert got["eval_slot_ns"]["value"] > 0
    assert last["correct"] is True
    infos = [json.loads(ln)["info"] for ln in lines[:-1]]
    record = [i for i in infos if "checks" in i][0]
    assert record["result"]["extras"]["eval_blocks"] >= 8
    # the profiled run keeps w = 0, the model after update 1 and the final
    # one: one call of eight a shard
    prof = [i for i in infos if "profiled_run" in i][0]["profiled_run"]
    assert prof["extras"]["eval_snapshots"] == 3
    assert prof["extras"]["eval_blocks"] == 8


def test_the_trajectory_eval_stage_is_among_the_programs_spans(
        sparse_manifest, on_cpu, capsys, monkeypatch):
    """``run.py`` hands the aggregator's snapshot to the metric readers as
    ``program_trace``: a reader spied here sees the stage in it."""
    on_cpu(1)
    seen = {}
    real = manifest_mod.Manifest.read_metrics

    def read_metrics(self, kind, workload, record, trace):
        seen.update(record["program_trace"]["stages_ms"])
        return real(self, kind, workload, record, trace)

    monkeypatch.setattr(manifest_mod.Manifest, "read_metrics", read_metrics)
    rc, _lines = _run(capsys, sparse_manifest, TINY, trace=1, seed=12)
    assert rc == 0
    assert seen["trajectory.eval"]["count"] == 1
    assert "merge.apply" in seen and "compute" in seen


@pytest.mark.parametrize("control", [False, True], ids=["sound", "bf16-model"])
def test_check_sparse_holds_step_and_evaluation_to_the_reference(
        sparse_manifest, on_cpu, capsys, control):
    on_cpu(1)
    argv = ["--workload", TINY, "--seed", "2147483659"]
    rc = check_sparse.main(argv + (["--bf16-model"] if control else []),
                           manifest_path=sparse_manifest)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])["check_sparse"]
    assert out["bf16_model"] is control
    assert out["step"]["sampled"] > 0 and out["evaluation"]["snapshots"] == 8
    assert out["step"]["slot_ns"] > 0 and out["evaluation"]["slot_ns"] > 0
    if control:
        # a model rounded to bf16 is NOT correct, by both limits
        assert rc == 1 and out["correct"] is False
        assert out["step"]["off"] > out["step"]["limit"]
        assert out["evaluation"]["off"] > out["evaluation"]["limit"]
    else:
        assert rc == 0 and out["correct"] is True


def test_check_sparse_refuses_a_cell_that_is_not_sparse_asgd():
    with pytest.raises(ValueError, match="no sparse ASGD cell"):
        check_sparse.main(["--workload", "mnist8m-asgd.steady", "--seed", "1"])
