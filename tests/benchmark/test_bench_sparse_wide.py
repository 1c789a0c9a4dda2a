"""CPU rehearsal of what ISSUE 37 gave the benchmark: the
``kdd2012-logistic-asgd`` configuration and its cell, three per-layer
metrics (the apply's device time and its share of the HBM roofline, read
from a device trace; the engine's count of model-sized buffers, read from
the program's counters), the apply's byte count, and the rehearsal
configuration ``tiny-sparse-wide`` (11 non-zeros in 16 slots, a width that
is no multiple of 8 and 26 times the slots a step samples, rare clicks)
through ``run.py`` as it stands."""

import json
import math

import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    E2E,
    MANIFEST,
    PER_LAYER,
    RESULT_KEYS,
    _run,
    on_cpu,
)

from benchmark import check_sparse, manifest as manifest_mod
from benchmark import roofline, roofline_apply

CONFIG = "kdd2012-logistic-asgd"
CELL = CONFIG + ".steady"
NEW = ["apply_device_ms", "apply_roofline", "model_copies_peak"]
TINY = "tiny-sparse-wide.steady"
MODEL_BYTES = 4 * 54_686_452


def test_the_manifest_appends_one_configuration_one_cell_three_metrics():
    # found by name: later PRs append behind these, so no tail is pinned
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["n"] and len(entry["source"]) <= 200
    assert "kdd2012" in entry["source"] and "54,686,452" in entry["source"]
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "steady", 1)
    first = PER_LAYER.index(NEW[0])
    assert first >= 32 and PER_LAYER[first:first + 3] == NEW
    for m in MANIFEST["per_layer"][first:first + 3]:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s"
    man = manifest_mod.Manifest()
    # every accepted metric without a cell list reports in the new cell
    # too; the three in no other; the criteo cell's three stay its own
    cells = {m["name"] for m in man.metric_entries("per_layer", CELL)}
    assert set(PER_LAYER[:16]) | set(NEW) <= cells
    assert not {"step_slot_ns", "eval_slot_ns", "trajectory_eval_s",
                "updates_per_apply"} & cells
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
    assert (pub["n"], pub["d"], pub["nnz_per_row"]) == (
        149_639_105, 54_686_452, 11)
    assert (config["kind"], config["storage_dtype"]) == ("sparse", "float32")
    assert (config["solver"], config["loss"], config["num_workers"],
            config["batch_rate"], config["bucket_ratio"]) == (
        "asgd", "logistic", 8, 0.05, 0.7)
    # a whole number of eighths of the set in eight shards of ONE shape
    # (one compile of a step), and no fewer than one eighth
    assert config["n"] % 8 == 0
    eighths = config["n"] / (pub["n"] / 8)
    assert abs(eighths - round(eighths)) < 1e-6 and round(eighths) >= 1
    # f32 values + int32 columns in 16 slots a row, a label a row: with the
    # engine's model-sized state (one live model, a result and a pinned
    # version a worker) over the floor of a quarter of the chip's 16 GB
    held = config["n"] * (16 * 8 + 4)
    assert held + 17 * MODEL_BYTES >= 0.25 * 16e9 and held < 0.6 * 16e9
    # the width no chooser of the sparse step was fitted to
    assert config["d"] % 8 == 4 and MODEL_BYTES > 128 * 2**20
    pins = config["pins"]
    assert (pins["ell_width"], pins["nnz_per_row"], pins["shard_dtype"]) == (
        16, 11, "float32")
    share = config["generator"]["bernoulli_labels"]["positive_share"]
    # y^2 = y: the labels' second moment is the share of clicks
    assert pins["label_second_moment_min"] < share < pins["label_second_moment_max"]
    assert config["generator"]["column_skew"] == 1.0
    assert config["noise"] == 0.0 and config["generator"]["unit_values"] is True
    # the target lies over the entropy of the click share and under ln 2
    h = -(share * math.log(share) + (1 - share) * math.log(1 - share))
    assert h / math.log(2) < config["target_fraction"] < 1.0
    # a window's snapshots are two evaluation calls of eight at most
    assert config["printer_freq"] >= 12
    for key in ("gamma", "data", "noise", "target_fraction", "printer_freq",
                "sizes", "column_skew", "bernoulli_labels"):
        assert len(config["assumed"][key]) > 20, key
    # the step's needed bytes: 233,811 sampled rows of 16 slots x 8 B, and
    # the touched entries of w and g (3.7M slots: fewer than d)
    rows = config["n"] // 8
    need = roofline.sparse_step_bytes(rows, 16, config["d"], 0.05, 4, 4)
    sampled = 0.05 * rows
    assert need == pytest.approx(sampled * 128 + rows + sampled * 4
                                 + 2 * sampled * 16 * 4)


def test_an_apply_needs_the_model_twice_and_each_gradient_once():
    assert roofline_apply.apply_bytes(MODEL_BYTES, 1) == 3 * MODEL_BYTES
    assert roofline_apply.apply_bytes(MODEL_BYTES, 8) == 10 * MODEL_BYTES
    # a run's mean over its dispatches may be fractional
    assert roofline_apply.apply_bytes(1000, 1.25) == 3250.0


def _record(extras, accepted=240):
    return {"result": {"elapsed_s": 20.0, "accepted": accepted,
                       "extras": extras},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_the_readers_read_the_trace_and_the_programs_counters():
    man = manifest_mod.Manifest()
    read = lambda name, run, trace=None: (  # noqa: E731
        man.metric_reader(name).read(run, trace))
    extras = {"model_bytes": MODEL_BYTES, "apply_dispatches": 200,
              "model_copies_peak": 31, "results_held_max": 3}
    trace = {"modules": {
        "jit_step": {"count": 36, "median_s": 0.08, "total_s": 2.9},
        "jit_apply": {"count": 30, "median_s": 0.001, "total_s": 0.030},
        "jit_apply_fold": {"count": 2, "median_s": 0.003, "total_s": 0.006},
    }}
    # 36 ms of applies over 32 dispatches
    assert read("apply_device_ms", _record(extras), trace) == pytest.approx(
        36.0 / 32)
    need = (2 + 240 / 200) * MODEL_BYTES
    assert read("apply_roofline", _record(extras), trace) == pytest.approx(
        100 * need / (0.036 / 32) / 819e9)
    assert read("apply_roofline", _record(extras), trace) < 100
    assert read("model_copies_peak", _record(extras)) == 31
    # a program without the counters (the parent commit), a window with no
    # apply, a run without a device trace or peaks: nothing, and no raise
    assert read("model_copies_peak", _record({})) is None
    assert read("apply_roofline", _record({}), trace) is None
    assert read("apply_roofline", _record({"model_bytes": 8}), trace) is None
    no_apply = {"modules": {"jit_step": trace["modules"]["jit_step"]}}
    for name in NEW[:2]:
        assert read(name, _record(extras), None) is None
        assert read(name, _record(extras), no_apply) is None
    unpeaked = dict(_record(extras), peaks=None)
    assert read("apply_roofline", unpeaked, trace) is None
    # the parent's apply has the same names: its device time reads there too
    assert read("apply_device_ms", _record({}), trace) == pytest.approx(
        36.0 / 32)


@pytest.fixture(scope="module")
def wide_manifest(tmp_path_factory):
    """The real manifest's metrics over the rehearsal configuration: its
    cell is an entry, and the three new metrics list it."""
    doc = json.loads(json.dumps(MANIFEST))
    doc["configs"] = [{
        "name": "tiny-sparse-wide", "source": "rehearsal", "reduced": [],
        "why": "rehearsal",
        "file": "tests/benchmark/configs/tiny-sparse-wide.json"}]
    doc["workloads"] = [{"name": TINY, "config": "tiny-sparse-wide",
                         "traffic": "steady", "chips": 1, "why": "rehearsal"}]
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = [TINY]
    path = tmp_path_factory.mktemp("bench_wide") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_rehearsal_cell_runs_the_wide_sparse_path(
        wide_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, wide_manifest, TINY, seed=2_147_483_659)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS and set(last["metrics"]) == set(E2E)
    assert last["correct"] is True, lines[-2]
    for name, c in last["compared"].items():
        assert c["value"] <= c["limit"], name
    assert last["compared"]["nnz_per_row"]["value"] < 0.33
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    assert record["data"]["width"] == 16 and record["data"]["d"] == 40_004
    assert record["target"]["f0"] == pytest.approx(0.693147, rel=1e-5)
    assert 0.02 < record["pins"]["label_second_moment"] < 0.08
    assert record["pins"]["nnz_per_row"] == pytest.approx(11.0, abs=1e-4)
    extras = record["result"]["extras"]
    # no eight-row view of this width: the element-wise gather
    assert extras["sparse_gather_path"] == "elements"
    assert extras["model_bytes"] == 4 * 40_004
    assert extras["sampled_slots_per_step"] * 16 <= 40_004
    # the engine's account of model-sized buffers: every snapshot and one
    # stack of eight at the end, at least
    assert extras["snapshots_held"] == record["snapshots"]
    assert extras["eval_calls"] == -(-record["snapshots"] // 8)
    assert extras["model_copies_peak"] >= extras["snapshots_held"] + 8 - 1
    # (a worker is available again once its result is QUEUED: a fleet
    # drained, a fleet less one queued and a fleet in flight at the most)
    assert 1 <= extras["results_held_max"] <= 3 * 8 - 1
    assert 1 <= extras["versions_pinned_max"] <= 8


def test_traced_rehearsal_reports_the_count_of_model_copies(
        wide_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, wide_manifest, TINY, trace=1, seed=11)
    assert rc == 0
    last = json.loads(lines[-1])
    got = last["metrics"]
    # on the CPU there is no device plane: the two trace metrics find
    # nothing to read, and the line leaves them out
    assert "model_copies_peak" in got
    assert "apply_device_ms" not in got and "apply_roofline" not in got
    assert got["model_copies_peak"]["unit"] == "copies"
    infos = [json.loads(ln)["info"] for ln in lines[:-1]]
    record = [i for i in infos if "checks" in i][0]
    assert got["model_copies_peak"]["value"] == (
        record["result"]["extras"]["model_copies_peak"])
    assert last["correct"] is True
    # the profiled run keeps w = 0, the model after update 1 and the final
    # one: one stack of eight
    prof = [i for i in infos if "profiled_run" in i][0]["profiled_run"]
    assert prof["extras"]["eval_calls"] == 1
    assert prof["extras"]["snapshots_held"] == 3


def test_the_trajectory_eval_span_says_how_many_stacks_it_evaluated(
        wide_manifest, on_cpu, capsys, monkeypatch):
    from asyncframework_tpu.metrics import trace as prog_trace

    on_cpu(1)
    spans = []
    real = prog_trace.UpdateTrace.add

    def add(self, stage, *args, **kw):
        span = real(self, stage, *args, **kw)
        if stage == prog_trace.TRAJECTORY_EVAL:
            spans.append(span)
        return span

    monkeypatch.setattr(prog_trace.UpdateTrace, "add", add)
    rc, lines = _run(capsys, wide_manifest, TINY, trace=1, seed=12)
    assert rc == 0
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    # the warm-up's, then the checked run's (the profiled run samples no
    # span); each says its snapshots and the stacks of eight they made
    assert len(spans) == 2
    assert spans[-1].batch == record["snapshots"]
    assert spans[-1].calls == -(-record["snapshots"] // 8)
    assert spans[-1].to_wire()["c"] == spans[-1].calls


@pytest.mark.parametrize("control", [False, True], ids=["sound", "bf16-model"])
def test_check_sparse_holds_the_wide_shape_to_the_reference(
        wide_manifest, on_cpu, capsys, control):
    on_cpu(1)
    argv = ["--workload", TINY, "--seed", "2147483659"]
    rc = check_sparse.main(argv + (["--bf16-model"] if control else []),
                           manifest_path=wide_manifest)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])["check_sparse"]
    assert out["bf16_model"] is control
    assert out["step"]["sampled"] > 0 and out["evaluation"]["snapshots"] == 8
    if control:
        assert rc == 1 and out["correct"] is False
    else:
        assert rc == 0 and out["correct"] is True
