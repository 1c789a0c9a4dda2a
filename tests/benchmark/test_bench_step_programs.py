"""CPU rehearsal of what ISSUE 57 gives the benchmark: ONE per-layer
metric, ``step_programs_loaded``, a file of its own and an entry APPENDED
to ``BENCHMARK.json`` that lists the four sparse cells.  It reads the
counters of the padded-ELL steps' store (``step_programs_loaded`` /
``step_programs_built`` of ``TrainResult.extras``), through ``run.py`` as
it stands, over the rehearsal configuration with shards of unequal width:
0 on a store's first run, 100 on its second, nothing on a record without
the counters (the parent's) and in the dense cells."""

import json

import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    E2E,
    MANIFEST,
    PER_LAYER,
    TINY_CELLS,
    _run,
    on_cpu,
    tiny_manifest,
)

from benchmark import manifest as manifest_mod

NAME = "step_programs_loaded"
SPARSE_CELLS = ["criteo-logistic-asgd.steady", "kdd2012-logistic-asgd.steady",
                "webspam-logistic-asgd.steady", "criteo-asaga.steady"]
TINY = "tiny-sparse-ragged.steady"


def test_the_manifest_appends_the_reader_behind_what_was_there():
    # found by name: later PRs append behind it, so no tail is pinned
    at = PER_LAYER.index(NAME)
    assert at >= 67 and PER_LAYER.count(NAME) == 1
    assert PER_LAYER[at - 1] == "task_enqueue_cpu_mean_ms"
    assert MANIFEST["per_layer"][at] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "set-up", "moves": "setup_s",
        "workloads": SPARSE_CELLS}
    assert NAME not in E2E
    reader = manifest_mod.Manifest().metric_reader(NAME)
    assert (reader.NAME, reader.UNIT, reader.SOURCE, reader.LAYER,
            reader.MOVES) == (NAME, "%", "program_counter", "set-up",
                              "setup_s")


def test_the_four_sparse_cells_report_it_and_no_dense_one():
    man = manifest_mod.Manifest()
    sparse = {c["name"] for c in MANIFEST["workloads"]
              if man.config(c["config"])["kind"] == "sparse"}
    assert sparse == set(SPARSE_CELLS)
    for cell in MANIFEST["workloads"]:
        listed = NAME in {m["name"] for m in man.metric_entries(
            "per_layer", cell["name"])}
        assert listed == (cell["name"] in sparse), cell["name"]
    # ISSUE 57 only adds: the workloads, the configurations, the end-to-end
    # metrics and the run's length are what they were
    assert len(MANIFEST["workloads"]) == 9 and len(MANIFEST["configs"]) == 8
    assert MANIFEST["run_seconds"] == 20 and len(E2E) == 3


def _record(extras):
    return {"result": {"elapsed_s": 20.0, "accepted": 200, "extras": extras}}


@pytest.mark.parametrize("extras,want", [
    ({}, None),                                     # the parent's record
    ({"sparse_step_shapes": 8}, None),
    ({"step_programs_loaded": 0, "step_programs_built": 0,
      "step_programs_failed": 0}, None),            # a step left on jit
    ({"step_programs_loaded": 8, "step_programs_built": 0,
      "step_programs_failed": 0}, 100.0),           # a warm run
    ({"step_programs_loaded": 0, "step_programs_built": 8,
      "step_programs_failed": 0}, 0.0),             # a machine's first
    ({"step_programs_loaded": 6, "step_programs_built": 2,
      "step_programs_failed": 2}, 75.0),            # two files cut short
    ({"step_programs_loaded": 1, "step_programs_built": 0,
      "step_programs_failed": 0}, 100.0),           # one shape a solver
], ids=["parent", "no-counter", "on-jit", "warm", "first", "two-failed",
        "one-shape"])
def test_the_reader_reads_the_stores_counters(extras, want):
    read = manifest_mod.Manifest().metric_reader(NAME).read
    assert read(_record(extras), None) == want
    assert read(_record(extras), {"modules": {}}) == want


@pytest.fixture(scope="module")
def ragged_manifest(tmp_path_factory):
    """The real manifest's metrics over the rehearsal configuration with
    shards of unequal width: its cell is an entry, and the new metric (and
    ``step_shapes``, which it is read beside) lists it."""
    doc = json.loads(json.dumps(MANIFEST))
    doc["configs"] = [{
        "name": "tiny-sparse-ragged", "source": "rehearsal", "reduced": [],
        "why": "rehearsal",
        "file": "tests/benchmark/configs/tiny-sparse-ragged.json"}]
    doc["workloads"] = [{"name": TINY, "config": "tiny-sparse-ragged",
                         "traffic": "steady", "chips": 1, "why": "rehearsal"}]
    for m in doc["per_layer"]:
        if m["name"] in (NAME, "step_shapes"):
            m["workloads"] = [TINY]
    path = tmp_path_factory.mktemp("bench_store") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_a_stores_first_run_reads_0_and_its_second_100(
        ragged_manifest, on_cpu, capsys, step_store):
    """Two traced runs of the rehearsal cell over ONE store (the
    ``step_store`` fixture: a directory of this test's own): the first
    builds an executable a shard shape and stores it, the second loads
    them all, and both are ``correct``; the record carries the counters
    as the scalars the harness keeps."""
    on_cpu(1)
    seen = []
    for _ in range(2):
        rc, lines = _run(capsys, ragged_manifest, TINY, trace=1,
                         seed=2_147_483_659)
        assert rc == 0
        last = json.loads(lines[-1])
        assert last["correct"] is True, lines[-2]
        record = [json.loads(ln)["info"] for ln in lines[:-1]
                  if "checks" in json.loads(ln)["info"]][0]
        seen.append((last["metrics"], record["result"]["extras"]))
    (first, extras0), (second, extras1) = seen
    shapes = int(first["step_shapes"]["value"])
    assert shapes >= 5
    assert first[NAME] == {"value": 0.0, "unit": "%"}
    assert second[NAME] == {"value": 100.0, "unit": "%"}
    assert (extras0["step_programs_built"], extras0["step_programs_loaded"],
            extras0["step_programs_failed"]) == (shapes, 0, 0)
    assert (extras1["step_programs_built"], extras1["step_programs_loaded"],
            extras1["step_programs_failed"]) == (0, shapes, 0)
    assert len(list(step_store.iterdir())) == shapes
    # untraced, the line holds the end-to-end metrics alone
    rc, lines = _run(capsys, ragged_manifest, TINY, seed=2_147_483_659)
    assert rc == 0 and set(json.loads(lines[-1])["metrics"]) == set(E2E)


def test_off_the_store_the_metric_is_absent_and_so_it_is_in_a_dense_cell(
        ragged_manifest, tiny_manifest, on_cpu, capsys):
    """Where the steps stay on ``jit`` (the CPU, as every test but the
    store's own runs; the parent) the counters read zero and the line
    leaves the metric out; a dense cell's record has no such counter."""
    on_cpu(1)
    rc, lines = _run(capsys, ragged_manifest, TINY, trace=1,
                     seed=2_147_483_659)
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] is True and NAME not in last["metrics"]
    assert "step_shapes" in last["metrics"]
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    assert record["result"]["extras"]["step_programs_built"] == 0
    cell = "tiny-dense-f32.steady"
    assert cell in TINY_CELLS
    rc, lines = _run(capsys, tiny_manifest, cell, trace=1)
    assert rc == 0 and NAME not in json.loads(lines[-1])["metrics"]
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    assert not [k for k in record["result"]["extras"]
                if k.startswith("step_programs")]
