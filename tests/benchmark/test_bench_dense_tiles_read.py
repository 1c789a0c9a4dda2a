"""CPU rehearsal of what ISSUE 49 gives the benchmark: one per-layer metric,
``dense_tiles_read``, the share of a dense shard's 128-row lane tiles a
worker step fetches (one always-on figure of ``TrainResult.extras``, host
arithmetic from the draw's rate: no clock, no device read)."""

import json

import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    MANIFEST,
    PER_LAYER,
    TINY_CELLS,
    _run,
    on_cpu,
)

from benchmark import manifest as manifest_mod

NAME = "dense_tiles_read"
CELL = "mnist8m-asaga.steady"


def _record(**extras):
    return {"program_trace": None,
            "result": {"accepted": 40, "elapsed_s": 4.0, "extras": extras}}


def test_the_manifest_appends_the_reader_behind_what_was_there():
    assert PER_LAYER[-1] == NAME and PER_LAYER.count(NAME) == 1
    assert MANIFEST["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "steps",
        "moves": "updates_per_s", "workloads": [CELL],
    }
    mod = manifest_mod.Manifest().metric_reader(NAME)
    assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        NAME, "%", "program_counter", "steps", "updates_per_s")
    # the layer is the one the step's other two metrics name, letter for
    # letter, and the cell it lists is the dense one that draws at 0.01
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name["step_device_ms"]["layer"] == "steps"
    config = [c for c in MANIFEST["configs"] if c["name"] == "mnist8m-asaga"]
    assert json.load(open(config[0]["file"]))["batch_rate"] == 0.01


def test_the_entry_in_front_of_it_stands_as_it_was():
    """``test_bench_model_read_local.py`` asserts that ITS metric is the
    list's last, which an append ends (``tests/conftest.py`` marks that
    one assertion and says why); what that test holds beyond the position
    is held here."""
    assert PER_LAYER[-2] == "model_read_local"
    assert PER_LAYER.count("model_read_local") == 1
    assert MANIFEST["per_layer"][-2] == {
        "name": "model_read_local", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "updates_per_s", "workloads": ["mnist8m-f32-asgd.steady"],
    }
    mod = manifest_mod.Manifest().metric_reader("model_read_local")
    assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "model_read_local", "%", "program_counter", "engine",
        "updates_per_s")
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert [n for n, w in cells.items() if w["chips"] > 1] == [
        "mnist8m-f32-asgd.steady"]


@pytest.mark.parametrize("extras,want", [
    # ASAGA's draw at 0.01: 1 - 0.99^128 of the tiles hold a sampled row
    ({"dense_step_path": "onepass_tiles",
      "dense_tiles_read_share": 1 - 0.99 ** 128}, 72.3748),
    # the whole-shard kernel and the two products read every tile
    ({"dense_step_path": "onepass", "dense_tiles_read_share": 1.0}, 100.0),
    ({"dense_step_path": "two_products", "dense_tiles_read_share": 1.0},
     100.0),
    ({"dense_tiles_read_share": 0.0}, 0.0),
    # the parent commit's record names its path and no share
    ({"dense_step_path": "onepass"}, None),
    # a padded-ELL run has neither
    ({"sparse_gather_path": "rows8"}, None),
])
def test_the_reader_reads_the_one_figure_and_nothing_else(extras, want):
    read = manifest_mod.Manifest().metric_reader(NAME).read
    got = read(_record(**extras), None)
    assert got == want if want is None else got == pytest.approx(
        want, abs=1e-4)
    traced = dict(_record(**extras),
                  program_trace={"stages_ms": {"compute": {"count": 4,
                                                           "p50": 9.0}}})
    assert read(traced, {"modules": {}}) == got


@pytest.fixture()
def listing_manifest(tmp_path):
    """The tiny cells under the real manifest's metrics, with the ASAGA
    rehearsal cell and a dense ASGD one on ``dense_tiles_read``'s list (the
    real list names the accepted cell: a rehearsal cell is not on it)."""
    doc = json.loads(json.dumps(MANIFEST))
    configs = sorted({c for c, _t, _n in TINY_CELLS.values()})
    doc["configs"] = [
        {"name": c, "source": "rehearsal", "reduced": [], "why": "rehearsal",
         "file": f"tests/benchmark/configs/{c}.json"} for c in configs
    ]
    doc["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "rehearsal"}
        for n, (c, t, k) in TINY_CELLS.items()
    ]
    for m in doc["per_layer"]:
        if m["name"] == NAME:
            m["workloads"] = ["tiny-asaga.steady", "tiny-dense-bf16.steady",
                              "tiny-sparse.steady"]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


@pytest.mark.parametrize("cell,reports", [
    ("tiny-asaga.steady", True), ("tiny-dense-bf16.steady", True),
    ("tiny-sparse.steady", False)])
def test_a_traced_rehearsal_reads_the_whole_shard_on_this_backend(
        cell, reports, listing_manifest, on_cpu, capsys):
    """Off the TPU the step is the two XLA products at any rate: every
    tile is read, and the record says so; a padded-ELL run says nothing."""
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, listing_manifest, cell, trace=1)
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    if reports:
        assert last["metrics"][NAME] == {"value": 100.0, "unit": "%"}
    else:
        assert NAME not in last["metrics"]
