"""CPU rehearsal of what ISSUE 47 gives the benchmark: one per-layer metric,
``model_read_local``, the share of a run's tasks whose step took the model
from a buffer already on its shard's chip (two always-on counters of
``TrainResult.extras``, no clock)."""

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

NAME = "model_read_local"
FOUR = "mnist8m-f32-asgd.steady"


def _record(**extras):
    return {"program_trace": None,
            "result": {"accepted": 40, "elapsed_s": 4.0, "extras": extras}}


def test_the_manifest_appends_the_reader_behind_what_was_there():
    assert PER_LAYER[-1] == NAME and PER_LAYER.count(NAME) == 1
    entry = MANIFEST["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "updates_per_s", "workloads": [FOUR],
    }
    mod = manifest_mod.Manifest().metric_reader(NAME)
    assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        NAME, "%", "program_counter", "engine", "updates_per_s")
    # the cell it lists is the one whose workers lie on several chips
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert [n for n, w in cells.items() if w["chips"] > 1] == [FOUR]


@pytest.mark.parametrize("extras,want", [
    # every task read its own chip's replica
    ({"model_reads_local": 1701, "model_reads_copied": 0}, 100.0),
    # four chips, one buffer on the driver's: three tasks in four copy
    ({"model_reads_local": 430, "model_reads_copied": 1290}, 25.0),
    ({"model_reads_local": 0, "model_reads_copied": 12}, 0.0),
    # the parent commit's record has neither counter
    ({"apply_dispatches": 7}, None),
    ({"model_reads_local": 5}, None),
    # a run that launched no task
    ({"model_reads_local": 0, "model_reads_copied": 0}, None),
])
def test_the_reader_reads_the_two_counters_and_nothing_else(extras, want):
    read = manifest_mod.Manifest().metric_reader(NAME).read
    # an untraced run's record (no spans, no device trace) reads the same
    got = read(_record(**extras), None)
    assert got == want if want is None else got == pytest.approx(want)
    traced = dict(_record(**extras),
                  program_trace={"stages_ms": {"compute": {"count": 4,
                                                           "p50": 9.0}}})
    assert read(traced, {"modules": {}}) == got


@pytest.fixture()
def listing_manifest(tmp_path):
    """The tiny cells under the real manifest's metrics, with the
    four-device rehearsal cell on ``model_read_local``'s list (the real
    list names the accepted cell: a rehearsal cell is not on it)."""
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
            m["workloads"] = ["tiny-dense-f32.four", "tiny-dense-f32.steady"]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


@pytest.mark.parametrize("cell", ["tiny-dense-f32.four",
                                  "tiny-dense-f32.steady"])
def test_a_traced_rehearsal_reads_every_model_where_its_shard_lies(
        cell, listing_manifest, on_cpu, capsys):
    """ASGD over four devices keeps a replica a device, over one the one
    buffer: either way no task copies the model."""
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, listing_manifest, cell, trace=1)
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    got = last["metrics"]
    assert got[NAME] == {"value": 100.0, "unit": "%"}
    # nothing was copied, so the copy's own reader finds no span
    assert "task_model_copy_p50_ms" not in got
