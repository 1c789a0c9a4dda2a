"""CPU rehearsal of what ISSUE 41 gives the benchmark: six per-layer
metrics that read the stages inside ``task.inbox`` and ``task.dispatch``
and the wait of a task that had its chip to itself."""

import json

import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    MANIFEST,
    PER_LAYER,
    TINY_CELLS,
    _run,
    on_cpu,
    tiny_manifest,
)

from benchmark import manifest as manifest_mod

EVERY_CELL = ["task_inbox_p50_ms", "task_wake_p50_ms", "task_enqueue_p50_ms"]
NEW = EVERY_CELL + ["task_model_copy_p50_ms", "task_turn_p50_ms",
                    "empty_chip_wait_excess_ms"]
STAGE_OF = {
    "task_inbox_p50_ms": "task.inbox", "task_wake_p50_ms": "task.wake",
    "task_enqueue_p50_ms": "task.enqueue",
    "task_model_copy_p50_ms": "task.model_copy",
    "task_turn_p50_ms": "task.turn",
    "empty_chip_wait_excess_ms": "task.device_wait.alone",
}


def test_the_manifest_appends_the_six_readers_behind_what_was_there():
    first = PER_LAYER.index(NEW[0])
    assert first >= 38 and PER_LAYER[first:first + 6] == NEW
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in EVERY_CELL:
        assert "workloads" not in entries[name]
    four, ragged = "mnist8m-f32-asgd.steady", "webspam-logistic-asgd.steady"
    assert entries["task_model_copy_p50_ms"]["workloads"] == [four]
    assert entries["empty_chip_wait_excess_ms"]["workloads"] == [four]
    assert entries["task_turn_p50_ms"]["workloads"] == [ragged]
    man = manifest_mod.Manifest()
    for name in NEW:
        mod = man.metric_reader(name)
        assert (mod.LAYER, mod.MOVES, mod.SOURCE, mod.UNIT) == (
            "engine", "updates_per_s", "program_span", "ms")
        assert mod.STAGE == STAGE_OF[name]


@pytest.mark.parametrize("name", NEW[:5])
def test_a_stage_reader_reads_its_stages_median_and_nothing_else(name):
    read = manifest_mod.Manifest().metric_reader(name).read
    stage = STAGE_OF[name]
    run = {"program_trace": {"stages_ms": {
        stage: {"count": 4, "p50": 1.25}, "compute": {"count": 4, "p50": 9.0},
    }}}
    assert read(run, None) == 1.25
    del run["program_trace"]["stages_ms"][stage]  # the parent commit's record
    assert read(run, None) is None
    run["program_trace"]["stages_ms"][stage] = {"count": 0, "p50": 0.0}
    assert read(run, None) is None
    assert read({"program_trace": None}, None) is None  # an untraced run


def test_the_empty_chips_excess_is_the_alone_wait_less_the_step():
    read = manifest_mod.Manifest().metric_reader(
        "empty_chip_wait_excess_ms").read
    run = {"program_trace": {"stages_ms": {
        "task.device_wait.alone": {"count": 7, "p50": 5.5},
        "task.device_wait": {"count": 40, "p50": 9.0},
    }}}
    trace = {"modules": {
        "jit_step": {"count": 700, "median_s": 0.00425},
        "jit_apply": {"count": 500, "median_s": 0.0001},
    }}
    assert read(run, trace) == pytest.approx(1.25)
    assert read(run, None) is None                    # no device trace
    assert read(run, {"modules": {}}) is None         # no step in it
    del run["program_trace"]["stages_ms"]["task.device_wait.alone"]
    assert read(run, trace) is None                   # nobody was alone
    assert read({"program_trace": None}, trace) is None


def test_a_traced_rehearsal_on_four_devices_reports_the_split(
        tiny_manifest, on_cpu, capsys):
    cell = "tiny-dense-f32.four"
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, tiny_manifest, cell, trace=1)
    assert rc == 0
    got = json.loads(lines[-1])["metrics"]
    assert set(EVERY_CELL) <= set(got), sorted(got)
    # the three with a list of cells name the accepted cells, not this one
    assert not set(NEW[3:]) & set(got)
    for name in EVERY_CELL:
        assert got[name]["unit"] == "ms" and got[name]["value"] >= 0.0
    # every wake lies inside its inbox, every enqueue inside its dispatch
    assert got["task_wake_p50_ms"]["value"] <= got["task_inbox_p50_ms"]["value"]
    assert (got["task_enqueue_p50_ms"]["value"]
            <= got["task_dispatch_p50_ms"]["value"])
