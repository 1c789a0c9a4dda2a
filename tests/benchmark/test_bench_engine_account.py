"""CPU rehearsal of what ISSUE 34 gives the benchmark: seven per-layer
metrics of the engine's account of what it did NOT give the device
(``chip_starved``, ``inflight_mean``, ``barrier_hold``, ``backlog_hold``,
``worker_idle_p50_ms``) and of a run's end (``run_tail_s``,
``teardown_s``).  That each file states what its manifest entry states is
``test_bench_harness.py::test_metric_file_states_what_the_manifest_states``,
which takes every entry of the manifest."""

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

#: a record of 4 s with every counter and the span the seven read
SYNTHETIC = {
    "program_trace": {"stages_ms": {
        "compute": {"count": 9, "p50": 8.0},
        "worker.idle": {"count": 7, "p50": 1.25},
    }},
    "result": {"elapsed_s": 4.0, "extras": {
        "chip_empty_max_s": 1.0, "chip_empty_mean_s": 0.5,
        "inflight_task_s": 23.0, "submit_hold_barrier_s": 3.0,
        "submit_hold_backlog_s": 0.02, "run_tail_s": 0.57,
        "checkpoint_s": 0.25, "close_s": 0.125,
    }},
}
#: name -> (what the reader makes of SYNTHETIC, what it needs there)
NEW = {
    "chip_starved": (25.0, ["chip_empty_max_s"]),
    "inflight_mean": (5.75, ["inflight_task_s"]),
    "barrier_hold": (75.0, ["submit_hold_barrier_s"]),
    "backlog_hold": (0.5, ["submit_hold_backlog_s"]),
    "worker_idle_p50_ms": (1.25, ["worker.idle"]),
    "run_tail_s": (0.57, ["run_tail_s"]),
    "teardown_s": (0.375, ["checkpoint_s", "close_s"]),
}


def test_the_manifest_appends_the_seven_behind_eval_slot_ns():
    at = PER_LAYER.index("eval_slot_ns")
    assert at == 24 and PER_LAYER[at + 1:at + 8] == list(NEW)
    for entry in MANIFEST["per_layer"][at + 1:at + 8]:
        # each reports in every cell whose end-to-end metric it moves
        assert "workloads" not in entry, entry["name"]
        assert entry["layer"] == "engine"
        assert entry["moves"] == ("setup_s" if entry["name"] == "teardown_s"
                                  else "updates_per_s")


@pytest.mark.parametrize("name", list(NEW))
def test_a_reader_finds_its_number_or_nothing(name):
    """The hand-computed value on a synthetic record; None on a record
    without its counter or span (the parent commit's), each one it reads
    taken away in turn, and on an untraced record."""
    want, needs = NEW[name]
    read = manifest_mod.Manifest().metric_reader(name).read
    assert read(SYNTHETIC, None) == pytest.approx(want, abs=1e-12)
    for gone in needs:
        run = json.loads(json.dumps(SYNTHETIC))
        run["result"]["extras"].pop(gone, None)
        run["program_trace"]["stages_ms"].pop(gone, None)
        assert read(run, None) is None, gone
    bare = {"program_trace": None,
            "result": {"elapsed_s": 4.0, "extras": {}}}
    assert read(bare, None) is None


@pytest.mark.parametrize("cell", ["tiny-dense-f32.four", "tiny-asaga.steady"])
def test_traced_rehearsal_reports_the_seven_and_untraced_none(
        cell, tiny_manifest, on_cpu, capsys):
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, tiny_manifest, cell, trace=1)
    assert rc == 0
    got = json.loads(lines[-1])["metrics"]
    assert set(NEW) <= set(got), sorted(got)
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    result = record["result"]
    nw = [json.loads(ln)["info"] for ln in lines[:-1]
          if "plan" in json.loads(ln)["info"]][0]["plan"]["num_workers"]
    for name in ("chip_starved", "barrier_hold", "backlog_hold"):
        assert got[name]["unit"] == "%"
        assert 0.0 <= got[name]["value"] <= 100.0, name
    assert 0.0 < got["inflight_mean"]["value"] <= nw
    assert got["worker_idle_p50_ms"]["value"] >= 0.0
    assert 0.0 <= got["run_tail_s"]["value"] <= result["elapsed_s"]
    # what follows the fence is on the record under both clocks: the
    # program's three and the harness's span hold the same seconds (the
    # harness's also holds the run's lead and ASAGA's own extras)
    extras = result["extras"]
    after = extras["trajectory_eval_s"] + got["teardown_s"]["value"]
    assert after <= record["spans"]["trajectory_eval_s"] + 1e-6
    # the record keeps scalars: the per-chip dict is the operator's
    assert "chip_empty_s" not in extras and "chip_empty_mean_s" in extras

    rc, lines = _run(capsys, tiny_manifest, cell, trace=0)
    assert rc == 0
    assert not set(NEW) & set(json.loads(lines[-1])["metrics"])
