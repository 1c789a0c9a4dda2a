"""CPU rehearsal of what ISSUE 53 gives the benchmark: eight per-layer
metrics of the engine, each a file of its own and an entry APPENDED to
``BENCHMARK.json``, none with a list of cells (every cell has the engine).
Six read the always-on clock on the waits at the engine's locks
(``lock_wait_*`` of ``TrainResult.extras``), two the fields a sampled task's
``task.enqueue`` span carries (``calls_in``, ``cpu_ms``) as the aggregator
folds them.  Through ``run.py`` as it stands, over the tiny cells."""

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

#: name -> (unit, source), in the order they were appended
NEW = {
    "submitter_lock_wait": ("%", "program_counter"),
    "updater_lock_wait": ("%", "program_counter"),
    "task_lock_wait_ms": ("ms", "program_counter"),
    "state_lock_wait": ("%", "program_counter"),
    "key_lock_wait": ("%", "program_counter"),
    "context_lock_wait": ("%", "program_counter"),
    "enqueue_calls_in_mean": ("calls", "program_span"),
    "task_enqueue_cpu_mean_ms": ("ms", "program_span"),
}
COUNTERS = [n for n, (_u, s) in NEW.items() if s == "program_counter"]
SPANS = [n for n, (_u, s) in NEW.items() if s == "program_span"]


# ------------------------------------------------------------- the manifest
def test_the_manifest_appends_the_eight_behind_what_was_there():
    # found by index: later PRs append behind these, so no tail is pinned
    first = PER_LAYER.index("submitter_lock_wait")
    assert first >= 59  # behind the fifty-nine there were
    assert PER_LAYER[first - 1] == "cloud_updates_per_apply"
    assert PER_LAYER[first:first + 8] == list(NEW)
    for name in NEW:
        assert PER_LAYER.count(name) == 1, name
    for m in MANIFEST["per_layer"][first:first + 8]:
        unit, source = NEW[m["name"]]
        # just the keys shown, and no list of cells
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": "engine",
                     "moves": "updates_per_s"}
    assert not set(NEW) & set(E2E)


def test_the_entries_in_front_of_them_stand_as_they_were():
    """ISSUE 53 only adds: the straggler cell's six (PR 51) are the six in
    front, entry for entry, and the names this PR's readers sit beside
    keep their entries."""
    first = PER_LAYER.index("submitter_lock_wait")
    assert PER_LAYER[first - 6:first] == [
        "delay_avg_ms", "task_delay_p50_ms", "delay_sleep_share",
        "straggler_update_share", "updates_under_delay",
        "cloud_updates_per_apply"]
    for m in MANIFEST["per_layer"][first - 6:first]:
        assert m["workloads"] == ["mnist8m-w32-asgd.cloud"]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, unit, source in (
            ("updater_busy", "%", "program_counter"),
            ("submitter_busy", "%", "program_counter"),
            ("task_enqueue_p50_ms", "ms", "program_span")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "engine", "moves": "updates_per_s"}
    assert len(MANIFEST["workloads"]) == 9 and len(MANIFEST["configs"]) == 8
    assert MANIFEST["run_seconds"] == 20


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_every_cell_reports_the_eight(cell):
    man = manifest_mod.Manifest()
    here = [m["name"] for m in man.metric_entries("per_layer", cell)]
    assert [n for n in here if n in NEW] == list(NEW)
    # and the end-to-end metric they move is every cell's
    assert "updates_per_s" in {
        m["name"] for m in man.metric_entries("end_to_end", cell)}


# -------------------------------------------------------------- the readers
def _recorded(extras=None, program_trace=None, accepted=900, dropped=100,
              elapsed_s=20.0):
    """A ``run`` dict as ``run.py`` hands a reader, cut to what the eight
    read."""
    return {"result": {"accepted": accepted, "dropped": dropped,
                       "elapsed_s": elapsed_s, "extras": dict(extras or {})},
            "program_trace": program_trace}


WAITED = {
    "lock_wait_submitter_s": 0.5, "lock_wait_updater_s": 0.25,
    "lock_wait_executor_s": 3.0, "lock_wait_main_s": 0.0,
    "lock_wait_state_s": 0.6, "lock_wait_key_s": 2.0,
    "lock_wait_context_s": 25.0, "lock_wait_history_s": 0.15,
    "lock_contended_state": 7, "lock_wait_max_ms": 4.0,
    "lock_wait_max_at": "context:executor:executor",
    "updater_busy_s": 19.0, "submitter_busy_s": 2.0,
}
NOTHING_WAITED = {k: 0.0 for k in WAITED
                  if k.startswith("lock_wait") and k.endswith("_s")}
TRACED = {
    "stages_ms": {"task.enqueue": {"count": 40, "p50": 0.9}},
    "stages_calls_in": {"task.enqueue": {
        "count": 40, "min": 0.0, "max": 9.0, "mean": 2.75, "p50": 2.0,
        "p95": 7.0, "p99": 9.0}},
    "stages_cpu_ms": {"task.enqueue": {
        "count": 40, "min": 0.1, "max": 0.8, "mean": 0.3, "p50": 0.28,
        "p95": 0.6, "p99": 0.8}},
    "enqueue_ms_by_calls_in": {"0": {"count": 4, "p50": 0.3, "mean": 0.4}},
    "enqueue_cpu_ms_by_calls_in": {"0": {"count": 4, "mean": 0.25}},
}


@pytest.mark.parametrize("name,want", [
    ("submitter_lock_wait", 2.5), ("updater_lock_wait", 1.25),
    ("task_lock_wait_ms", 3.0), ("state_lock_wait", 3.0),
    ("key_lock_wait", 10.0),
    ("context_lock_wait", 125.0),  # several threads' waits add: over 100
    ("enqueue_calls_in_mean", 2.75), ("task_enqueue_cpu_mean_ms", 0.3),
])
def test_a_reader_returns_the_number_of_a_recorded_run(name, want):
    mod = manifest_mod.Manifest().metric_reader(name)
    unit, source = NEW[name]
    assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        name, unit, source, "engine", "updates_per_s")
    assert mod.read(_recorded(WAITED, TRACED), None) == pytest.approx(want)
    # the device trace is not read
    assert mod.read(_recorded(WAITED, TRACED), {"idle_share": 0.5}) == (
        pytest.approx(want))


@pytest.mark.parametrize("name", COUNTERS)
def test_a_run_in_which_nothing_waited_reads_zero_not_none(name):
    mod = manifest_mod.Manifest().metric_reader(name)
    got = mod.read(_recorded(NOTHING_WAITED, None), None)
    assert got == 0.0 and got is not None
    assert isinstance(got, float)


@pytest.mark.parametrize("name", list(NEW))
def test_a_program_without_the_clock_is_left_out_and_nothing_raises(name):
    """The parent under this PR's files: no ``lock_wait_*`` key, no
    ``calls_in`` on a span.  The reader finds nothing and says so."""
    mod = manifest_mod.Manifest().metric_reader(name)
    parent = {"updater_busy_s": 19.0, "submitter_busy_s": 2.0}
    old_trace = {"stages_ms": {"task.enqueue": {"count": 40, "p50": 0.9}}}
    assert mod.read(_recorded(parent, old_trace), None) is None
    assert mod.read(_recorded(parent, None), None) is None
    assert mod.read(_recorded({}, {}), None) is None
    # nothing came back, nothing ran: no share of nothing
    assert mod.read(_recorded(WAITED, None, accepted=0, dropped=0,
                              elapsed_s=0.0), None) is None
    # a table with no sample is no reading
    empty = {"stages_calls_in": {"task.enqueue": {"count": 0}},
             "stages_cpu_ms": {"task.enqueue": {"count": 0}}}
    if name in SPANS:
        assert mod.read(_recorded(WAITED, empty), None) is None


def test_a_lock_wait_is_a_part_of_the_busy_share_it_is_read_beside():
    man = manifest_mod.Manifest()
    run = _recorded(WAITED, TRACED)
    for who in ("submitter", "updater"):
        waited = man.metric_reader(who + "_lock_wait").read(run, None)
        busy = man.metric_reader(who + "_busy").read(run, None)
        assert 0.0 < waited < busy
    # (the CPU clock's statistic is the MEAN: a host whose thread clock
    # ticks reads 0 or a tick a call, and a median of 0.0)
    cpu = man.metric_reader("task_enqueue_cpu_mean_ms").read(run, None)
    wall = man.metric_reader("task_enqueue_p50_ms").read(run, None)
    assert cpu < wall
    ticked = dict(TRACED, stages_cpu_ms={"task.enqueue": {
        "count": 40, "min": 0.0, "max": 10.0, "mean": 0.5, "p50": 0.0,
        "p95": 10.0, "p99": 10.0}})
    assert man.metric_reader("task_enqueue_cpu_mean_ms").read(
        _recorded(WAITED, ticked), None) == 0.5


# ------------------------------------------------------------ the rehearsal
@pytest.mark.parametrize("cell", [
    "tiny-dense-f32.four", "tiny-asaga.steady", "tiny-dense-f32.tiny-sync"])
def test_a_traced_rehearsal_reports_all_eight_as_numbers(
        cell, tiny_manifest, on_cpu, capsys):
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, tiny_manifest, cell, trace=1)
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] is True
    got = last["metrics"]
    for name, (unit, _source) in NEW.items():
        assert name in got, (name, sorted(got))
        assert got[name]["unit"] == unit
        assert got[name]["value"] >= 0.0, name
    infos = [json.loads(ln)["info"] for ln in lines[:-1]]
    extras = [i for i in infos if "checks" in i][0]["result"]["extras"]
    elapsed = [i for i in infos if "checks" in i][0]["result"]["elapsed_s"]
    for who in ("submitter", "updater", "executor", "main"):
        assert extras[f"lock_wait_{who}_s"] >= 0.0
    for lock in ("state", "key", "context", "history", "pool"):
        assert extras[f"lock_wait_{lock}_s"] >= 0.0
        assert extras[f"lock_contended_{lock}"] >= 0
    assert isinstance(extras["lock_wait_max_at"], str)
    # the metric IS the counter over the run's seconds
    assert got["state_lock_wait"]["value"] == pytest.approx(
        100.0 * extras["lock_wait_state_s"] / elapsed)
    assert got["task_lock_wait_ms"]["value"] == pytest.approx(
        1e3 * extras["lock_wait_executor_s"] / last["attempted"])
    # a step's call takes the thread some CPU, and no more than its wall
    assert 0.0 < got["task_enqueue_cpu_mean_ms"]["value"]
    # the profiled run's record carries the clock too (always on): the
    # device's idle share and the locks' waits are of the SAME run there
    ran = [i for i in infos if "profiled_run" in i][0]["profiled_run"]
    assert ran["extras"]["lock_wait_state_s"] >= 0.0
    assert "lock_wait_max_at" in ran["extras"]


def test_an_untraced_rehearsal_reports_none_of_them_and_keeps_the_clock(
        tiny_manifest, on_cpu, capsys):
    cell = "tiny-dense-f32.steady"
    on_cpu(1)
    rc, lines = _run(capsys, tiny_manifest, cell)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last["metrics"]) == set(E2E)
    infos = [json.loads(ln)["info"] for ln in lines[:-1]]
    extras = [i for i in infos if "checks" in i][0]["result"]["extras"]
    assert extras["lock_wait_submitter_s"] <= extras["submitter_busy_s"]
    assert extras["lock_wait_updater_s"] <= extras["updater_busy_s"]
