"""CPU rehearsal of what ISSUE 58 gives the benchmark: the configuration
``mnist8m-w32-asaga`` (the paper's Fig 8: ASAGA on the production cluster
under the cloud tail), its one cell under the mix ``cloud`` (which exists),
eleven per-layer readers that list that cell alone (two that read the age
of a worker's history by class, nine that import the ``read`` of a metric
whose cell list a ``model_config`` PR may not extend), the plain reference
of the age (``benchmark/reference_history_age.py``) and the builder's check
(``benchmark/check_history_age.py``) with its negative control.  The
rehearsal configuration is ``tiny-w32-asaga`` under ``tiny-cloud``, through
``run.py`` as it stands.

Three tests written for earlier PRs assert the manifest's size or "one cell
under ``cloud``", true until this append (``tests/conftest.py`` marks them
and says why); what each holds beyond that count is held here, by the three
tests named ``..._beyond_the_count``."""

import json
import os

import pytest

import test_bench_saga
from test_bench_harness import (  # noqa: F401 - fixtures, by name
    E2E,
    MANIFEST,
    PER_LAYER,
    RESULT_KEYS,
    _cell_resolves_to_a_plan_from_its_files,
    _held_to_its_sources_shape,
    _run,
    on_cpu,
)

from test_bench_cloud import _info
from test_bench_saga import control, saga_manifest  # noqa: F401 - fixtures

from benchmark import manifest as manifest_mod
from benchmark import plan as plan_mod, reference_delay
from benchmark import reference_history_age

CONFIG = "mnist8m-w32-asaga"
CELL = CONFIG + ".cloud"
TWIN_CELL = "mnist8m-w32-asgd.cloud"
AGES = ["late_history_age", "healthy_history_age"]
#: a reader that imports another metric's ``read``: its own name -> that one
DOUBLED = {
    "w32_history_reuse": "history_reuse",
    "w32_updater_history": "updater_history",
    "w32_merge_history_p50_ms": "merge_history_p50_ms",
    "w32_history_drift": "history_drift",
    "w32_history_device_ms": "history_device_ms",
    "saga_delay_avg_ms": "delay_avg_ms",
    "saga_delay_sleep_share": "delay_sleep_share",
    "saga_straggler_update_share": "straggler_update_share",
    "saga_updates_under_delay": "updates_under_delay",
}
NEW = AGES + list(DOUBLED)
TINY = "tiny-w32-asaga.tiny-cloud"
HERE = os.path.dirname(os.path.abspath(__file__))
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}


# ------------------------------------------------------------- the manifest
def test_the_manifest_appends_one_configuration_one_cell_eleven_metrics():
    # found by name: later PRs append behind these, so no tail is pinned
    names = [c["name"] for c in MANIFEST["configs"]]
    assert names.index(CONFIG) >= 8  # behind the eight there were
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == ["storage_dtype"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for said in ("1907.08526", "Fig 8", "Table 3", "README.md:72", "ASAGA",
                 "32 workers", "8,100,000 x 784", "beta 0.7", "b=0.1"):
        assert said in entry["source"], said
    assert entry["file"] == "benchmark/configs/mnist8m-w32-asaga.json"
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert cells.index(CELL) >= 9  # behind the nine there were
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "cloud", 1)
    assert len(cell["why"]) <= 200
    for said in ("32 workers", "unfolded updater", "stale"):
        assert said in cell["why"], said
    # one cell of the configuration; ten cells of at most 24, one on four
    assert [c["name"] for c in MANIFEST["workloads"]
            if c["config"] == CONFIG] == [CELL]
    assert len(cells) <= 24
    assert [c["name"] for c in MANIFEST["workloads"] if c["chips"] > 1] == [
        "mnist8m-f32-asgd.steady"]
    first = PER_LAYER.index(NEW[0])
    assert first >= 68 and PER_LAYER[first:first + 11] == NEW
    for m in MANIFEST["per_layer"][first:first + 11]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] == [CELL]
    for name in AGES:
        assert BY_NAME[name] == {
            "name": name, "unit": "updates", "better": "lower",
            "source": "program_counter", "layer": "engine",
            "moves": "time_to_target_s", "workloads": [CELL]}
    # a doubled reader keeps everything of the metric whose read it imports
    for name, of in DOUBLED.items():
        assert ({k: v for k, v in BY_NAME[name].items()
                 if k not in ("name", "workloads")}
                == {k: v for k, v in BY_NAME[of].items()
                    if k not in ("name", "workloads")}), name
        assert CELL not in BY_NAME[of]["workloads"]  # its list stands
    man = manifest_mod.Manifest()
    # every accepted metric without a cell list reports in the new cell;
    # the eleven in no other
    here = {m["name"] for m in man.metric_entries("per_layer", CELL)}
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    assert unlisted | set(NEW) == here
    assert {"step_roofline", "device_idle", "updater_busy",
            "updater_lock_wait", "state_lock_wait", "key_lock_wait",
            "peak_hbm_gb", "updates_to_target"} <= here
    for other in MANIFEST["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m["name"] for m in man.metric_entries(
                "per_layer", other["name"])}
    # no traffic file was added: the mix is PR 51's
    assert sorted(os.listdir(os.path.join(HERE, "..", "..", "benchmark",
                                          "traffic"))) == [
        "cloud.json", "steady-w32.json", "steady.json"]


def test_the_entry_in_front_of_them_stands_as_it_was():
    at = PER_LAYER.index(NEW[0])
    assert PER_LAYER[at - 1] == "step_programs_loaded"
    assert MANIFEST["per_layer"][at - 1] == {
        "name": "step_programs_loaded", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "set-up", "moves": "setup_s",
        "workloads": ["criteo-logistic-asgd.steady",
                      "kdd2012-logistic-asgd.steady",
                      "webspam-logistic-asgd.steady", "criteo-asaga.steady"]}
    assert PER_LAYER[:3] == ["data_gen_s", "warmup_s", "task_p50_ms"]
    assert [c["name"] for c in MANIFEST["configs"]][:8] == [
        "mnist8m-asgd", "mnist8m-f32-asgd", "mnist8m-asaga",
        "criteo-logistic-asgd", "kdd2012-logistic-asgd",
        "webspam-logistic-asgd", "criteo-asaga", "mnist8m-w32-asgd"]
    assert [c["name"] for c in MANIFEST["workloads"]][:9] == [
        "mnist8m-asgd.steady", "mnist8m-asgd.steady-w32",
        "mnist8m-f32-asgd.steady", "mnist8m-asaga.steady",
        "criteo-logistic-asgd.steady", "kdd2012-logistic-asgd.steady",
        "webspam-logistic-asgd.steady", "criteo-asaga.steady", TWIN_CELL]
    assert MANIFEST["run_seconds"] == 20
    assert [(m["name"], m["bound"]) for m in MANIFEST["end_to_end"]] == [
        ("updates_per_s", 0.04), ("time_to_target_s", 0.06),
        ("setup_s", 0.1)]


# ------------- what the three marked tests hold beyond the count they assert
def test_the_four_sparse_cells_report_it_beyond_the_count():
    """``test_bench_step_programs.py::test_the_four_sparse_cells_report_it_
    and_no_dense_one`` but for ``len(workloads) == 9 and len(configs) ==
    8``."""
    man = manifest_mod.Manifest()
    sparse = {c["name"] for c in MANIFEST["workloads"]
              if man.config(c["config"])["kind"] == "sparse"}
    assert sparse == {"criteo-logistic-asgd.steady",
                      "kdd2012-logistic-asgd.steady",
                      "webspam-logistic-asgd.steady", "criteo-asaga.steady"}
    for cell in MANIFEST["workloads"]:
        listed = "step_programs_loaded" in {
            m["name"] for m in man.metric_entries("per_layer", cell["name"])}
        assert listed == (cell["name"] in sparse), cell["name"]
    assert len(MANIFEST["workloads"]) >= 10 and len(MANIFEST["configs"]) >= 9
    assert MANIFEST["run_seconds"] == 20 and len(E2E) == 3


def test_the_lock_clocks_neighbours_stand_beyond_the_count():
    """``test_bench_lock_clock.py::test_the_entries_in_front_of_them_stand_
    as_they_were`` but for the same count."""
    first = PER_LAYER.index("submitter_lock_wait")
    assert PER_LAYER[first - 6:first] == [
        "delay_avg_ms", "task_delay_p50_ms", "delay_sleep_share",
        "straggler_update_share", "updates_under_delay",
        "cloud_updates_per_apply"]
    for m in MANIFEST["per_layer"][first - 6:first]:
        assert m["workloads"] == [TWIN_CELL]
    for name, unit, source in (
            ("updater_busy", "%", "program_counter"),
            ("submitter_busy", "%", "program_counter"),
            ("task_enqueue_p50_ms", "ms", "program_span")):
        assert BY_NAME[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "engine", "moves": "updates_per_s"}
    assert MANIFEST["run_seconds"] == 20


def test_the_straggler_cells_entries_stand_beyond_the_count():
    """``test_bench_cloud.py::test_the_manifest_appends_one_configuration_
    one_cell_six_metrics`` but for "one cell under the mix ``cloud``" and
    "``coeff != 0`` in that cell alone": there are two of each now, PR 51's
    and this one."""
    twin = "mnist8m-w32-asgd"
    six = ["delay_avg_ms", "task_delay_p50_ms", "delay_sleep_share",
           "straggler_update_share", "updates_under_delay",
           "cloud_updates_per_apply"]
    moves = dict.fromkeys(six, "updates_per_s")
    moves.update(straggler_update_share="time_to_target_s",
                 updates_under_delay="time_to_target_s")
    names = [c["name"] for c in MANIFEST["configs"]]
    assert names.index(twin) >= 7
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == twin]
    assert entry["reduced"] == ["storage_dtype"]
    assert len(entry["source"]) <= 200
    for said in ("1907.08526", "Figs 7-8", "README.md:72", "32 workers",
                 "8,100,000 x 784"):
        assert said in entry["source"], said
    assert entry["file"] == "benchmark/configs/mnist8m-w32-asgd.json"
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert cells.index(TWIN_CELL) >= 8
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == TWIN_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        twin, "cloud", 1)
    assert len(cell["why"]) <= 200
    # one cell of THAT configuration; two under the mix, in this order
    assert [c["name"] for c in MANIFEST["workloads"]
            if c["config"] == twin] == [TWIN_CELL]
    assert [c["name"] for c in MANIFEST["workloads"]
            if c["traffic"] == "cloud"] == [TWIN_CELL, CELL]
    first = PER_LAYER.index(six[0])
    assert first >= 51 and PER_LAYER[first:first + 6] == six
    for m in MANIFEST["per_layer"][first:first + 6]:
        assert m["workloads"] == [TWIN_CELL] and m["moves"] == moves[m["name"]]
        assert (m["layer"], m["unit"]) == ("engine", {
            "delay_avg_ms": "ms", "task_delay_p50_ms": "ms",
            "cloud_updates_per_apply": "updates"}.get(m["name"], "%"))
    man = manifest_mod.Manifest()
    here = {m["name"] for m in man.metric_entries("per_layer", TWIN_CELL)}
    assert set(PER_LAYER[:16]) | set(six) <= here
    assert {"chip_starved", "barrier_hold", "inflight_mean",
            "worker_idle_p50_ms", "device_idle", "step_roofline"} <= here
    assert "updates_per_apply" not in here
    assert BY_NAME["updates_per_apply"]["workloads"] == [
        "mnist8m-asgd.steady", "mnist8m-asgd.steady-w32",
        "mnist8m-f32-asgd.steady"]
    for other in MANIFEST["workloads"]:
        if other["name"] != TWIN_CELL:
            assert not set(six) & {m["name"] for m in man.metric_entries(
                "per_layer", other["name"])}
    # somebody is late in the two cells under ``cloud`` and in no other
    for other in MANIFEST["workloads"]:
        plan = plan_mod.resolve(man.config(other["config"]),
                                man.traffic(other["traffic"]))
        assert (plan["coeff"] != 0) == (other["name"] in (TWIN_CELL, CELL))


# -------------------------------------------------------- the configuration
def test_the_configuration_is_its_two_twins_letter_for_letter():
    man = manifest_mod.Manifest()
    config = man.config(CONFIG)
    _held_to_its_sources_shape(config)
    data_twin = man.config("mnist8m-asgd")
    for key in ("kind", "n", "d", "published", "storage_dtype", "noise",
                "reduced"):
        assert config[key] == data_twin[key], key
    # the pins are the data's, and ONE more: the history limit read at
    # this cell's size (its two readings beside it, under ``assumed``)
    own = {k: v for k, v in config["pins"].items() if k.startswith("history")}
    assert own == {"history_drift_limit": 1.5e-5}
    assert {k: v for k, v in config["pins"].items() if k not in own} == (
        data_twin["pins"])
    recipe_twin = man.config("mnist8m-w32-asgd")
    for key in ("num_workers", "batch_rate", "bucket_ratio", "loss",
                "target_fraction", "kind", "n", "d", "published",
                "storage_dtype", "noise", "reduced"):
        assert config[key] == recipe_twin[key], key
    differ = {k for k in set(config) | set(recipe_twin)
              if config.get(k) != recipe_twin.get(k)}
    assert differ == {"name", "source", "solver", "gamma", "printer_freq",
                      "guarantees", "deployment", "assumed", "pins"}
    assert "generator" not in config
    # the recipe: reference README.md:72, under ASAGA
    assert (config["solver"], config["loss"], config["num_workers"],
            config["batch_rate"], config["bucket_ratio"],
            config["target_fraction"]) == (
        "asaga", "least_squares", 32, 0.1, 0.7, 0.001)
    assert config["printer_freq"] == 128
    # constant gamma: the crossing is asked for at 2 to 2.5 calibrations,
    # by 2 K gamma / d = ln 1000 (mnist8m-asaga's file) within a fifth
    rule = 784 * 6.9078 / (2 * config["gamma"])
    assert 0.8 * 6_400 <= rule <= 1.2 * 8_000
    # the floor: 32 shards of 253,125 rows of bf16 are 80% of the chip
    assert config["n"] == 32 * 253_125
    stored = config["n"] * config["d"] * 2 + config["n"] * 4
    assert 0.75 * 16e9 < stored < 0.85 * 16e9
    said = config["deployment"]
    for word in ("32 logical workers", "ONE", "12.74 GB", "80%",
                 "one device queue", "history slices"):
        assert word in said, word
    # the recipe's guarantees, word for word, and ASAGA's own behind them
    assert config["guarantees"].startswith(recipe_twin["guarantees"])
    for word in ("alpha_bar is the mean of the history table",
                 "whatever was late", "history_within"):
        assert word in config["guarantees"], word
    for key in ("gamma", "data", "noise", "printer_freq", "delay_scale"):
        assert len(config["assumed"][key]) > 20, key
    for key in ("data", "noise", "delay_scale", "row_second_moment"):
        assert config["assumed"][key] == recipe_twin["assumed"][key], key
    # the history pin is stated with its two readings beside it, and lies
    # between them with a factor of 3 to spare on each side
    said = config["assumed"]["history_drift_limit"]
    for word in ("2.34e-6", "9.29e-5", "--round-delta", "run.DRIFT_LIMIT"):
        assert word in said, word
    assert 3 * 2.34e-6 <= config["pins"]["history_drift_limit"] <= 9.29e-5 / 3
    assert "history_by_column_limit" not in config["pins"]  # dense shards


def test_only_the_two_history_deployments_state_history_limits(
        monkeypatch, saga_manifest, on_cpu, capsys, control, tmp_path):
    """``test_bench_sparse_asaga.py::test_only_the_sparse_history_
    deployment_states_history_limits`` asserts that ``criteo-asaga`` is the
    one cell that states a history limit, true until this configuration
    stated ``history_drift_limit`` (``tests/conftest.py`` marks it and says
    why).  What it holds beyond that count: every OTHER cell states none,
    and with the two set aside ``test_bench_saga``'s check of a stated
    limit holds as it was written: its body, called as it stands."""
    man = manifest_mod.Manifest()
    stating = [cell["name"] for cell in MANIFEST["workloads"]
               if [k for k in man.config(cell["config"])["pins"]
                   if k.startswith("history")]]
    assert stating == ["criteo-asaga.steady", CELL]
    others = dict(MANIFEST, workloads=[
        c for c in MANIFEST["workloads"] if c["name"] not in stating])
    monkeypatch.setattr(test_bench_saga, "MANIFEST", others)
    test_bench_saga.test_a_configuration_may_state_its_own_drift_limit(
        saga_manifest, on_cpu, capsys, control, tmp_path)
    # run.py reads the stated limit for history_within, and no other cell's
    from benchmark import run

    assert run.DRIFT_LIMIT == 2e-6
    assert man.config(CONFIG)["pins"]["history_drift_limit"] == 1.5e-5
    assert "history_drift_limit" not in man.config("mnist8m-asaga")["pins"]


def test_the_real_cell_resolves_to_the_recipes_plan():
    man = manifest_mod.Manifest()
    _cell_resolves_to_a_plan_from_its_files(man, CELL)
    config, mix = man.config(CONFIG), man.traffic("cloud")
    plan = plan_mod.resolve(config, mix)
    twin = plan_mod.resolve(man.config("mnist8m-w32-asgd"), mix)
    assert {k for k in plan if plan[k] != twin[k]} == {
        "solver", "gamma", "printer_freq"}
    assert (plan["num_workers"], plan["batch_rate"], plan["bucket_ratio"],
            plan["coeff"], plan["mode"], plan["taw"]) == (
        32, 0.1, 0.7, -1.0, "async", 2_147_483_647)
    assert (plan["solver"], plan["loss"]) == ("asaga", "least_squares")
    assert (plan["gamma"], plan["printer_freq"]) == (
        config["gamma"], config["printer_freq"])
    assert "per_config" not in mix and "num_workers" not in mix
    late = reference_delay.late_workers(plan["num_workers"], plan["coeff"])
    assert sorted(w for w, c in late.items() if c == "long_tail") == [0, 4]
    assert sorted(w for w, c in late.items() if c == "normal") == [
        8, 12, 16, 20, 24, 28]
    # the rehearsal runs the same recipe at a CPU test's size
    tiny = json.load(open(os.path.join(HERE, "configs",
                                       "tiny-w32-asaga.json")))
    for key in ("kind", "solver", "loss", "batch_rate", "bucket_ratio"):
        assert tiny[key] == config[key], key
    assert sorted(reference_delay.late_workers(
        tiny["num_workers"]).values()) == ["long_tail", "normal", "normal"]


# --------------------------------------------------------------- the readers
def _record(extras=None, stages=None, accepted=14_200, elapsed_s=20.0):
    return {
        "program_trace": {"stages_ms": stages or {}},
        "result": {"elapsed_s": elapsed_s, "accepted": accepted,
                   "extras": extras or {}},
    }


#: a window of the cell as ISSUE 58 expects it
CLOUD = {"avg_delay_ms": 45.0, "delay_calibrated_at_update": 3_204,
         "delay_calibrated_at_s": 4.5, "straggler_workers": 8,
         "delayed_tasks": 900, "delay_sleep_s": 93.0,
         "delay_sleep_long_tail_s": 31.0, "accepted_from_stragglers": 1_450,
         "accepted_after_calibration": 10_996,
         "history_age_late_sum": 88_000, "history_age_late_n": 1_100,
         "history_age_healthy_sum": 266_220, "history_age_healthy_n": 9_860,
         "history_reused": 12_780, "history_recomputed": 1_420,
         "updater_history_s": 6.0, "history_drift": 4.5e-7}
#: what a ``coeff`` 0 run of this PR's program reports
STEADY = {"avg_delay_ms": 0.0, "delay_calibrated_at_update": 0,
          "delay_calibrated_at_s": 0.0, "straggler_workers": 0,
          "delayed_tasks": 0, "delay_sleep_s": 0.0,
          "delay_sleep_long_tail_s": 0.0, "accepted_from_stragglers": 0,
          "accepted_after_calibration": 0, "history_age_late_sum": 0,
          "history_age_late_n": 0, "history_age_healthy_sum": 0,
          "history_age_healthy_n": 0}
STAGES = {"merge.history": {"count": 1_775, "p50": 0.31},
          "compute": {"count": 1_775, "p50": 44.0}}
TRACE = {"modules": {
    "jit_saga_commit_history": {"count": 2_100, "total_s": 0.021},
    "jit_saga_table_delta": {"count": 210, "total_s": 0.105}}}
WANT = {"late_history_age": 80.0, "healthy_history_age": 27.0,
        "w32_history_reuse": 90.0, "w32_updater_history": 30.0,
        "w32_merge_history_p50_ms": 0.31, "w32_history_drift": 4.5e-7,
        "w32_history_device_ms": 0.06, "saga_delay_avg_ms": 45.0,
        "saga_delay_sleep_share": 100 * 93.0 / (8 * 15.5),
        "saga_straggler_update_share": 100 * 1_450 / 14_200,
        "saga_updates_under_delay": 100 * 10_996 / 14_200}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_states_what_its_entry_states(name):
    mod = manifest_mod.Manifest().metric_reader(name)
    entry = BY_NAME[name]
    assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        name, entry["unit"], entry["source"], entry["layer"], entry["moves"])
    if name in DOUBLED:
        of = manifest_mod.Manifest().metric_reader(DOUBLED[name])
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            of.UNIT, of.SOURCE, of.LAYER, of.MOVES)
        # the original's own function, imported and not written again
        assert mod.read.__module__ == "benchmark.metrics." + DOUBLED[name]
        assert mod.read.__code__.co_code == of.read.__code__.co_code
    else:
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            "updates", "program_counter", "engine", "time_to_target_s")
    assert len(mod.__doc__) > 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_the_cells_record(name):
    read = manifest_mod.Manifest().metric_reader(name).read
    run = _record(extras=CLOUD, stages=STAGES)
    assert read(run, TRACE) == pytest.approx(WANT[name])
    if name != "w32_history_device_ms":
        # the device trace is nothing to the others
        assert read(run, None) == read(run, TRACE)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_the_parents_record(name):
    """The parent's record of the new cell has the delay's account and the
    history's counters and none of the four age keys; a ``coeff`` 0 run of
    this program reports zeros; a run that ended inside its calibration
    booked nothing.  The two age readers return None there and raise
    nothing; a doubled reader reads what its original reads."""
    man = manifest_mod.Manifest()
    read = man.metric_reader(name).read
    parents = {k: v for k, v in CLOUD.items() if "history_age" not in k}
    short = dict(STEADY, straggler_workers=8)
    half = dict(CLOUD, history_age_late_n=0, history_age_healthy_n=0)
    records = [_record(extras=e, stages=s) for e in (
        {}, {"updater_busy_s": 17.1}, parents, STEADY, short, half)
        for s in (STAGES, {})]
    records += [dict(_record(extras=parents), program_trace=None)]
    for record in records:
        for trace in (None, {"modules": {}}, TRACE):
            if name in AGES:
                assert read(record, trace) is None, record["result"]
            else:
                own = man.metric_reader(DOUBLED[name]).read
                assert read(record, trace) == own(record, trace)
    if name in AGES:
        # one class counted, the other not: each reads its own
        one = dict(CLOUD, history_age_late_n=0)
        want = None if name == "late_history_age" else 27.0
        assert read(_record(extras=one), None) == want
    else:
        # on the parent's record of this cell the nine read
        got = read(_record(extras=parents, stages=STAGES), TRACE)
        assert got == pytest.approx(WANT[name])


# ------------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def saga_cloud_manifest(tmp_path_factory):
    """The real manifest's metrics over the rehearsal configuration: its
    cell is an entry, and the eleven new metrics list it."""
    doc = json.loads(json.dumps(MANIFEST))
    doc["configs"] = [{
        "name": "tiny-w32-asaga", "source": "rehearsal", "reduced": [],
        "why": "rehearsal",
        "file": "tests/benchmark/configs/tiny-w32-asaga.json"}]
    doc["workloads"] = [{"name": TINY, "config": "tiny-w32-asaga",
                         "traffic": "tiny-cloud", "chips": 1,
                         "why": "rehearsal"}]
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = [TINY]
    path = tmp_path_factory.mktemp("bench_saga_cloud") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def test_traced_rehearsal_reports_the_eleven_metrics(
        saga_cloud_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, saga_cloud_manifest, TINY, trace=1, seconds=4.0,
                     seed=2_147_483_659)
    assert rc == 0
    last = json.loads(lines[-1])
    assert RESULT_KEYS <= set(last) <= RESULT_KEYS | {"breakdown"}
    # on the CPU there is no device plane: the one reader of the device
    # trace finds nothing and is left out (as ``busy_s`` / ``window_s``,
    # which a traced line has on the chip: PERF.md section 6, PR 58)
    got = last["metrics"]
    here = [n for n in NEW if n != "w32_history_device_ms"]
    assert set(NEW) & set(got) == set(here), sorted(set(NEW) - set(got))
    assert "busy_s" not in last["device"]
    assert {n: got[n]["unit"] for n in here} == {
        n: BY_NAME[n]["unit"] for n in here}
    # the accepted metrics keep their lists: none of the originals is here
    assert not set(DOUBLED.values()) & set(got)
    record = _info(lines, "checks")
    extras, accepted = record["result"]["extras"], record["result"][
        "accepted"]
    # the calibration ended inside the window: 100 x 12 accepted updates
    assert 1_200 <= extras["delay_calibrated_at_update"] < accepted
    assert extras["straggler_workers"] == 3 and extras["delayed_tasks"] > 0
    for who in ("late", "healthy"):
        n, total = (extras[f"history_age_{who}_n"],
                    extras[f"history_age_{who}_sum"])
        assert n > 0 and got[f"{who}_history_age"]["value"] == total / n
    counted = extras["history_age_late_n"] + extras["history_age_healthy_n"]
    under = accepted - extras["delay_calibrated_at_update"]
    assert under - 12 <= counted <= under
    # a late worker's slice is the older one; the healthy ones come round
    # oftener than once a fleet
    assert (got["late_history_age"]["value"]
            > got["healthy_history_age"]["value"])
    assert got["saga_delay_avg_ms"]["value"] == extras["avg_delay_ms"] > 0
    assert got["saga_updates_under_delay"]["value"] == pytest.approx(
        100 * under / accepted)
    assert got["saga_straggler_update_share"]["value"] == pytest.approx(
        100 * extras["accepted_from_stragglers"] / accepted)
    assert 0 < got["saga_straggler_update_share"]["value"] < 100 * 3 / 12
    assert 0 < got["saga_delay_sleep_share"]["value"] <= 100.0
    assert got["w32_history_reuse"]["value"] == pytest.approx(
        100 * extras["history_reused"] / accepted)
    assert got["w32_history_drift"]["value"] == extras["history_drift"]
    assert got["w32_merge_history_p50_ms"]["value"] > 0
    # history_within compared, and under its limit
    within = last["compared"]["history_within"]
    assert within["value"] <= within["limit"] == 2e-6
    assert all(record["checks"].values()), record["checks"]
    assert last["correct"] is True and last["failed"] == 0


def test_untraced_rehearsal_reports_the_end_to_end_metrics_alone(
        saga_cloud_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, saga_cloud_manifest, TINY, seconds=4.0,
                     seed=3_000_000_019)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS and list(last)[-1] == "compared"
    assert set(last["metrics"]) == set(E2E)
    assert "history_within" in last["compared"]
    for name, c in last["compared"].items():
        assert c["value"] <= c["limit"], (name, c)
    assert last["correct"] is True
    extras = _info(lines, "checks")["result"]["extras"]
    assert extras["delayed_tasks"] > 0 and extras["history_age_late_n"] > 0


def test_a_steady_rehearsal_reports_neither_age(
        saga_cloud_manifest, on_cpu, capsys, tmp_path):
    """The same configuration with nobody late (``steady``): the four
    integers read zero and the line leaves both ages out, with the four
    readers of the delay's account; the history's counters and span read as
    ever (the device trace's one finds no device plane on the CPU)."""
    doc = json.load(open(saga_cloud_manifest))
    quiet = "tiny-w32-asaga.steady"
    doc["workloads"] = [dict(doc["workloads"][0], name=quiet,
                             traffic="steady")]
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = [quiet]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    on_cpu(1)
    rc, lines = _run(capsys, str(path), quiet, trace=1, seed=77)
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] is True
    assert set(NEW) & set(last["metrics"]) == {
        n for n in NEW if n.startswith("w32_")} - {"w32_history_device_ms"}
    extras = _info(lines, "checks")["result"]["extras"]
    assert [extras[k] for k in STEADY] == list(STEADY.values())


# ----------------------------------------------------- the builder's checks
@pytest.fixture()
def patched(monkeypatch):
    """``check_delay``'s and ``check_history_age``'s patches, undone after
    the test: they replace ``engine_loop.DelayModel``,
    ``engine_loop.RunInstruments`` and
    ``RunInstruments.on_gradient_merged`` for every run built after."""
    from asyncframework_tpu.solvers import engine_loop, instrumentation

    monkeypatch.setattr(engine_loop, "DelayModel", engine_loop.DelayModel)
    monkeypatch.setattr(engine_loop, "RunInstruments",
                        engine_loop.RunInstruments)
    monkeypatch.setattr(
        instrumentation.RunInstruments, "on_gradient_merged",
        instrumentation.RunInstruments.on_gradient_merged)
    return True


@pytest.mark.parametrize("halved", [False, True], ids=["as-drawn", "halved"])
def test_check_delay_holds_an_asaga_run_to_the_reference(
        halved, saga_cloud_manifest, on_cpu, capsys, patched):
    """``benchmark/check_delay.py`` builds ``solvers.ASAGA`` for an
    ``asaga`` plan: correct as the program is, NOT correct with every sleep
    halved."""
    from benchmark import check_delay

    assert patched
    on_cpu(1)
    argv = ["--workload", TINY, "--seed", "3000000019", "--seconds", "4.0"]
    rc = check_delay.main(argv + (["--halve"] if halved else []),
                          manifest_path=saga_cloud_manifest)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])["check_delay"]
    assert out["halved"] is halved and out["accepted"] > 1_200
    assert out["who"]["late"] == {"0": "long_tail", "4": "normal",
                                  "8": "normal"}
    assert out["who"]["within"] and out["calibration"]["within"]
    assert out["schedule"]["delayed_tasks"] > 0
    if halved:
        assert rc == 1 and out["correct"] is False
        assert not out["schedule"]["within"] and not out["account"]["within"]
    else:
        assert rc == 0 and out["correct"] is True
        assert out["schedule"]["differ"] == 0
        assert out["account"]["log"] == out["account"]["extras"]


@pytest.mark.parametrize("dropped", [False, True],
                         ids=["by-class", "class-dropped"])
def test_check_history_age_holds_the_run_to_the_replay(
        dropped, saga_cloud_manifest, on_cpu, capsys, patched):
    """``benchmark/check_history_age.py``: the four integers equal the
    replay of the run's own accept order EXACTLY; NOT correct (its negative
    control) when every late worker's age is booked to the healthy class:
    by both classes, while the accept order still holds."""
    from benchmark import check_history_age

    assert patched
    on_cpu(1)
    argv = ["--workload", TINY, "--seed", "3000000019", "--seconds", "4.0"]
    rc = check_history_age.main(
        argv + (["--drop-class"] if dropped else []),
        manifest_path=saga_cloud_manifest)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])[
        "check_history_age"]
    assert out["dropped_class"] is dropped and out["accepted"] > 1_200
    assert out["order"]["within"]
    assert out["order"]["heard"] == out["accepted"]
    assert out["order"]["calibrated_at_update"] >= 1_200
    ref_late, ref_healthy = (out["late"]["reference"],
                             out["healthy"]["reference"])
    assert ref_late["history_age_late_n"] > 0
    assert ref_healthy["history_age_healthy_n"] > 0
    assert set(out["by_class"]) == {"healthy", "normal", "long_tail"}
    assert out["by_class"]["long_tail"]["count"] + out["by_class"]["normal"][
        "count"] == ref_late["history_age_late_n"]
    # the long tail's slice is the oldest, the healthy ones' the youngest
    means = {k: v["mean"] for k, v in out["by_class"].items()}
    assert means["long_tail"] > means["normal"] > means["healthy"]
    if dropped:
        assert rc == 1 and out["correct"] is False
        assert not out["late"]["within"] and not out["healthy"]["within"]
        got = out["healthy"]["extras"]
        assert out["late"]["extras"] == {"history_age_late_sum": 0,
                                         "history_age_late_n": 0}
        assert got["history_age_healthy_sum"] == (
            ref_late["history_age_late_sum"]
            + ref_healthy["history_age_healthy_sum"])
        assert got["history_age_healthy_n"] == (
            ref_late["history_age_late_n"]
            + ref_healthy["history_age_healthy_n"])
    else:
        assert rc == 0 and out["correct"] is True
        assert out["late"]["extras"] == ref_late
        assert out["healthy"]["extras"] == ref_healthy


def test_check_history_age_refuses_a_cell_without_a_delayed_history(
        saga_cloud_manifest, on_cpu, tmp_path):
    from benchmark import check_history_age

    doc = json.load(open(saga_cloud_manifest))
    doc["workloads"][0]["traffic"] = "steady"
    steady = tmp_path / "steady.json"
    steady.write_text(json.dumps(doc))
    doc["workloads"][0]["traffic"] = "tiny-cloud"
    doc["workloads"][0]["config"] = "tiny-dense-cloud"
    doc["configs"][0].update(
        name="tiny-dense-cloud",
        file="tests/benchmark/configs/tiny-dense-cloud.json")
    asgd = tmp_path / "asgd.json"
    asgd.write_text(json.dumps(doc))
    on_cpu(1)
    for path in (steady, asgd):
        with pytest.raises(ValueError, match="no delayed asynchronous ASAGA"):
            check_history_age.main(["--workload", TINY, "--seed", "1",
                                    "--seconds", "1.0"],
                                   manifest_path=str(path))


def test_the_reference_restates_the_age_with_no_program_code():
    src = open(reference_history_age.__file__).read()
    assert "asyncframework_tpu" not in src.replace(
        "no program code", "") and "import jax" not in src
    order = [3, 0, 1, 3, 2, 0, 3]
    assert reference_history_age.ages(order, 2) == [(3, 3), (0, 4), (3, 3)]
    assert reference_history_age.account(order, [0], 2) == {
        "history_age_late_sum": 4, "history_age_late_n": 1,
        "history_age_healthy_sum": 6, "history_age_healthy_n": 2}
