"""CPU rehearsal of what ISSUE 51 gives the benchmark: the configuration
``mnist8m-w32-asgd`` (the paper's production-cluster deployment, Figs 7-8),
the traffic mix ``cloud`` (``coeff`` -1), their one cell, six per-layer
metrics that read the delay's stage and account, each a file of its own that
lists the one cell, the plain reference of the schedule
(``benchmark/reference_delay.py``) and the builder's check
(``benchmark/check_delay.py``) with its negative control.  The rehearsal
configuration is ``tiny-dense-cloud`` under ``tiny-cloud``, through
``run.py`` as it stands."""

import json
import os

import pytest

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

from benchmark import manifest as manifest_mod
from benchmark import plan as plan_mod, reference_delay

CONFIG = "mnist8m-w32-asgd"
CELL = CONFIG + ".cloud"
NEW = ["delay_avg_ms", "task_delay_p50_ms", "delay_sleep_share",
       "straggler_update_share", "updates_under_delay",
       "cloud_updates_per_apply"]
MOVES = dict.fromkeys(NEW, "updates_per_s")
MOVES.update(straggler_update_share="time_to_target_s",
             updates_under_delay="time_to_target_s")
TINY = "tiny-dense-cloud.tiny-cloud"
HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------------------- the manifest
def test_the_manifest_appends_one_configuration_one_cell_six_metrics():
    # found by name: later PRs append behind these, so no tail is pinned
    names = [c["name"] for c in MANIFEST["configs"]]
    assert names.index(CONFIG) >= 7  # behind the seven there were
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["storage_dtype"]
    assert len(entry["source"]) <= 200
    for said in ("1907.08526", "Figs 7-8", "README.md:72", "32 workers",
                 "8,100,000 x 784"):
        assert said in entry["source"], said
    assert entry["file"] == "benchmark/configs/mnist8m-w32-asgd.json"
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert cells.index(CELL) >= 8  # behind the eight there were
    (cell,) = [c for c in MANIFEST["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "cloud", 1)
    assert len(cell["why"]) <= 200
    # one cell of the configuration, one cell under the mix
    assert [c["name"] for c in MANIFEST["workloads"]
            if c["config"] == CONFIG or c["traffic"] == "cloud"] == [CELL]
    first = PER_LAYER.index(NEW[0])
    assert first >= 51 and PER_LAYER[first:first + 6] == NEW
    for m in MANIFEST["per_layer"][first:first + 6]:
        assert m["workloads"] == [CELL] and m["moves"] == MOVES[m["name"]]
        assert (m["layer"], m["unit"]) == ("engine", {
            "delay_avg_ms": "ms", "task_delay_p50_ms": "ms",
            "cloud_updates_per_apply": "updates"}.get(m["name"], "%"))
    man = manifest_mod.Manifest()
    # every accepted metric without a cell list reports in the new cell
    # too; the six in no other; ``updates_per_apply`` keeps its own list
    here = {m["name"] for m in man.metric_entries("per_layer", CELL)}
    assert set(PER_LAYER[:16]) | set(NEW) <= here
    assert {"chip_starved", "barrier_hold", "inflight_mean",
            "worker_idle_p50_ms", "device_idle", "step_roofline"} <= here
    assert "updates_per_apply" not in here
    for m in MANIFEST["per_layer"]:
        if m["name"] == "updates_per_apply":
            assert m["workloads"] == [
                "mnist8m-asgd.steady", "mnist8m-asgd.steady-w32",
                "mnist8m-f32-asgd.steady"]
    for other in MANIFEST["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m["name"] for m in man.metric_entries(
                "per_layer", other["name"])}
    # every other cell still runs with nobody late
    for other in MANIFEST["workloads"]:
        plan = plan_mod.resolve(man.config(other["config"]),
                                man.traffic(other["traffic"]))
        assert (plan["coeff"] != 0) == (other["name"] == CELL)


def test_the_entries_in_front_of_them_stand_as_they_were():
    """``test_bench_dense_tiles_read.py`` (PR 49) asserts that ITS metric
    is the list's last and ``model_read_local`` the one before, which an
    append ends (``tests/conftest.py`` marks the two assertions and says
    why); what they hold beyond the position is held here."""
    at = PER_LAYER.index(NEW[0])
    assert PER_LAYER[at - 2:at] == ["model_read_local", "dense_tiles_read"]
    assert PER_LAYER.count("dense_tiles_read") == 1
    assert PER_LAYER.count("model_read_local") == 1
    assert MANIFEST["per_layer"][at - 1] == {
        "name": "dense_tiles_read", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "steps",
        "moves": "updates_per_s", "workloads": ["mnist8m-asaga.steady"],
    }
    assert MANIFEST["per_layer"][at - 2] == {
        "name": "model_read_local", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "updates_per_s", "workloads": ["mnist8m-f32-asgd.steady"],
    }
    man = manifest_mod.Manifest()
    for name, layer in (("dense_tiles_read", "steps"),
                        ("model_read_local", "engine")):
        mod = man.metric_reader(name)
        assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            name, "%", "program_counter", layer, "updates_per_s")
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name["step_device_ms"]["layer"] == "steps"
    config = [c for c in MANIFEST["configs"] if c["name"] == "mnist8m-asaga"]
    assert json.load(open(config[0]["file"]))["batch_rate"] == 0.01
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert [n for n, w in cells.items() if w["chips"] > 1] == [
        "mnist8m-f32-asgd.steady"]


def test_the_configuration_is_mnist8m_under_the_recipe_of_figs_7_and_8():
    man = manifest_mod.Manifest()
    config = man.config(CONFIG)
    _held_to_its_sources_shape(config)
    twin = man.config("mnist8m-asgd")
    # the same data: the block letter for letter, so that the pair
    # ``steady-w32`` / ``cloud`` differs in the tail and nothing else
    for key in ("kind", "n", "d", "published", "storage_dtype", "noise",
                "pins", "reduced", "loss", "solver", "batch_rate",
                "bucket_ratio", "target_fraction"):
        assert config[key] == twin[key], key
    assert "generator" not in config and "generator" not in twin
    assert config["published"] == {"n": 8_100_000, "d": 784}
    assert (config["n"], config["d"]) == (8_100_000, 784)
    assert config["reduced"] == ["storage_dtype"]
    # the recipe: reference README.md:72
    assert (config["solver"], config["loss"], config["num_workers"],
            config["batch_rate"], config["bucket_ratio"],
            config["target_fraction"]) == (
        "asgd", "least_squares", 32, 0.1, 0.7, 0.001)
    assert config["printer_freq"] == 160
    # the crossing is asked for at 2.5 to 3 calibrations: by section 4's
    # rule for this gamma
    nw, d = 32, 784
    rule = nw * ((3.45 * d / (2 * nw * config["gamma"]) + 1) ** 2 - 1)
    assert 8_000 <= rule <= 9_600
    # the floor: 32 shards of 253,125 rows of bf16 are 80% of the chip
    assert config["n"] == 32 * 253_125
    stored = config["n"] * config["d"] * 2
    assert 0.75 * 16e9 < stored < 0.85 * 16e9
    said = config["deployment"]
    for word in ("32 logical workers", "ONE", "12.74 GB", "80%",
                 "one device queue"):
        assert word in said, word
    for key in ("gamma", "data", "noise", "printer_freq", "delay_scale"):
        assert len(config["assumed"][key]) > 20, key
    assert "OWN" in config["assumed"]["delay_scale"]
    assert len(config["guarantees"]) > 20


def test_the_real_cell_resolves_to_the_recipes_plan():
    man = manifest_mod.Manifest()
    _cell_resolves_to_a_plan_from_its_files(man, CELL)
    config, mix = man.config(CONFIG), man.traffic("cloud")
    plan = plan_mod.resolve(config, mix)
    assert (plan["num_workers"], plan["batch_rate"], plan["bucket_ratio"],
            plan["coeff"], plan["mode"], plan["taw"]) == (
        32, 0.1, 0.7, -1.0, "async", 2_147_483_647)
    assert (plan["solver"], plan["loss"]) == ("asgd", "least_squares")
    # gamma and printer_freq are the configuration's: the mix overrides
    # nothing, for this configuration or any
    assert (plan["gamma"], plan["printer_freq"]) == (
        config["gamma"], config["printer_freq"])
    assert "per_config" not in mix and "num_workers" not in mix
    assert len(mix["why"]) > 20 and set(mix["assumed"]) >= {"who_is_late"}
    # who is late at this count, by the reference
    late = reference_delay.late_workers(plan["num_workers"], plan["coeff"])
    assert sorted(w for w, c in late.items() if c == "long_tail") == [0, 4]
    assert sorted(w for w, c in late.items() if c == "normal") == [
        8, 12, 16, 20, 24, 28]
    # the rehearsal runs the same mix's parameters at a CPU test's size
    tiny_mix = json.load(open(os.path.join(HERE, "traffic",
                                           "tiny-cloud.json")))
    for key in ("mode", "coeff", "taw"):
        assert tiny_mix[key] == mix[key], key
    tiny = json.load(open(os.path.join(HERE, "configs",
                                       "tiny-dense-cloud.json")))
    for key in ("kind", "solver", "loss", "batch_rate", "bucket_ratio"):
        assert tiny[key] == config[key], key
    assert sorted(reference_delay.late_workers(
        tiny["num_workers"]).values()) == ["long_tail", "normal", "normal"]


# --------------------------------------------------------------- the readers
def _record(extras=None, stages=None, accepted=31_400, elapsed_s=20.0):
    return {
        "program_trace": {"stages_ms": stages or {}},
        "result": {"elapsed_s": elapsed_s, "accepted": accepted,
                   "extras": extras or {}},
    }


#: a window of the cell as ISSUE 51 expects it
CLOUD = {"avg_delay_ms": 15.0, "delay_calibrated_at_update": 3_204,
         "delay_calibrated_at_s": 2.0, "straggler_workers": 8,
         "delayed_tasks": 2_600, "delay_sleep_s": 96.0,
         "delay_sleep_long_tail_s": 30.0, "accepted_from_stragglers": 2_826,
         "accepted_after_calibration": 28_196, "apply_dispatches": 7_850}
#: what a ``coeff`` 0 run of this PR's program reports
STEADY = {"avg_delay_ms": 0.0, "delay_calibrated_at_update": 0,
          "delay_calibrated_at_s": 0.0, "straggler_workers": 0,
          "delayed_tasks": 0, "delay_sleep_s": 0.0,
          "delay_sleep_long_tail_s": 0.0, "accepted_from_stragglers": 0,
          "accepted_after_calibration": 0}
WANT = {"delay_avg_ms": 15.0, "task_delay_p50_ms": 31.0,
        "delay_sleep_share": 100 * 96.0 / (8 * 18.0),
        "straggler_update_share": 100 * 2_826 / 31_400,
        "updates_under_delay": 100 * 28_196 / 31_400,
        "cloud_updates_per_apply": 4.0}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_the_account_and_the_stage(name):
    read = manifest_mod.Manifest().metric_reader(name).read
    run = _record(extras=CLOUD,
                  stages={"task.delay": {"count": 325, "p50": 31.0},
                          "compute": {"count": 3_925, "p50": 14.1}})
    assert read(run, None) == pytest.approx(WANT[name])
    # the device trace is nothing to any of them
    assert read(run, {"modules": {}, "busy_s": 2.8}) == read(run, None)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_nothing_was_injected(name):
    """The parent's record has no account and no such stage; a ``coeff`` 0
    run of this program reports zeros and records no ``task.delay``; a run
    that ended inside its calibration injected nothing.  Each reader
    returns None and raises nothing: the line leaves the metric out."""
    read = manifest_mod.Manifest().metric_reader(name).read
    stages = {"compute": {"count": 3_925, "p50": 13.4},
              "task.delay": {"count": 0}}
    short = dict(STEADY, straggler_workers=8)
    for extras in ({}, {"updater_busy_s": 17.1}, STEADY, short):
        for record in (_record(extras=extras, stages=stages),
                       dict(_record(extras=extras), program_trace=None)):
            if name == "cloud_updates_per_apply":
                continue  # reads ``apply_dispatches``: below
            assert read(record, None) is None, (name, extras)
    if name == "cloud_updates_per_apply":
        # the accepted metric's own reading, where it would read
        own = manifest_mod.Manifest().metric_reader("updates_per_apply").read
        for extras in ({}, {"apply_dispatches": 0},
                       {"apply_dispatches": 7_850}):
            record = _record(extras=extras)
            assert read(record, None) == own(record, None)
        assert read(_record(extras={}), None) is None


# ------------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def cloud_manifest(tmp_path_factory):
    """The real manifest's metrics over the rehearsal configuration: its
    cell is an entry, and the six new metrics list it."""
    doc = json.loads(json.dumps(MANIFEST))
    doc["configs"] = [{
        "name": "tiny-dense-cloud", "source": "rehearsal", "reduced": [],
        "why": "rehearsal",
        "file": "tests/benchmark/configs/tiny-dense-cloud.json"}]
    doc["workloads"] = [{"name": TINY, "config": "tiny-dense-cloud",
                         "traffic": "tiny-cloud", "chips": 1,
                         "why": "rehearsal"}]
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = [TINY]
    path = tmp_path_factory.mktemp("bench_cloud") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def _info(lines, key):
    return [json.loads(ln)["info"] for ln in lines[:-1]
            if key in json.loads(ln)["info"]][0]


def test_traced_rehearsal_reports_the_six_metrics(
        cloud_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, cloud_manifest, TINY, trace=1, seconds=3.0,
                     seed=2_147_483_659)
    assert rc == 0
    last = json.loads(lines[-1])
    assert RESULT_KEYS <= set(last) <= RESULT_KEYS | {"breakdown"}
    got = last["metrics"]
    assert set(NEW) <= set(got), sorted(got)
    units = {"delay_avg_ms": "ms", "task_delay_p50_ms": "ms",
             "delay_sleep_share": "%", "straggler_update_share": "%",
             "updates_under_delay": "%", "cloud_updates_per_apply": "updates"}
    assert {n: got[n]["unit"] for n in NEW} == units
    assert "updates_per_apply" not in got  # the accepted one keeps its list
    record = _info(lines, "checks")
    extras, accepted = record["result"]["extras"], record["result"][
        "accepted"]
    # the calibration ended inside the window: 100 x 12 accepted updates
    assert 1_200 < extras["delay_calibrated_at_update"] < accepted
    assert extras["straggler_workers"] == 3 and extras["delayed_tasks"] > 0
    assert got["delay_avg_ms"]["value"] == extras["avg_delay_ms"] > 0
    assert got["updates_under_delay"]["value"] == pytest.approx(
        100 * (accepted - extras["delay_calibrated_at_update"]) / accepted)
    assert got["straggler_update_share"]["value"] == pytest.approx(
        100 * extras["accepted_from_stragglers"] / accepted)
    assert 0 < got["straggler_update_share"]["value"] < 100 * 3 / 12
    assert 0 < got["delay_sleep_share"]["value"] <= 100.0
    assert got["cloud_updates_per_apply"]["value"] == pytest.approx(
        accepted / extras["apply_dispatches"])
    # a delayed task sleeps at least 1.5 x the scale, to the millisecond
    assert got["task_delay_p50_ms"]["value"] >= round(
        1.5 * extras["avg_delay_ms"])
    assert 0 < extras["delay_sleep_long_tail_s"] < extras["delay_sleep_s"]
    assert all(record["checks"].values()), record["checks"]
    assert last["correct"] is True and last["failed"] == 0
    # the profiled run is a run of its own: it calibrates anew, and says
    # where in ITS window the tail began (or that it never did)
    prof = _info(lines, "profiled_run")["profiled_run"]
    assert prof["compiles"] == 0
    assert "delay_calibrated_at_s" in prof["extras"]


def test_untraced_rehearsal_reports_the_end_to_end_metrics_alone(
        cloud_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, cloud_manifest, TINY, seconds=3.0,
                     seed=3_000_000_019)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS and list(last)[-1] == "compared"
    assert set(last["metrics"]) == set(E2E)
    for name, c in last["compared"].items():
        assert c["value"] <= c["limit"], (name, c)
    assert last["correct"] is True
    record = _info(lines, "checks")
    assert record["result"]["extras"]["delayed_tasks"] > 0


def test_a_steady_rehearsal_reports_none_of_the_six(
        cloud_manifest, on_cpu, capsys, tmp_path):
    """The same configuration with nobody late (``steady``): the account
    reads zeros, no ``task.delay`` is recorded, and the line leaves five of
    the six out; ``cloud_updates_per_apply`` reads what the accepted
    metric reads."""
    doc = json.load(open(cloud_manifest))
    quiet = "tiny-dense-cloud.steady"
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
    assert set(NEW) & set(last["metrics"]) == {"cloud_updates_per_apply"}
    extras = _info(lines, "checks")["result"]["extras"]
    assert extras["delayed_tasks"] == 0 and extras["delay_sleep_s"] == 0.0
    assert extras["straggler_workers"] == 0 and extras["avg_delay_ms"] == 0.0


# ------------------------------------------------------ the builder's check
@pytest.fixture()
def patched(monkeypatch):
    """``check_delay``'s patch, undone after the test: it replaces
    ``engine_loop.DelayModel`` and ``RunInstruments.on_gradient_merged``
    for every run built after it."""
    from asyncframework_tpu.solvers import engine_loop, instrumentation

    monkeypatch.setattr(engine_loop, "DelayModel", engine_loop.DelayModel)
    monkeypatch.setattr(
        instrumentation.RunInstruments, "on_gradient_merged",
        instrumentation.RunInstruments.on_gradient_merged)
    return True


@pytest.mark.parametrize("halved", [False, True], ids=["as-drawn", "halved"])
def test_check_delay_holds_the_run_to_the_reference(
        halved, cloud_manifest, on_cpu, capsys, patched):
    """``benchmark/check_delay.py``: correct on the program as it is, NOT
    correct (its negative control) when every sleep is halved inside the
    patch: by the schedule and by the account, while who is late and when
    the tail began still hold."""
    from benchmark import check_delay

    assert patched  # in place before main() applies the patch
    on_cpu(1)
    argv = ["--workload", TINY, "--seed", "3000000019", "--seconds", "3.0"]
    rc = check_delay.main(argv + (["--halve"] if halved else []),
                          manifest_path=cloud_manifest)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])["check_delay"]
    assert out["halved"] is halved and out["accepted"] > 1_200
    assert out["who"]["late"] == {"0": "long_tail", "4": "normal",
                                  "8": "normal"}
    assert out["who"]["within"] and out["calibration"]["within"]
    assert out["calibration"]["slept_before"] == 0
    assert out["calibration"]["accepted_before"] > 1_200
    assert out["schedule"]["delayed_tasks"] > 0
    if halved:
        assert rc == 1 and out["correct"] is False
        assert not out["schedule"]["within"] and not out["account"]["within"]
        first = out["schedule"]["first_differs"]
        assert first["slept_ms"] * 2 == first["reference_ms"]
        assert out["account"]["extras"]["delay_sleep_s"] == pytest.approx(
            2 * out["account"]["log"]["delay_sleep_s"])
    else:
        assert rc == 0 and out["correct"] is True
        assert out["schedule"]["differ"] == 0
        assert out["account"]["log"] == out["account"]["extras"]


def test_check_delay_refuses_a_cell_with_nobody_late(cloud_manifest, on_cpu):
    from benchmark import check_delay

    doc = json.load(open(cloud_manifest))
    doc["workloads"][0]["traffic"] = "steady"
    path = os.path.join(os.path.dirname(cloud_manifest), "steady.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    on_cpu(1)
    with pytest.raises(ValueError, match="no asynchronous straggler cell"):
        check_delay.main(["--workload", TINY, "--seed", "1",
                          "--seconds", "1.0"], manifest_path=path)
