"""CPU rehearsal of what ISSUE 23 gives the benchmark: six per-layer
metrics read from the program's engine spans and always-on counters, and
the three records the benchmark reconstructs today, checked against its
reconstruction (``TrainResult.snapshot_updates``, ``staleness_hist``,
``compiles_in_run``)."""

import json

import pytest

from test_bench_harness import (  # noqa: F401 - fixtures, by name
    PER_LAYER,
    TINY_CELLS,
    _run,
    on_cpu,
    tiny_manifest,
)

from benchmark import manifest as manifest_mod
from benchmark import target

NEW = ["task_dispatch_p50_ms", "task_device_wait_p50_ms",
       "result_queue_p50_ms", "updater_busy", "submitter_busy",
       "host_stall_max_ms"]


def _infos(lines):
    return [json.loads(ln)["info"] for ln in lines[:-1]]


def test_the_manifest_appends_the_six_engine_metrics():
    # behind the ten there were, by position: later PRs append behind these
    assert PER_LAYER[10:16] == NEW
    for name in NEW:
        mod = manifest_mod.Manifest().metric_reader(name)
        assert (mod.LAYER, mod.MOVES) == ("engine", "updates_per_s")


@pytest.mark.parametrize("cell", ["tiny-dense-f32.steady",
                                  "tiny-asaga.steady"])
def test_traced_rehearsal_reports_the_engine_metrics(
        cell, tiny_manifest, on_cpu, capsys):
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, tiny_manifest, cell, trace=1)
    assert rc == 0
    last = json.loads(lines[-1])
    # the metrics only, not ``correct``: whether a TRACED 1.5 s ASAGA run
    # on a loaded CPU crosses its target is not this test's (the parent
    # commit's does not either)
    got = last["metrics"]
    assert set(NEW) <= set(got), sorted(got)
    for name in NEW:
        assert got[name]["value"] >= 0.0, name
    assert got["updater_busy"]["unit"] == "%" and got["updater_busy"]["value"] <= 100
    assert got["submitter_busy"]["value"] <= 100
    # the children cover the task: none is longer than their parent
    task = got["task_p50_ms"]["value"]
    parts = [got[n]["value"] for n in NEW[:3]]
    assert max(parts) <= task * 1.5 + 1.0
    record = [i for i in _infos(lines) if "checks" in i][0]
    extras = record["result"]["extras"]
    for key in ("updater_busy_s", "updater_wait_s", "submitter_busy_s",
                "submitter_wait_s", "submit_empty_polls", "drains",
                "updater_apply_s", "drain_items_max", "task_retries",
                "compiles_in_run", "host_stall_max_ms", "host_stalls"):
        assert key in extras, key
    assert 0.0 <= extras["updater_apply_s"] <= extras["updater_busy_s"]
    assert extras["compiles_in_run"] == 0
    assert record["checks"]["no_compile_in_window"]


def test_the_readers_find_nothing_on_a_program_without_the_stages():
    """The parent commit records neither the stages nor the counters: the
    readers return None there and the line leaves the metrics out."""
    run = {"program_trace": {"stages_ms": {"compute": {"count": 3, "p50": 9.0}}},
           "result": {"elapsed_s": 2.0, "extras": {}}}
    man = manifest_mod.Manifest()
    for name in NEW:
        assert man.metric_reader(name).read(run, None) is None, name
    run["program_trace"] = None  # an untraced record
    for name in NEW:
        assert man.metric_reader(name).read(run, None) is None, name
    run["result"]["extras"] = {"updater_busy_s": 0.5, "submitter_busy_s": 0.1,
                               "host_stall_max_ms": 7.5}
    assert man.metric_reader("updater_busy").read(run, None) == 25.0
    assert man.metric_reader("submitter_busy").read(run, None) == 5.0
    assert man.metric_reader("host_stall_max_ms").read(run, None) == 7.5


@pytest.mark.parametrize("cell", ["tiny-dense-f32.steady",
                                  "tiny-asaga.steady",
                                  "tiny-dense-f32.tiny-sync"])
def test_the_programs_records_equal_the_benchmarks_reconstruction(
        cell, tiny_manifest, on_cpu, capsys, monkeypatch):
    """``snapshot_updates`` against ``target.snapshot_updates`` (from
    ``printer_freq`` and the solvers' cadence), ``compiles_in_run`` against
    ``compiles_in_window``, and the histogram's mean against the sampled
    ``staleness_mean``, in the checked run of a rehearsal."""
    from asyncframework_tpu import solvers

    results = []
    for cls in (solvers.ASGD, solvers.ASAGA):
        for mode in ("run", "run_sync"):
            real = getattr(cls, mode)

            def spy(self, _real=real):
                res = _real(self)
                results.append(res)
                return res

            monkeypatch.setattr(cls, mode, spy)
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, tiny_manifest, cell, trace=1, seconds=1.5)
    assert rc == 0
    infos = _infos(lines)
    plan = [i for i in infos if "plan" in i][0]["plan"]
    record = [i for i in infos if "checks" in i][0]
    # warm-up, the checked run, the profiled run
    assert len(results) == 3
    res = results[1]
    assert res.accepted == record["result"]["accepted"]
    sync = plan["mode"] == "sync"
    want = target.snapshot_updates(
        len(res.trajectory), plan["printer_freq"], res.accepted,
        per_snapshot=plan["num_workers"] if sync else 1,
    )
    assert res.snapshot_updates == want
    assert [u for u, _f in record["trajectory"]] == want
    assert res.extras["compiles_in_run"] == 0
    assert record["checks"]["no_compile_in_window"]
    assert sum(res.staleness_hist.values()) == res.accepted + res.dropped
    exact = (sum(s * n for s, n in res.staleness_hist.items())
             / sum(res.staleness_hist.values()))
    sampled = json.loads(lines[-1])["metrics"]["staleness_mean"]["value"]
    # one update in eight is sampled: the two means agree to the sample's
    # error, far inside the worker count
    assert abs(exact - sampled) <= 0.25 * plan["num_workers"]
