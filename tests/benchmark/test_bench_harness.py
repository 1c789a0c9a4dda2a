"""CPU rehearsal of the benchmark harness (``benchmark/run.py``).

Everything the manifest names loads by name; every kind of cell the harness
takes as data (dense f32, dense bf16, padded ELL, ASAGA, the synchronous
barrier, four devices) runs at a tiny size and prints the contract's last
line.  The platform check is relaxed HERE, by patching the module, not by
an option of ``run.py``: the command the driver runs has no such switch.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import plan as plan_mod, roofline, run, target  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
E2E = [m["name"] for m in MANIFEST["end_to_end"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]
#: ``compared`` is the harness's own and comes last: every number ``correct``
#: was decided from, beside its limit (the driver ignores the key)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}

# ------------------------------------------------------------ the manifest


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    cells = MANIFEST["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(set(pairs)) == len(pairs)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert {c["config"] for c in cells} == {c["name"] for c in MANIFEST["configs"]}
    assert "setup_s" in E2E
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"
        }
        assert m["moves"] in E2E
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in MANIFEST[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(E2E + PER_LAYER)) == len(E2E + PER_LAYER)


def test_a_full_check_fits_the_drivers_budget_with_24_cells():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert run.RUN_OVERHEAD_S == 60  # what the contract allows a run
    assert runs * (rs + run.RUN_OVERHEAD_S) + 24 * 2 * 90 + 1200 <= 43200


def _digits(text):
    return re.sub(r"(?<=\d)[,_](?=\d)", "", text)


def _held_to_its_sources_shape(config):
    """A configuration file states what ITS source publishes (``published``:
    ``n``, ``d`` and, for padded ELL, ``nnz_per_row``).  No width is cut,
    ever; the scale ``n`` is cut only where the file lists it in ``reduced``
    and its ``deployment`` names both row counts beside the reason."""
    pub = config["published"]
    assert config["d"] == pub["d"], "d is a width: never cut"
    if config["kind"] == "sparse":
        assert config["nnz_per_row"] == pub["nnz_per_row"], \
            "the non-zeros a row are a width: never cut"
    if config["n"] != pub["n"]:
        assert "n" in config["reduced"], "n differs and is not listed"
        assert config["n"] < pub["n"], "n is over the published n"
        said = _digits(config.get("deployment", ""))
        assert str(config["n"]) in said and str(pub["n"]) in said, \
            "deployment does not say by how much n is cut"


def _cell_resolves_to_a_plan_from_its_files(man, cell):
    entry = man.workload(cell)
    config = man.config(entry["config"])
    mix = man.traffic(entry["traffic"])
    plan = plan_mod.resolve(config, mix)
    assert set(plan) == set(plan_mod.RUN_KEYS)
    centry = [c for c in man.doc["configs"] if c["name"] == entry["config"]][0]
    assert centry["file"].startswith(tuple(p + "/" for p in man.doc["paths"]))
    assert config["reduced"] == centry["reduced"]
    assert config["source"] == centry["source"]
    assert {"gamma", "data", "noise"} <= set(config["assumed"])
    _held_to_its_sources_shape(config)
    kw = plan_mod.solver_config_kwargs(plan, seed=3, seconds=20, trace=False)
    from asyncframework_tpu.solvers.base import SolverConfig

    cfg = SolverConfig(**kw)
    assert cfg.run_timeout_s == 20 and cfg.trace_sample is None
    assert cfg.drain_batch == 1  # left at the program's default


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_cell_resolves_to_a_plan_from_its_files(cell):
    _cell_resolves_to_a_plan_from_its_files(manifest_mod.Manifest(), cell)


@pytest.mark.parametrize("kind,name", [("end_to_end", n) for n in E2E]
                         + [("per_layer", n) for n in PER_LAYER])
def test_metric_file_states_what_the_manifest_states(kind, name):
    entry = [m for m in MANIFEST[kind] if m["name"] == name][0]
    mod = manifest_mod.Manifest().metric_reader(name)
    assert mod.NAME == name
    assert mod.UNIT == entry["unit"]
    assert mod.SOURCE == entry["source"]
    if kind == "per_layer":
        assert mod.LAYER == entry["layer"]
        assert mod.MOVES == entry["moves"]
    assert callable(mod.read)


# ----------------------------------------------------------- the yardstick


def test_snapshot_updates_follow_the_solvers_cadence():
    # w=0, after updates 1, 11, 21 (printer_freq 10), then the final model
    assert target.snapshot_updates(5, 10, 27) == [0, 1, 11, 21, 27]
    # synchronous mode counts rounds of num_workers gradients
    assert target.snapshot_updates(4, 2, 40, per_snapshot=8) == [0, 8, 24, 40]


def test_updates_to_target_interpolates_log_linearly():
    ks, fs = [0, 1, 11, 21], [1.0, 0.5, 0.01, 0.0001]
    # 0.001 is half way between 0.01 and 0.0001 in the logarithm
    assert target.updates_to_target(ks, fs, 0.001) == pytest.approx(16.0)
    assert target.updates_to_target(ks, fs, 0.01) == pytest.approx(11.0)
    assert target.updates_to_target(ks, fs, 1e-9) is None
    assert target.time_to_target_s(16.0, 200, 4.0) == pytest.approx(0.32)


def test_peaks_table_refuses_an_unknown_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_step_bytes_count_each_sampled_row_once():
    # mnist8m bf16 shard: 101,250 sampled rows of 1,568 bytes dominate
    b = roofline.dense_step_bytes(1_012_500, 784, 2, 0.1)
    assert b == pytest.approx(101_250 * 784 * 2 + 1_012_500 + 101_250 * 4 + 2 * 784 * 4)
    # rcv1: 4,360 sampled rows of 80 slots, cols+vals 8 bytes a slot; w and g
    # are touched at no more than d entries each
    s = roofline.sparse_step_bytes(87_206, 80, 47_236, 0.05, 4, 4)
    assert s == pytest.approx(4360.3 * 80 * 8 + 87_206 + 4360.3 * 4 + 2 * 47_236 * 4)
    data = {"kind": "dense", "shard_rows": [10, 12], "d": 4, "itemsize": 4}
    assert roofline.step_bytes(data, 0.5) == roofline.dense_step_bytes(12, 4, 4, 0.5)


def test_sparse_step_bytes_charge_a_slot_what_the_shard_stores():
    """f32 values and int32 columns are the 8 bytes a slot the count had as
    a literal until PR 29; a shard that stores bf16 values is charged 6, so
    that its share of the roofline cannot be counted too high."""
    rows, width, d, rate = 525_000, 128, 1_000_000, 0.05
    sampled = rate * rows
    rest = rows + sampled * 4 + 2 * d * 4
    f32 = roofline.sparse_step_bytes(rows, width, d, rate, 4, 4)
    assert f32 == sampled * width * 8 + rest
    bf16 = roofline.sparse_step_bytes(rows, width, d, rate, 2, 4)
    assert bf16 == sampled * width * 6 + rest
    data = {"kind": "sparse", "shard_rows": [rows - 1, rows], "d": d,
            "width": width, "itemsize": 2, "index_itemsize": 4}
    assert roofline.step_bytes(data, rate) == bf16


# ------------------------------------------- the configuration's generator


def _tiny_config(name):
    with open(os.path.join(ROOT, "tests", "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _shard_bytes(ds):
    names = ("cols", "vals", "y") if hasattr(ds.shard(0), "cols") else ("X", "y")
    return [np.asarray(getattr(ds.shard(w), n)).tobytes()
            for w in range(ds.num_workers) for n in names]


@pytest.mark.parametrize("name", ["tiny-dense-f32", "tiny-dense-bf16",
                                  "tiny-sparse"])
def test_a_configuration_without_a_generator_key_builds_todays_arrays(name):
    """Without ``generator`` the call is the one the harness made before the
    key existed, letter for letter, and the arrays are the same bytes for
    the same seed.  (A configuration WITH the mapping is held to the next
    test instead.)"""
    import jax
    import jax.numpy as jnp

    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.data.sparse import SparseShardedDataset

    config = _tiny_config(name)
    assert "generator" not in config
    devs = jax.devices()[:1]
    got = run.build_dataset(config, 4, devs, seed=2_147_483_659)
    if config["kind"] == "dense":
        want = ShardedDataset.generate_on_device(
            config["n"], config["d"], 4, devs, seed=2_147_483_659,
            noise=config["noise"], dtype=jnp.dtype(config["storage_dtype"]),
        )
    else:
        want = SparseShardedDataset.generate_on_device(
            config["n"], config["d"], config["nnz_per_row"], 4, devs,
            seed=2_147_483_659, noise=config["noise"],
        )
    assert _shard_bytes(got) == _shard_bytes(want)
    data = run.describe_data(got, config)
    assert data["itemsize"] == jnp.dtype(config["storage_dtype"]).itemsize
    if config["kind"] == "sparse":
        assert (data["index_itemsize"], data["width"]) == (4, 16)
    else:
        assert "index_itemsize" not in data


def _generator_takes_the_configurations_call(config):
    """Every key of the ``generator`` mapping is a keyword parameter of the
    program's generator for the configuration's ``kind``, and none is an
    argument the harness passes itself: the call ``build_dataset`` would
    make binds to the generator's signature.  Nothing is built, so an 11 GB
    deployment is held to it on the CPU."""
    import inspect

    generate, args, kwargs = run.generator_call(config, 8, None, seed=1)
    sig = inspect.signature(generate)
    for key in config.get("generator", {}):
        # by name: a generator that swallows ``**kwargs`` takes none of them
        assert key in sig.parameters, f"the generator takes no {key!r}"
    sig.bind(*args, **kwargs)
    return sorted(config.get("generator", {}))


@pytest.mark.parametrize("name", [c["name"] for c in MANIFEST["configs"]])
def test_a_configurations_generator_keys_are_the_generators_parameters(name):
    _generator_takes_the_configurations_call(
        manifest_mod.Manifest().config(name)
    )


@pytest.mark.parametrize("name", ["tiny-dense-f32", "tiny-sparse"])
def test_every_generator_key_reaches_the_programs_generator(name, monkeypatch):
    """A recording stand-in for the program's generator: the mapping's keys
    arrive as keyword arguments beside the ones passed without it, and the
    harness knows none of their names."""
    import jax

    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.data.sparse import SparseShardedDataset

    cls = SparseShardedDataset if name == "tiny-sparse" else ShardedDataset
    real = cls.generate_on_device
    calls = []

    def recording(*args, value_dtype="float32", column_skew=0.0, **kwargs):
        calls.append({"value_dtype": value_dtype, "column_skew": column_skew,
                      **kwargs})
        return real(*args, **kwargs)

    monkeypatch.setattr(cls, "generate_on_device", recording)
    config = _tiny_config(name)
    plain = run.build_dataset(config, 4, jax.devices()[:1], seed=7)
    config["generator"] = {"value_dtype": "bfloat16", "column_skew": 1.1}
    told = run.build_dataset(config, 4, jax.devices()[:1], seed=7)
    assert calls[0]["value_dtype"] == "float32" and calls[0]["column_skew"] == 0.0
    assert calls[1]["value_dtype"] == "bfloat16" and calls[1]["column_skew"] == 1.1
    # everything else went as it goes without the mapping
    drop = lambda c: {k: v for k, v in c.items()  # noqa: E731
                      if k not in ("value_dtype", "column_skew")}
    assert drop(calls[0]) == drop(calls[1])
    assert _shard_bytes(plain) == _shard_bytes(told)


@pytest.mark.parametrize("name", ["tiny-dense-f32", "tiny-sparse"])
def test_a_generator_key_the_program_does_not_take_fails_loudly(name):
    import jax

    config = _tiny_config(name)
    config["generator"] = {"no_such_argument": 1}
    with pytest.raises(TypeError, match="no_such_argument"):
        run.build_dataset(config, 4, jax.devices()[:1], seed=7)


# ---------------------------- a configuration of another source, as data only

#: what a later PR would add for a deployment of another source: the LIBSVM
#: criteo shape (sizes from memory, as ISSUE 30 has them), its scale cut to
#: six eighths, and a ``generator`` mapping (the keys are the stand-in's below:
#: no test here may say what the program's generator takes or lacks)
OTHER_SOURCE = {
    "name": "other-sparse",
    "source": "rehearsal: a public sparse set of another shape",
    "kind": "sparse",
    "n": 34_380_463, "d": 1_000_000, "nnz_per_row": 39,
    "published": {"n": 45_840_617, "d": 1_000_000, "nnz_per_row": 39},
    "storage_dtype": "bfloat16", "noise": 0.01,
    "generator": {"value_dtype": "bfloat16", "labels": "zero_one"},
    "solver": "asgd", "loss": "logistic", "num_workers": 8,
    "batch_rate": 0.05, "bucket_ratio": 0.7, "target_fraction": 0.5,
    "gamma": 1.0, "printer_freq": 10,
    "pins": {},
    "reduced": ["n", "storage_dtype"],
    "deployment": "34,380,463 of the 45,840,617 rows (six eighths): a whole "
                  "run has to fit the budget of a run",
    "assumed": {"gamma": "-", "data": "-", "noise": "-"},
}


def _scratch_manifest(root, config):
    """A manifest with one more configuration and a cell on it, added as
    files and entries under a scratch root: no file of the tree is edited."""
    import shutil

    doc = json.loads(json.dumps(MANIFEST))
    rel = f"benchmark/configs/{config['name']}.json"
    os.makedirs(root / "benchmark" / "configs")
    (root / rel).write_text(json.dumps(config))
    shutil.copytree(os.path.join(ROOT, "benchmark", "traffic"),
                    root / "benchmark" / "traffic")
    doc["configs"].append({
        "name": config["name"], "source": config["source"], "file": rel,
        "reduced": config["reduced"], "why": "rehearsal",
    })
    doc["workloads"].append({
        "name": config["name"] + ".steady", "config": config["name"],
        "traffic": "steady", "chips": 1, "why": "rehearsal",
    })
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return manifest_mod.Manifest(str(path), root=str(root))


def test_a_configuration_of_another_source_is_added_as_data(
        tmp_path, monkeypatch):
    """Another published shape, a cut ``n`` that is listed and accounted
    for, and a ``generator`` mapping: the cell resolves to a plan and passes
    what every cell and configuration of the tree is held to."""
    from asyncframework_tpu.data.sparse import SparseShardedDataset

    man = _scratch_manifest(tmp_path, OTHER_SOURCE)
    _cell_resolves_to_a_plan_from_its_files(man, "other-sparse.steady")
    config = man.config("other-sparse")
    calls = []

    def recording(cls, n, d, nnz_per_row, num_workers, devices=None, seed=42,
                  noise=0.01, value_dtype="float32", labels="planted"):
        calls.append((n, d, nnz_per_row, num_workers, seed, noise,
                      value_dtype, labels))

    monkeypatch.setattr(SparseShardedDataset, "generate_on_device",
                        classmethod(recording))
    assert _generator_takes_the_configurations_call(config) == [
        "labels", "value_dtype"]
    assert calls == []  # bound, not built
    generate, args, kwargs = run.generator_call(config, 8, None, seed=3)
    generate(*args, **kwargs)
    assert calls == [(34_380_463, 1_000_000, 39, 8, 3, 0.01, "bfloat16",
                      "zero_one")]
    # a key the generator does not take, and one the harness passes itself
    config["generator"] = {"no_such_argument": 1}
    with pytest.raises(AssertionError, match="no_such_argument"):
        _generator_takes_the_configurations_call(config)
    config["generator"] = {"seed": 7}
    with pytest.raises(TypeError, match="seed"):
        _generator_takes_the_configurations_call(config)


@pytest.mark.parametrize("fault,change", [
    ("d is a width", {"d": 500_000}),
    ("non-zeros a row", {"nnz_per_row": 32}),
    ("not listed", {"reduced": ["storage_dtype"]}),
    ("by how much", {"deployment": "six eighths of the rows"}),
    ("over the published", {"n": 50_000_000,
                "deployment": "50,000,000 rows where 45,840,617 are published"}),
    ("published", {"published": None}),
])
def test_a_configuration_that_cuts_its_sources_shape_is_refused(
        tmp_path, fault, change):
    config = {**OTHER_SOURCE, **change}
    if config["published"] is None:
        del config["published"]  # nothing in the test stands for a source
    man = _scratch_manifest(tmp_path, config)
    with pytest.raises((AssertionError, KeyError), match=fault):
        _cell_resolves_to_a_plan_from_its_files(man, "other-sparse.steady")


def test_the_published_key_reaches_no_run():
    """``published`` is for the test above: the plan does not carry it and
    the generator's call is the one made without it."""
    man = manifest_mod.Manifest()
    for cell in MANIFEST["workloads"]:
        config = man.config(cell["config"])
        bare = {k: v for k, v in config.items() if k != "published"}
        mix = man.traffic(cell["traffic"])
        assert plan_mod.resolve(config, mix) == plan_mod.resolve(bare, mix)
        told, plain = (run.generator_call(c, 8, None, seed=5)
                       for c in (config, bare))
        assert told[1:] == plain[1:]


# ------------------------------------------------------------ the rehearsal

TINY_CELLS = {
    # name: (config, traffic, chips)
    "tiny-dense-f32.steady": ("tiny-dense-f32", "steady", 1),
    "tiny-dense-bf16.steady": ("tiny-dense-bf16", "steady", 1),
    "tiny-sparse.steady": ("tiny-sparse", "steady", 1),
    "tiny-asaga.steady": ("tiny-asaga", "steady", 1),
    "tiny-dense-f32.tiny-sync": ("tiny-dense-f32", "tiny-sync", 1),
    "tiny-dense-f32.four": ("tiny-dense-f32", "steady", 4),
}


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The real manifest's metrics over tiny configurations: new cells are
    new entries and new files (``tests/benchmark/configs``, ``traffic``)
    and no edit to the harness."""
    doc = dict(MANIFEST)
    configs = sorted({c for c, _t, _n in TINY_CELLS.values()})
    doc["configs"] = [
        {"name": c, "source": "rehearsal", "reduced": [], "why": "rehearsal",
         "file": f"tests/benchmark/configs/{c}.json"} for c in configs
    ]
    doc["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "rehearsal"}
        for n, (c, t, k) in TINY_CELLS.items()
    ]
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


@pytest.fixture()
def on_cpu(monkeypatch, tmp_path):
    """Relax the platform check for the rehearsal and keep the profiler's
    files out of the tree."""
    import jax

    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(run, "TRACE_RUN_S", 1.0)
    monkeypatch.setattr(run, "TRACE_EDGE_S", 0.2)

    def use(chips):
        monkeypatch.setattr(run, "_devices", lambda: jax.devices()[:chips])

    return use


def _run(capsys, manifest, cell, trace=0, seconds=1.5, seed=5):
    rc = run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)], manifest_path=manifest,
    )
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return rc, lines


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_rehearsal_cell_prints_the_contracts_last_line(
        cell, tiny_manifest, on_cpu, capsys):
    chips = TINY_CELLS[cell][2]
    on_cpu(chips)
    rc, lines = _run(capsys, tiny_manifest, cell)
    assert rc == 0
    last = json.loads(lines[-1])
    assert set(last) == RESULT_KEYS
    assert set(last["device"]) == DEVICE_KEYS
    assert last["device"]["count"] == chips
    assert set(last["metrics"]) == set(E2E)
    for name, m in last["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    assert last["correct"] is True, lines[-2]
    assert last["failed"] == 0 and last["attempted"] > 0
    # each number compared, beside its limit, as the line's last key
    assert list(last)[-1] == "compared"
    for name, c in last["compared"].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"], name
    assert ("history_within" in last["compared"]) == (cell == "tiny-asaga.steady")
    assert ("nnz_per_row" in last["compared"]) == (cell == "tiny-sparse.steady")
    # everything before the last line is an info line
    assert all(set(json.loads(ln)) == {"info"} for ln in lines[:-1])
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    assert record["checks"]["no_compile_in_window"]
    if chips == 4:
        per_dev = record["memory_peak_by_chip"]
        assert len(per_dev) == 4


@pytest.mark.parametrize("cell", ["tiny-dense-f32.steady", "tiny-dense-f32.four"])
def test_traced_rehearsal_reports_per_layer_metrics(
        cell, tiny_manifest, on_cpu, capsys):
    on_cpu(TINY_CELLS[cell][2])
    rc, lines = _run(capsys, tiny_manifest, cell, trace=1)
    assert rc == 0
    last = json.loads(lines[-1])
    assert RESULT_KEYS <= set(last) <= RESULT_KEYS | {"breakdown"}
    # on the CPU there is no device plane: readers of the device trace find
    # nothing and are left out; the program's spans and counters are read
    got = set(last["metrics"])
    assert got <= set(PER_LAYER)
    assert {"data_gen_s", "warmup_s", "task_p50_ms", "merge_queue_p50_ms",
            "staleness_mean", "updates_to_target"} <= got
    assert not got & {"step_device_ms", "step_roofline", "device_idle"}
    assert "busy_s" not in last["device"]
    # the profiler ran around a short run of its own, after the checked one
    infos = [json.loads(ln)["info"] for ln in lines[:-1]]
    prof = [i for i in infos if "profiled_run" in i][0]
    assert prof["profiled_run"]["accepted"] > 0
    assert prof["profiled_run"]["compiles"] == 0
    lo, hi = prof["window"]
    assert 0.2 <= lo < hi <= prof["profiled_run"]["elapsed_s"] + 1.0
    record = [i for i in infos if "checks" in i][0]
    assert all(record["checks"].values()), record["checks"]
    assert last["correct"] is True
    assert list(last)[-1] == "compared"


def test_a_run_says_how_long_it_took_against_the_budget(
        tiny_manifest, on_cpu, capsys, monkeypatch):
    """``wall_s`` (process start to the result line) on the last info line,
    beside the ``seconds + RUN_OVERHEAD_S`` a full check reckons a run at;
    over it the run says so on stderr, before the compared numbers, and is
    reported as any other."""
    on_cpu(1)
    monkeypatch.setattr(run, "T0", run.time.monotonic())
    rc = run.main(["--workload", "tiny-sparse.steady", "--seed", "11",
                   "--seconds", "1.5"], manifest_path=tiny_manifest)
    io = capsys.readouterr()
    lines = [json.loads(ln) for ln in io.out.splitlines() if ln.strip()]
    assert rc == 0 and lines[-1]["correct"] is True
    said = lines[-2]["info"]
    assert set(said) == {"wall_s", "budget_s", "trajectory_eval_s"}
    assert said["budget_s"] == 1.5 + run.RUN_OVERHEAD_S
    record = [ln["info"] for ln in lines[:-1] if "checks" in ln["info"]][0]
    spans, elapsed = record["spans"], record["result"]["elapsed_s"]
    assert said["wall_s"] >= spans["setup_s"] + elapsed + spans["post_s"]
    assert 0 <= said["trajectory_eval_s"] == spans["trajectory_eval_s"]
    assert said["trajectory_eval_s"] == pytest.approx(
        record["run_wall_s"] - elapsed)
    assert said["wall_s"] < said["budget_s"] and "took" not in io.err


def test_a_run_over_the_budget_says_so_and_is_reported_as_before(
        tiny_manifest, on_cpu, capsys, monkeypatch):
    on_cpu(1)
    monkeypatch.setattr(run, "RUN_OVERHEAD_S", 0)
    monkeypatch.setattr(run, "T0", run.time.monotonic())
    rc = run.main(["--workload", "tiny-dense-f32.steady", "--seed", "12",
                   "--seconds", "1.5"], manifest_path=tiny_manifest)
    io = capsys.readouterr()
    last = json.loads(io.out.splitlines()[-1])
    assert rc == 0 and last["correct"] is True and set(last) == RESULT_KEYS
    err = io.err.splitlines()
    over = [i for i, ln in enumerate(err) if "over the 1.5 s" in ln]
    assert len(over) == 1 and "RUN_OVERHEAD_S" in err[over[0]]
    # the compared numbers stay the last lines of stderr
    assert all(ln.startswith("compared ") for ln in err[over[0] + 1:])
    assert len(err) - over[0] - 1 == len(last["compared"])


def test_a_host_that_holds_every_thread_costs_the_run_no_worker(
        tiny_manifest, on_cpu, capsys, monkeypatch):
    """The program's heartbeat monitor declares an idle executor lost after
    2 s of silence and is itself a Python thread, so a host that holds every
    Python thread that long inside a run makes the run report workers lost
    whenever the monitor wakes before the executors do: the driver's first
    check of PR 22 met it on the chip.  Here the monitor's clock runs 3 s
    ahead of the executors', which is that hold as the monitor sees it: at
    the program's 2 s every idle executor is lost at the first scan.  The
    cells run with the reference's 120 s (``plan.DEFAULTS``); a mix that
    exercises failure detection brings its own value."""
    import asyncframework_tpu.engine.heartbeat as hb

    class Late(hb.SystemClock):
        def now_ms(self):
            return super().now_ms() + 3000.0

    real_init = hb.HeartbeatMonitor.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        self._clock = Late()

    monkeypatch.setattr(hb.HeartbeatMonitor, "__init__", init)
    on_cpu(1)
    rc, lines = _run(capsys, tiny_manifest, "tiny-dense-f32.steady")
    assert rc == 0
    last = json.loads(lines[-1])
    record = [json.loads(ln)["info"] for ln in lines[:-1]
              if "checks" in json.loads(ln)["info"]][0]
    assert "workers_lost" not in record["result"]["extras"]
    assert last["correct"] is True and last["failed"] == 0

    config = {"name": "c", "solver": "asgd", "num_workers": 8,
              "batch_rate": 0.1, "bucket_ratio": 0.7, "gamma": 1.0,
              "printer_freq": 10, "target_fraction": 0.001}
    assert plan_mod.resolve(config, {"name": "m"})[
        "heartbeat_timeout_ms"] == 120_000.0
    tight = plan_mod.resolve(config, {"name": "m", "heartbeat_timeout_ms": 2000})
    kw = plan_mod.solver_config_kwargs(tight, seed=1, seconds=5, trace=False)
    assert kw["heartbeat_timeout_ms"] == 2000.0


def test_traced_rehearsal_of_the_barrier_sizes_its_profiled_run(
        tiny_manifest, on_cpu, capsys):
    on_cpu(1)
    rc, lines = _run(capsys, tiny_manifest, "tiny-dense-f32.tiny-sync", trace=1)
    assert rc == 0
    infos = [json.loads(ln)["info"] for ln in lines[:-1]]
    prof = [i for i in infos if "profiled_run" in i][0]["profiled_run"]
    main = [i for i in infos if "checks" in i][0]["result"]
    # run_sync has no deadline: both round counts come from the warm-up's rate
    assert 0 < prof["accepted"] < main["accepted"]
    assert json.loads(lines[-1])["correct"] is True


def test_wrong_platform_or_chip_count_exits_nonzero_with_no_result(
        tiny_manifest, monkeypatch, capsys):
    import jax

    # the check as the driver meets it: a CPU where a TPU is asked for
    monkeypatch.setattr(run, "_devices", lambda: jax.devices()[:1])
    rc, lines = _run(capsys, tiny_manifest, "tiny-dense-f32.steady")
    assert rc != 0 and lines == []
    # the right platform with too few devices
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    rc, lines = _run(capsys, tiny_manifest, "tiny-dense-f32.four")
    assert rc != 0 and lines == []


def test_benchmark_alone_without_the_program_exits_nonzero(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no system under test: no result, non-zero exit."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in MANIFEST["paths"]:
        shutil.copytree(
            os.path.join(ROOT, p), tmp_path / p,
            ignore=shutil.ignore_patterns("__pycache__", "fixtures"),
        )
    cell = MANIFEST["workloads"][0]["name"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
