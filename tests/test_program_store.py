"""The store of the padded-ELL worker steps' executables (ISSUE 57).

``ops/program_store.LoadedByShape`` stands where such a step is called: a
table of executables by the SHAPE of a call; on a miss a key computed
WITHOUT tracing, the executable stored under it loaded, or the jitted
function lowered, compiled, serialized and written.  Here: what is built
and loaded is the same program to the bit; everything that decides the
executable is a miss when it changes (an operand's shape or dtype, the
draw's rate, one byte of the source); a file cut short builds and says
so; a solver's shard shapes are an entry each; a dense solver has no
such counter; and whatever is not a committed device array, or lives on
a backend that does not serialize whole, stays on ``jit``.

Every test here takes the ``step_store`` fixture (``tests/conftest.py``):
an empty directory of its own, off the persistent compile cache, with the
CPU let in.
"""

import os
import shutil
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncframework_tpu.data.sharded import ShardedDataset
from asyncframework_tpu.data.sparse import SparseShardedDataset
from asyncframework_tpu.ops import program_store, steps
from asyncframework_tpu.solvers import ASAGA, ASGD, SolverConfig
from asyncframework_tpu.utils import devices

D, ROWS, K = 64, 128, 8


def _operands(rows=ROWS, k=K, vals_dtype=np.float32, dev=None, seed=0):
    dev = dev or jax.devices()[0]
    rs = np.random.default_rng(seed)
    host = (rs.integers(0, D, (rows, k)).astype(np.int32),
            rs.normal(size=(rows, k)).astype(vals_dtype),
            rs.integers(0, 2, rows).astype(vals_dtype),
            rs.normal(size=D).astype(np.float32),
            np.asarray(jax.random.PRNGKey(1)))
    return tuple(jax.device_put(a, dev) for a in host)


def _asgd(batch_rate=0.25, loss="logistic", live_width=None):
    return steps.make_sparse_asgd_worker_step(batch_rate, D, loss,
                                              live_width=live_width)


def _entries(root):
    return sorted(os.listdir(root)) if os.path.isdir(root) else []


@pytest.mark.parametrize("factory", ["asgd", "saga"])
def test_a_step_built_stored_and_loaded_is_the_same_to_the_bit(
        step_store, factory):
    """The first object builds and stores, the second loads; ``g``, the
    key chain and every other output are bit for bit what ``jit`` gives;
    a second call of a shape goes to the table."""
    args = _operands()
    if factory == "saga":
        make = lambda: steps.make_sparse_saga_worker_step(0.25, D)  # noqa: E731
        args = (*args[:4], jnp.zeros(ROWS, jnp.float32).at[::3].set(0.5), args[4])
        args = tuple(jax.device_put(a, jax.devices()[0]) for a in args)
    else:
        make = _asgd
    first = make()
    built = first(*args)
    assert first.counts() == {"loaded": 0, "built": 1, "failed": 0}
    (entry,) = _entries(step_store)
    assert entry.startswith(f"sparse_{factory}_worker_step-")
    assert not entry.endswith("-cache")  # the compile cache counts those
    second = make()
    loaded = second(*args)
    assert second.counts() == {"loaded": 1, "built": 0, "failed": 0}
    traced = second._jitted(*args)
    for b, l, t in zip(built, loaded, traced):
        assert np.array_equal(np.asarray(b), np.asarray(l))
        assert np.array_equal(np.asarray(l), np.asarray(t))
    second(*args)
    assert second.counts() == {"loaded": 1, "built": 0, "failed": 0}
    assert _entries(step_store) == [entry]
    # the module keeps its name: what a device trace finds the step by
    (program,) = second._programs.values()
    assert program.runtime_executable().hlo_modules()[0].name == "jit_step"


@pytest.mark.parametrize("what", [
    "rows", "width", "values-dtype", "batch-rate", "loss", "live-width",
    "device", "x64"])
def test_what_decides_the_executable_is_a_miss_when_it_changes(
        step_store, what):
    """A changed operand shape or dtype, rate, loss, width read, device or
    precision mode is another key: the step is BUILT, and stored beside
    the first."""
    args = _operands()
    _asgd()(*args)
    assert len(_entries(step_store)) == 1
    make, other = _asgd, args
    if what == "rows":
        other = _operands(rows=ROWS + 8)
    elif what == "width":
        other = _operands(k=K + 8)
    elif what == "values-dtype":
        other = _operands(vals_dtype=jnp.bfloat16)
    elif what == "batch-rate":
        make = lambda: _asgd(batch_rate=0.5)  # noqa: E731
    elif what == "loss":
        make = lambda: _asgd(loss="least_squares")  # noqa: E731
    elif what == "live-width":
        make = lambda: _asgd(live_width={K: K - 2})  # noqa: E731
    elif what == "device":
        other = _operands(dev=jax.devices()[1])
    step = make()
    if what == "x64":
        with jax.enable_x64(True):
            step(*other)
    else:
        step(*other)
    assert step.counts() == {"loaded": 0, "built": 1, "failed": 0}
    assert len(_entries(step_store)) == 2


def test_one_byte_of_a_digested_source_file_is_a_miss(
        step_store, monkeypatch, tmp_path):
    """The key holds a digest of the source the step is traced from, by
    CONTENT: a copy of the files elsewhere reads the same digest (a cold
    checkout does not reach it), one changed byte in one of them another,
    and the step stored under the old source is not loaded."""
    real = program_store.source_files()
    names = [os.path.basename(p) for p in real]
    assert {"steps.py", "gradients.py", "pallas_kernels.py",
            "program_store.py", "sparse.py"} <= set(names)
    copies = []
    for path in real:
        copies.append(str(tmp_path / os.path.basename(path)))
        shutil.copy(path, copies[-1])
    digest = program_store.source_digest()
    monkeypatch.setattr(program_store, "source_files", lambda: tuple(copies))
    assert program_store.source_digest() == digest
    args = _operands()
    _asgd()(*args)
    loads = _asgd()
    loads(*args)
    assert loads.counts()["loaded"] == 1
    with open(copies[names.index("gradients.py")], "r+b") as f:
        f.seek(100)
        byte = f.read(1)
        f.seek(100)
        f.write(b"#" if byte != b"#" else b"!")
    assert program_store.source_digest() != digest
    saga = steps.make_sparse_saga_worker_step(0.25, D)
    saga(*args[:4], jax.device_put(jnp.zeros(ROWS), jax.devices()[0]),
         args[4])
    (old,) = [e for e in _entries(step_store) if "asgd" in e]
    assert old.split("-")[1] == digest[:16]
    edited = _asgd()
    edited(*args)
    assert edited.counts() == {"loaded": 0, "built": 1, "failed": 0}
    # the entry under the old source left with the first one stored under
    # the new: the store holds ONE source's programs a factory (another
    # factory's stay until it stores one itself)
    (new,) = [e for e in _entries(step_store) if "asgd" in e]
    assert new != old and new.split("-")[1] == (
        program_store.source_digest()[:16])
    assert len(_entries(step_store)) == 2


@pytest.mark.parametrize("damage", ["truncated", "empty", "not-ours"])
def test_a_stored_file_that_does_not_load_counts_failed_and_builds(
        step_store, damage, caplog):
    args = _operands()
    g, _ = _asgd()(*args)
    (entry,) = _entries(step_store)
    path = os.path.join(step_store, entry)
    whole = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write({"truncated": whole[:len(whole) // 2], "empty": b"",
                 "not-ours": b"\x00" * 64}[damage])
    step = _asgd()
    with caplog.at_level("WARNING"):
        g2, _ = step(*args)
    assert step.counts() == {"loaded": 0, "built": 1, "failed": 1}
    assert "did not load" in caplog.text
    assert np.array_equal(np.asarray(g), np.asarray(g2))
    # the build wrote the entry anew, whole: the next object loads it
    after = _asgd()
    after(*args)
    assert after.counts() == {"loaded": 1, "built": 0, "failed": 0}


@pytest.mark.parametrize("operands", ["host", "uncommitted", "traced"])
def test_operands_that_do_not_say_where_they_run_stay_on_jit(
        step_store, operands):
    """Only committed device arrays name the executable's device without
    asking ``jit``: a host array, an array that follows the default
    device and a tracer go to the jitted function, and nothing is stored."""
    args = _operands()
    want, _ = _asgd()._jitted(*args)
    step = _asgd()
    if operands == "host":
        g, _ = step(*[np.asarray(a) for a in args])
    elif operands == "uncommitted":
        g, _ = step(*[jnp.asarray(np.asarray(a)) for a in args])
    else:
        g, _ = jax.jit(lambda *a: step(*a))(*args)
    assert np.array_equal(np.asarray(g), np.asarray(want))
    assert step.counts() == {"loaded": 0, "built": 0, "failed": 0}
    assert _entries(step_store) == []


def test_the_cpu_stays_off_the_store_and_a_process_without_a_cache_has_none(
        step_store, monkeypatch):
    """XLA:CPU serializes an executable it loaded from the compile cache
    without its kernels, so ``serializes_whole`` says no for the CPU (the
    fixture lets it in, off that cache); and the store lies under the
    compile cache's directory, so a process that has none has no store."""
    monkeypatch.undo()  # the fixture's patches, and conftest's
    cpu = jax.devices()[0]
    assert cpu.platform == "cpu" and not program_store.serializes_whole(cpu)
    cache = jax.config.jax_compilation_cache_dir
    assert devices.step_store_dir() == os.path.join(cache, "step_programs")
    monkeypatch.setattr(devices, "step_store_dir", lambda: str(step_store))
    step = _asgd()
    step(*_operands())
    assert step.counts() == {"loaded": 0, "built": 0, "failed": 0}
    assert _entries(step_store) == []
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        monkeypatch.undo()
        assert devices.step_store_dir() is None
    finally:
        jax.config.update("jax_compilation_cache_dir", cache)


def test_a_backend_that_serializes_nothing_leaves_the_step_on_jit(
        step_store, monkeypatch, caplog):
    def refuse(compiled):
        raise ValueError("Compilation does not support serialization")

    monkeypatch.setattr(program_store.serialize_executable, "serialize",
                        refuse)
    args = _operands()
    step = _asgd()
    with caplog.at_level("WARNING"):
        g, _ = step(*args)
    assert "is not stored" in caplog.text
    want, _ = step._jitted(*args)
    assert np.array_equal(np.asarray(g), np.asarray(want))
    assert _entries(step_store) == []
    # the next shape does not try again: it is the jitted function itself
    step(*_operands(rows=ROWS + 8))
    assert step.counts() == {"loaded": 0, "built": 1, "failed": 0}
    assert step._programs[next(reversed(step._programs))] is step._jitted


def test_threads_that_miss_one_shape_at_once_build_it_once(step_store):
    """More threads than cores call a new shape together: one builds, the
    others wait at the object's lock and find the table filled."""
    args = _operands()
    want, _ = _asgd()._jitted(*args)
    step = _asgd()
    n = 4 * (os.cpu_count() or 4)
    out, start = [None] * n, threading.Barrier(n)

    def call(i):
        start.wait(timeout=30)
        out[i] = np.asarray(step(*args)[0])

    threads = [threading.Thread(target=call, args=(i,)) for i in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert step.counts() == {"loaded": 0, "built": 1, "failed": 0}
    assert len(_entries(step_store)) == 1
    for g in out:
        assert np.array_equal(g, np.asarray(want))


# ------------------------------------------------- through the solvers
LAW = {"law": "lognormal", "sigma": 0.6, "min": 8, "max": 384}


def _cfg(**kw):
    base = dict(num_workers=8, num_iterations=16, gamma=4.0, taw=2**31 - 1,
                batch_rate=0.1, bucket_ratio=0.7, printer_freq=8, seed=5,
                loss="logistic", run_timeout_s=60.0)
    base.update(kw)
    return SolverConfig(**base)


def _ragged():
    return SparseShardedDataset.generate_on_device(
        2_048, 4_007, 96, 8, jax.devices()[:1], seed=3, noise=0.0,
        column_skew=0.5, row_nnz=LAW,
        row_values={"law": "lognormal", "sigma": 0.5},
        bernoulli_labels={"scale": 3.0, "positive_share": 0.6})


def test_a_solvers_shard_shapes_are_an_entry_each_and_load_the_next_time(
        step_store):
    """ASGD over shards of unequal width: the first solver object builds
    one executable a shard SHAPE in its warm-up and reports them in every
    run's ``extras``; the next object over the same data loads them all,
    and reaches the first one's model (a round adds its gradients in
    the order they come back, so not to the bit)."""
    ds = _ragged()
    shapes = len({ds.shard(w).shape for w in range(8)})
    assert shapes >= 5
    first = ASGD(ds, None, _cfg(), devices=jax.devices()[:1])
    res = first.run_sync()
    assert res.extras["sparse_step_shapes"] == shapes
    assert (res.extras["step_programs_built"],
            res.extras["step_programs_loaded"],
            res.extras["step_programs_failed"]) == (shapes, 0, 0)
    assert len(_entries(step_store)) == shapes
    # the object's life, not the run's: a second run reports the same table
    again = first.run_sync()
    assert again.extras["step_programs_built"] == shapes
    second = ASGD(ds, None, _cfg(), devices=jax.devices()[:1])
    res2 = second.run_sync()
    assert (res2.extras["step_programs_built"],
            res2.extras["step_programs_loaded"],
            res2.extras["step_programs_failed"]) == (0, shapes, 0)
    np.testing.assert_allclose(res.final_w, res2.final_w, rtol=1e-4,
                               atol=1e-7)
    assert len(_entries(step_store)) == shapes


def test_asagas_padded_ell_step_is_stored_too(step_store):
    ds = SparseShardedDataset.generate_on_device(
        1_024, 512, 12, 8, jax.devices()[:1], seed=3, noise=0.0)
    cfg = _cfg(loss="least_squares", gamma=0.5, num_iterations=24)
    res = ASAGA(ds, None, cfg, devices=jax.devices()[:1]).run()
    assert res.extras["step_programs_built"] == 1
    (entry,) = _entries(step_store)
    assert entry.startswith("sparse_saga_worker_step-")
    res2 = ASAGA(ds, None, cfg, devices=jax.devices()[:1]).run()
    assert (res2.extras["step_programs_loaded"],
            res2.extras["step_programs_built"]) == (1, 0)


@pytest.mark.parametrize("solver", [ASGD, ASAGA])
def test_a_dense_solver_has_no_such_counter(step_store, solver):
    """The dense steps stay on ``jit`` (one shape a solver, and an updater
    that is busy 94 to 98% where there are 32 workers): no counter, no
    entry."""
    ds = ShardedDataset.generate_on_device(
        1_024, 32, 8, jax.devices()[:1], seed=3, noise=0.01)
    cfg = _cfg(loss="least_squares", gamma=0.5, batch_rate=0.1)
    res = solver(ds, None, cfg, devices=jax.devices()[:1]).run()
    assert not [k for k in res.extras if k.startswith("step_programs")]
    assert _entries(step_store) == []
