"""The dense worker steps read the shard where it lies -- checked on the
COMPILED program, not on a clock.

The TPU stores a dense ``(n, d)`` shard whose ``d * itemsize`` is not a
multiple of the 128-lane tile column-major (``{0,1}``: rows minor), so that
no row is padded (PERF.md section 3, "how the shard is stored").  A step
that gathers sampled rows makes XLA relay the whole shard to ``{1,0}`` first
-- a read and a write of all of it, every step -- and one that packs the
sampled row ids adds a serial scatter.  These tests compile the step for a
v5e with the TPU compiler that is installed here (no chip is needed, nothing
runs) and read the program: its shard parameter keeps the layout the device
gave it, nothing copies, gathers or scatters, and it needs no temporary worth
the name.  The same holds for every program that walks a whole shard on the
main path: the ASGD step, the ASAGA step, and ASAGA's table delta.  The
delta promotes a bf16 shard to f32 on purpose (``X.T @ v`` with an f32
vector, ``make_saga_table_delta``) and must do so inside the fusion that
reads it, not into a 3.2 GB copy.

Since PR 26 the two steps read the shard ONCE: on a TPU and a column-major
shard ``gradients.dense_step_path`` picks the Pallas kernel over ``X.T``,
which there is a ``bitcast``.  So the step's program must hold exactly one
instruction that takes the shard (or its ``bitcast``): a second reader is
the regression.  The choice asks ``gradients._on_tpu``, which sees the CPU
here, so the ``on_tpu`` fixture answers for it (and clears JAX's trace
caches around the test: the gradient sums are jitted at module level and
remember the program they traced for a shape).  At a lane-aligned width
(``d % 128 == 0``) the device stores the shard row-major and the choice
falls to the two XLA products: whichever runs there, nothing may copy or
transpose the shard.

All TPU compiles of the suite live in THIS file and describe the topology
inside a fixture: one process at a time may load libtpu, and a worker that
only collects the file must not.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from asyncframework_tpu.ops import gradients, steps

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<type>.*?) "
    r"(?P<op>[a-z][a-z0-9\-]*)\((?P<operands>[^)]*)\)"
)
_MOVES_DATA = {"copy", "transpose", "gather", "scatter", "dynamic-slice",
               "dynamic-update-slice", "sort"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    jax.clear_caches()
    monkeypatch.setattr(gradients, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _compile(one_chip, program, n, d, dtype, batch_rate=0.1):
    """``program`` of the main path, compiled for one described v5e chip
    over an ``(n, d)`` shard of ``dtype``."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    X, rows, vec = spec((n, d), dtype), spec((n,), jnp.float32), spec(
        (d,), jnp.float32)
    key = spec((2,), jnp.uint32)
    if program == "asgd-step":
        lowered = steps.make_asgd_worker_step(batch_rate).lower(
            X, rows, vec, key)
    elif program == "saga-step":
        lowered = steps.make_saga_worker_step(batch_rate).lower(
            X, rows, vec, rows, key)
    elif program == "saga-delta":
        lowered = steps.make_saga_table_delta().lower(X, rows, rows, rows)
    else:
        raise ValueError(program)
    return lowered.compile()


def _instructions(hlo_text):
    """(name, result type with layout, opcode, operand names) of every
    instruction of every computation (fused bodies included)."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            operands = re.findall(r"%([\w.\-]+)", m.group("operands"))
            out.append((m.group("name"), m.group("type"), m.group("op"),
                        operands))
    return out


@pytest.mark.parametrize(
    "program,n,d,dtype",
    [
        ("asgd-step", 40000, 784, jnp.bfloat16),  # the mnist8m cells' rows
        ("asgd-step", 40000, 784, jnp.float32),   # the four-chip cell's
        ("asgd-step", 8192, 2000, jnp.float32),   # epsilon's
        ("saga-step", 40000, 784, jnp.bfloat16),  # mnist8m-asaga's
        ("saga-step", 40000, 784, jnp.float32),
        ("saga-step", 8192, 2000, jnp.float32),
        ("saga-delta", 40000, 784, jnp.bfloat16),
        ("saga-delta", 40000, 784, jnp.float32),
        ("asgd-step", 8192, 1024, jnp.float32),   # lane-aligned: row-major
    ],
    ids=["bf16-784", "f32-784", "f32-2000", "saga-step-bf16-784",
         "saga-step-f32-784", "saga-step-f32-2000", "saga-delta-bf16-784",
         "saga-delta-f32-784", "f32-1024-lane-aligned"],
)
def test_dense_step_reads_the_shard_in_its_stored_layout(
    one_chip, no_compile_cache, on_tpu, program, n, d, dtype
):
    column_major = d % 128 != 0
    path = gradients.dense_step_path(jax.ShapeDtypeStruct((n, d), dtype))
    assert path == ("onepass" if column_major else "two_products")
    compiled = _compile(one_chip, program, n, d, dtype)
    text = compiled.as_text()
    instrs = _instructions(text)
    prefix = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    shard, shard_t = f"{prefix}[{n},{d}]", f"{prefix}[{d},{n}]"

    entry = text[text.index("ENTRY"):]
    param0 = [
        t for name, t, op, _ in _instructions(entry)
        if op == "parameter" and t.startswith(shard)
    ]
    assert len(param0) == 1, entry[:2000]
    # the device's own layout for this shape: rows minor (column-major)
    # where d is no multiple of the 128-lane tile (784, 2000), row-major
    # where it is (1024): the predicate of gradients.dense_step_path
    layout = re.match(re.escape(shard) + r"\{([\d,]+)", param0[0]).group(1)
    assert layout == ("0,1" if column_major else "1,0"), (
        f"the compiler now stores {shard} as {{{layout}}}: the byte model "
        f"in gradients.py's docstring, dense_step_path's predicate and "
        f"PERF.md section 3 rest on d % 128; re-derive them"
    )

    whole = {}  # name -> type, of everything as large as the shard
    for name, t, _op, _ in instrs:
        if shard in t or shard_t in t:
            whole[name] = t
    for name, t in whole.items():
        for m in re.finditer(
            "(" + re.escape(shard) + "|" + re.escape(shard_t) + r")\{([\d,]+)",
            t,
        ):
            # X.T of a column-major shard is row-major, and of a
            # row-major one column-major: a bitcast either way
            want = layout if m.group(1) == shard else layout[::-1]
            assert m.group(2) == want, (
                f"%{name} holds the shard relaid: {t[:120]}"
            )
    for name, t, op, operands in instrs:
        if op in _MOVES_DATA:
            touched = [o for o in operands if o in whole]
            assert name not in whole and not touched, (
                f"%{name} = {op}(...) moves the whole shard "
                f"({t[:100]}, operands {touched})"
            )
    # the step has no reason to pack row ids or to pick rows at all (the
    # compaction was a custom fusion: only its op_name said "scatter-add")
    assert not [i for i in instrs if i[2] in ("gather", "scatter")]
    assert "scatter-add" not in text and "/gather" not in text

    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1 << 20, f"{temp} bytes of temporaries"

    if program != "saga-delta" and column_major:
        # ONE read: the kernel, fed the shard's bitcast, and nothing else
        entry_instrs = _instructions(entry)
        held = {name for name, t, _op, _ in entry_instrs
                if shard in t or shard_t in t}
        readers = [(name, op) for name, _t, op, operands in entry_instrs
                   if op != "bitcast" and held & set(operands)]
        assert len(readers) == 1 and readers[0][1] == "custom-call", (
            f"the step reads the shard {len(readers)} times: {readers}"
        )
        # at b = 0.1 every lane tile holds a sampled row: ASGD's step and
        # ASAGA's alike are the whole-shard kernel with the operands it
        # has had since PR 26 (X.T, w, y, mask[, alpha]), and the list's
        # row gathers are nowhere in the program (asserted above)
        assert re.match(r"dense_onepass(\.\d+)?$", readers[0][0]), readers
        assert "dense_onepass_tiles" not in text
        call = [i for i in entry_instrs if i[0] == readers[0][0]][0]
        assert len(call[3]) == (5 if program == "saga-step" else 4), call


@pytest.mark.parametrize(
    "program,n,d,dtype,batch_rate",
    [
        # mnist8m-asaga's rows; 40,000 are 312 lane tiles and 64 rows
        ("saga-step", 40000, 784, jnp.bfloat16, 0.01),
        ("saga-step", 39936, 784, jnp.float32, 0.01),   # whole tiles only
        ("asgd-step", 40000, 784, jnp.bfloat16, 0.01),  # no solver's name
    ],
    ids=["saga-step-bf16-784", "saga-step-f32-784-aligned", "asgd-step-bf16"],
)
def test_a_thin_draw_reads_the_shard_by_the_tile_where_it_lies(
    one_chip, no_compile_cache, on_tpu, program, n, d, dtype, batch_rate
):
    """At ``b`` 0.01 a quarter of a shard's 128-row lane tiles hold no
    sampled row, and ``gradients.dense_step_path`` picks the kernel over
    the list of the others (``pallas_kernels.dense_onepass_tiles``): the
    shard parameter keeps ``{0,1}``, the kernel is the ONE instruction that
    takes it (its ``bitcast``, twice where the last tile is ragged: the
    windows by DMA, the array's edge through the pipeline), nothing as
    large as the shard is copied, gathered or scattered, and what IS
    gathered (``y``, ``mask``, ``alpha`` in, ``diff`` out, by rows of 128
    lanes) is as large as a vector of the shard's rows, not as the shard."""
    path = gradients.dense_step_path(
        jax.ShapeDtypeStruct((n, d), dtype), batch_rate)
    assert path == "onepass_tiles"
    compiled = _compile(one_chip, program, n, d, dtype, batch_rate)
    text = compiled.as_text()
    instrs = _instructions(text)
    prefix = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    shard, shard_t = f"{prefix}[{n},{d}]", f"{prefix}[{d},{n}]"
    entry = text[text.index("ENTRY"):]
    entry_instrs = _instructions(entry)
    param0 = [t for _n, t, op, _ in entry_instrs
              if op == "parameter" and t.startswith(shard)]
    assert len(param0) == 1, entry[:2000]
    assert re.match(re.escape(shard) + r"\{0,1", param0[0]), param0

    whole = {name for name, t, _op, _ in instrs if shard in t or shard_t in t}
    for name, t, op, operands in instrs:
        if op in _MOVES_DATA or op in ("gather", "scatter"):
            assert name not in whole and not whole & set(operands), (
                f"%{name} = {op}(...) moves the whole shard ({t[:100]})")
    # no index is scattered anywhere; the gathers are row gathers of
    # (tiles, 128) f32 views of the row vectors, 4 bytes a shard row
    assert not [i for i in instrs if i[2] == "scatter"]
    assert "scatter-add" not in text
    tiles = -(-n // 128)
    for name, t, op, _ in instrs:
        if op == "gather":
            assert re.search(r"f32\[(%d|%d),128\]" % (
                tiles, -(-tiles // 32) * 32), t), (name, t[:120])
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1 << 20, f"{temp} bytes of temporaries"

    held = {name for name, t, _op, _ in entry_instrs
            if shard in t or shard_t in t}
    readers = [(name, op, operands) for name, _t, op, operands in entry_instrs
               if op != "bitcast" and held & set(operands)]
    assert len(readers) == 1 and readers[0][1] == "custom-call", (
        f"the step reads the shard {len(readers)} times: {readers}")
    assert readers[0][0].startswith("dense_onepass_tiles"), readers
    # the windows' operand, and with a ragged last tile its edge block
    assert sum(o in held for o in readers[0][2]) == (2 if n % 128 else 1)


def test_mesh_step_runs_the_kernel_on_each_device_rows(
    topo, no_compile_cache, on_tpu
):
    """``make_mesh_asgd_worker_step`` calls the same gradient sum per
    device under ``shard_map``: the kernel's outputs must vary over the
    mesh axes the shard varies over (its ``vma``), each device reads its
    own rows through a ``bitcast``, and one all-reduce folds the partial
    gradients.  (The CPU interpreter cannot run the kernel's loops under
    ``shard_map``'s varying-axes check, so this is checked where it
    matters: on the program compiled for four described chips.)"""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("dp",))
    n, d = 4 * 40000, 784

    def spec(shape, dt, p):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, p))

    text = steps.make_mesh_asgd_worker_step(0.1, mesh).lower(
        spec((n, d), jnp.bfloat16, P("dp")), spec((n,), jnp.float32, P("dp")),
        spec((n,), jnp.float32, P("dp")), spec((d,), jnp.float32, P()),
        spec((2,), jnp.uint32, P()),
    ).compile().as_text()
    entry = _instructions(text[text.index("ENTRY"):])
    local, local_t = f"bf16[{n // 4},{d}]", f"bf16[{d},{n // 4}]"
    held = {name for name, t, _op, _ in entry if local in t or local_t in t}
    readers = [(name, op) for name, _t, op, operands in entry
               if op != "bitcast" and held & set(operands)]
    assert [op for _, op in readers] == ["custom-call"], readers
    assert "dense_onepass" in readers[0][0]
    assert not [i for i in entry if i[2] in ("copy", "transpose")
                and held & set(i[3])]
    assert [i for i in entry if i[2].startswith("all-reduce")]


# ------------------------------------------------- padded ELL (PR 29 to PR 32)
# The criteo deployment's shard: 2,865,039 rows x 40 slots, f32 values and
# int32 columns, stored rows minor (``{0,1}``: 40 is no multiple of the
# 128-lane tile).  What walks all of it must read it where it lies: a
# row-major copy of ``cols`` and ``vals`` pads 40 lanes to 128 (1,697 B of
# temporaries a shard row, which ``peak_bytes_in_use`` does not count: it
# decided the ``n`` a chip could hold until PR 32).

ELL_ROWS, ELL_WIDTH, ELL_D = 2_865_039, 40, 1_000_000
#: what one block of ``gradients.sparse_margins`` may keep in HBM: its
#: gathered ``(slots, 8)`` rows padded to 128 lanes, 512 B a slot (168 MB)
TEMP_BOUND = 512 * gradients.SPARSE_GATHER_BLOCK_SLOTS + 32e6


def _ell_specs(one_chip):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (spec((ELL_ROWS, ELL_WIDTH), jnp.int32),
            spec((ELL_ROWS, ELL_WIDTH), jnp.float32),
            spec((ELL_ROWS,), jnp.float32)), spec


def _makes_a_whole_shard(text):
    """Instructions (fused bodies included) whose RESULT is as tall as the
    shard, at its stored width or a narrower one (its live width), other
    than the parameters and the loop's plumbing."""
    shard = re.compile(r"\[%d,\d+\]" % ELL_ROWS)
    return [
        (name, op) for name, t, op, _ in _instructions(text)
        if shard.search(t) and op not in ("parameter", "get-tuple-element",
                                          "tuple", "while", "bitcast")
    ]


@pytest.mark.parametrize("loss", ["least_squares", "logistic"])
def test_blocked_sparse_evaluation_reads_the_shard_where_it_lies(
    one_chip, no_compile_cache, loss
):
    """``make_sparse_trajectory_loss_eval`` at the cell's shard and eight
    snapshots: per block ONE gather for all eight, nothing as large as the
    shard is made, and the gathered block (65,536 x 40 x 8 x 4 B = 84 MB)
    fits the chip's VMEM: 11 MB of temporaries in HBM where the whole-shard
    gather a snapshot wanted 4.86 GB (at 262,144 rows a block the gathered
    block is 336 MB of them, and a call takes 0.58 s where this takes 0.39:
    PERF.md section 6, PR 32)."""
    (cols, vals, y), spec = _ell_specs(one_chip)
    ev = steps.make_sparse_trajectory_loss_eval(loss)
    assert ev.blocks(ELL_ROWS) == 44 and ev.snapshots_per_call == 8
    compiled = ev.lower(cols, vals, y, spec((8, ELL_D), jnp.float32)).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    stored = [t for _n, t, op, _ in _instructions(entry)
              if op == "parameter" and f"[{ELL_ROWS},{ELL_WIDTH}]" in t]
    assert len(stored) == 2
    for t in stored:  # rows minor, as the device stores a width-40 shard
        assert re.search(r"\[\d+,\d+\]\{0,1", t), t
    assert not _makes_a_whole_shard(text), _makes_a_whole_shard(text)
    gathers = [i for i in _instructions(text) if i[2] == "gather"]
    assert len(gathers) == 1, gathers
    # eight snapshots an index: (8, 40, 65536), rows minor
    assert "f32[8,40,65536]" in gathers[0][1], gathers[0][1]
    assert not [i for i in _instructions(text) if i[2] in ("scatter", "sort")]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64e6, f"{temp} bytes of temporaries"


@pytest.mark.parametrize("model", ["w", "none"])
def test_reference_padded_ell_sums_build_no_gradient(
    one_chip, no_compile_cache, model
):
    """The benchmark's reference after every run (``benchmark/reference.py:
    _ell_sums``, a block of 65,536 rows): the sums-only block holds one
    ``w[cols]`` gather and no scatter and no ``(d,)`` array; with no model
    (the pins' pass at ``w = 0``) no gather either and no temporaries."""
    from benchmark import reference

    (cols, vals, y), spec = _ell_specs(one_chip)
    w = spec((ELL_D,), jnp.float32) if model == "w" else None
    start = spec((), jnp.int32)
    compiled = reference._ell_sums.lower(
        cols, vals, y, w, start, block=reference.BLOCK_ROWS, loss="logistic"
    ).compile()
    text = compiled.as_text()
    instrs = _instructions(text)
    assert not _makes_a_whole_shard(text), _makes_a_whole_shard(text)
    assert not [i for i in instrs if i[2] in ("scatter", "sort")]
    assert f"f32[{ELL_D}]" not in "".join(
        t for _n, t, op, _ in instrs if op not in ("parameter", "copy-start",
                                                   "copy-done"))
    gathers = [i for i in instrs if i[2] == "gather"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    if model == "w":
        assert len(gathers) == 1
        assert temp < 64e6, f"{temp} bytes of temporaries"
    else:
        assert not gathers and "gather" not in text
        assert temp < 1 << 20, f"{temp} bytes of temporaries"


def _model_gathers(text):
    """``(result type, slice sizes)`` of every gather whose operand is as
    large as the model: ``f32[1000000]`` or a two-dimensional view of it."""
    out = []
    for ln in text.splitlines():
        m = re.search(r"= (\S+) gather\(", ln)
        if not m:
            continue
        operand = re.search(r"gather\(%([\w.\-]+)", ln).group(1)
        held = re.search(
            r"%" + re.escape(operand) + r" = f32\[([\d,]+)\]", text)
        if held and np.prod([int(x) for x in held.group(1).split(",")]) == ELL_D:
            out.append((m.group(1).split("{")[0],
                        re.search(r"slice_sizes=\{([\d,]+)\}", ln).group(1)))
    return out


def _table_stays_in_vmem(text):
    """The ``(8, d / 8)`` table the loop gathers from is made once, in
    memory space 1.  Put out to HBM (which the compiler does where a
    block's gathered rows fit VMEM in its place) the gather costs 9.3 ns a
    slot, more than the element-wise one it replaced (PERF.md section 6,
    PR 36)."""
    tables = [t for _n, t, op, _ in _instructions(text[text.index("ENTRY"):])
              if op == "copy" and t.startswith(f"f32[8,{ELL_D // 8}]")]
    assert len(tables) == 1 and "S(1)" in tables[0], tables


def test_sparse_margins_gather_eight_model_values_an_index(
    one_chip, no_compile_cache, on_tpu
):
    """``gradients.sparse_margins`` alone at the criteo step's sample,
    145,472 packed rows x 40 slots against ``d`` = 1,000,000 (ISSUE 36):
    the chooser says ``rows8``, and the program compiled for the described
    v5e gathers the model ONLY through an eight-wide slice, a block of
    ``SPARSE_GATHER_BLOCK_SLOTS`` slots (8,192 rows) at a time, rows minor
    as the sample is stored.  The table stays in VMEM (``S(1)``): a block's
    gathered rows, padded to 128 lanes (512 B a slot), are too large to
    take its place and go to HBM, where the block bounds them; the whole
    sample at once wanted 3.2 GB."""
    cap = steps.sparse_step_capacity(0.05, ELL_ROWS)
    _, spec = _ell_specs(one_chip)
    c_sel, v_sel = (spec((cap, ELL_WIDTH), jnp.int32),
                    spec((cap, ELL_WIDTH), jnp.float32))
    w = spec((ELL_D,), jnp.float32)
    assert gradients.sparse_gather_path(w, c_sel) == "rows8"
    compiled = jax.jit(gradients.sparse_margins).lower(
        c_sel, v_sel, w).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    stored = [t for _n, t, op, _ in _instructions(entry)
              if op == "parameter" and f"[{cap},{ELL_WIDTH}]" in t]
    assert len(stored) == 2
    for t in stored:  # rows minor: a block's transpose is a bitcast
        assert re.search(r"\[\d+,\d+\]\{0,1", t), t
    rows = gradients.SPARSE_GATHER_BLOCK_SLOTS // ELL_WIDTH
    assert rows == 8_192
    assert _model_gathers(text) == [
        (f"f32[8,{ELL_WIDTH},{rows}]", "8,1")], _model_gathers(text)
    _table_stays_in_vmem(text)
    assert [i for i in _instructions(text) if i[2] == "while"]
    assert not [i for i in _instructions(text) if i[2] in ("scatter", "sort")]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_BOUND, f"{temp} bytes of temporaries"


def _segment_tiles(d):
    from asyncframework_tpu.ops import pallas_kernels

    return -(-d // pallas_kernels.SEGMENT_TILE)


def _sums_by_sorted_segments(text, slots, d):
    """The program adds ``slots`` products into a ``(d,)`` gradient by
    sorted segments (ISSUE 52): ONE sort of two operands, the (column,
    product) pairs padded to whole blocks of the kernel's DMA; the custom
    call of ``pallas_kernels.segment_tiles_sum``, which takes the tiles'
    bounds and the sorted pairs as rows of 128; and no scatter."""
    from asyncframework_tpu.ops import pallas_kernels

    block = 128 * pallas_kernels._SEGMENT_BLOCK_ROWS
    padded = -(-slots // block) * block
    pairs = [t for _n, t, op, _ in _instructions(text)
             if op == "sort" and t.startswith("(")]
    assert len(pairs) == 1, pairs
    assert f"s32[{padded}]" in pairs[0] and f"f32[{padded}]" in pairs[0]
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "segment_tiles_sum" in calls[0], calls
    for operand in (f"s32[{_segment_tiles(d) + 1}]",
                    f"s32[{padded // 128},128]", f"f32[{padded // 128},128]"):
        assert operand in calls[0], (operand, calls[0][:400])
    assert " scatter(" not in text


@pytest.mark.parametrize("live_width", [None, 39],
                         ids=["stored-40", "live-39"])
def test_sparse_step_moves_no_slot_to_put_it_in_order(
    one_chip, no_compile_cache, on_tpu, live_width
):
    """The sparse ASGD step at the criteo cell's shard (``b`` 0.05, the
    logistic link; ISSUE 33).  Nothing carries the sampled slots into
    another order for a SCATTER's sake: the program holds no gather whose
    result is a permutation of the columns or of the products (the parent
    of ISSUE 33 carried both into sorted order in front of its scatter:
    124 of its 251 ms on the chip).  One sort packs the sampled row ids:
    ONE operand, the 2,865,039 row keys, where ``jnp.nonzero`` scattered
    as many ones.  Since ISSUE 36 the model is gathered eight values an
    index (``gradients.sparse_margins``): no single-element gather of
    ``w`` is left in the step.  Since ISSUE 38 the cell's step is built at
    the shard's live width, 39 of the 40 stored slots: the same program
    over 5,673,408 slots in blocks of 8,320 rows (whole tiles of 128), the
    ``(8, d / 8)`` table still in VMEM, and of the shard's height nothing
    but a ``bitcast`` of its first 39 columns; ``stored-40`` is what
    ``live_width=None`` keeps building.

    Since ISSUE 52 the products are added into ``g`` by SORTED SEGMENTS
    (``gradients.sparse_scatter_path``: 23,000 slots a tile of 4,096
    columns): ONE two-operand sort of the (column, product) pairs, padded
    to whole blocks of the kernel's DMA, the tiles' bounds by a
    ``searchsorted`` (its one gather: 246 bounds an iteration), the
    kernel's custom call, and NO scatter at all; the pairs and their
    sorted copies lie in VMEM."""
    (cols, vals, y), spec = _ell_specs(one_chip)
    batch_rate = 0.05
    step = steps.make_sparse_asgd_worker_step(
        batch_rate, ELL_D, "logistic", live_width=live_width)
    cap = steps.sparse_step_capacity(batch_rate, ELL_ROWS)
    width = live_width or ELL_WIDTH
    slots = cap * width
    assert (cap, slots) == (145_472, {40: 5_818_880, 39: 5_673_408}[width])
    assert step.gather_path(ELL_ROWS, width) == "rows8"
    compiled = step.lower(cols, vals, y, spec((ELL_D,), jnp.float32),
                          spec((2,), jnp.uint32)).compile()
    text = compiled.as_text()
    instrs = _instructions(text)
    assert not _makes_a_whole_shard(text), _makes_a_whole_shard(text)

    assert step.scatter_path(ELL_ROWS, width) == "segments"
    sorts = [t for _n, t, op, _ in instrs if op == "sort"]
    # a single operand: the result is one array of row keys, not a tuple
    keys = [t for t in sorts if not t.startswith("(")]
    assert len(keys) == 1 and keys[0].startswith(f"s32[{ELL_ROWS}]"), sorts
    _sums_by_sorted_segments(text, slots, ELL_D)

    # the gathers are the mathematics' own: the sampled rows of cols and
    # vals, their labels, and a block of the model's eight-row table; none
    # makes a flat [slots] array (``flat[order]``, ``contrib[order]``) and
    # none takes the model one element an index
    rows = gradients._block_rows(gradients.SPARSE_GATHER_BLOCK_SLOTS, width)
    assert rows == {40: 8_192, 39: 8_320}[width]
    gathers = sorted(t.split("{")[0] for _n, t, op, _ in instrs
                     if op == "gather")
    assert gathers == sorted([
        f"s32[{cap},{width}]", f"f32[{cap},{width}]",
        f"f32[8,{width},{rows}]", f"f32[{cap}]",
        f"s32[{_segment_tiles(ELL_D) + 1}]"]), gathers
    assert _model_gathers(text) == [
        (f"f32[8,{width},{rows}]", "8,1")], _model_gathers(text)
    _table_stays_in_vmem(text)
    if live_width:  # the first 39 ELL columns, where they lie
        views = [t for _n, t, op, _ in _instructions(text[text.index("ENTRY"):])
                 if op == "bitcast" and f"[{ELL_ROWS},{width}]" in t]
        assert len(views) == 2, views
        assert f"[{cap * ELL_WIDTH}]" not in text
    # the gathered rows, their products and the keys' scratch (122.7 MB
    # until PR 36) and one block of the model's gathered rows
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 160e6 + TEMP_BOUND, f"{temp} bytes of temporaries"


SAGA_ROWS = 1_432_520  # criteo-asaga's shard: 11,460,160 rows, 8 workers


@pytest.mark.parametrize("program", ["step", "delta"])
def test_sparse_saga_programs_sum_by_sorted_segments(
    one_chip, no_compile_cache, on_tpu, program
):
    """criteo under ASAGA (``b`` 0.02 of 1,432,520 rows, 39 live slots of
    40): the step's history-corrected gradient and the accept path's exact
    table delta add the SAME 1,156,584 slots into ``g``, 4,700 a tile of
    4,096 columns, and both do it by sorted segments (ISSUE 52): one
    two-operand sort, the kernel's custom call, no scatter into
    ``f32[1000000]`` (the commit's ``alpha.at[idx].set`` is another
    program's).  The modules keep the names the benchmark's readers find
    their device time by."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    live, batch_rate = 39, 0.02
    cap = steps.sparse_step_capacity(batch_rate, SAGA_ROWS)
    slots = cap * live
    assert (cap, slots) == (29_656, 1_156_584)
    f32 = jnp.float32
    if program == "step":
        step = steps.make_sparse_saga_worker_step(
            batch_rate, ELL_D, live_width=live)
        assert step.scatter_path(SAGA_ROWS, live) == "segments"
        compiled = step.lower(
            spec((SAGA_ROWS, ELL_WIDTH), jnp.int32),
            spec((SAGA_ROWS, ELL_WIDTH), f32), spec((SAGA_ROWS,), f32),
            spec((ELL_D,), f32), spec((SAGA_ROWS,), f32),
            spec((2,), jnp.uint32)).compile()
        name = "jit_step"
    else:
        compiled = steps.make_sparse_table_delta(ELL_D).lower(
            spec((cap, live), jnp.int32), spec((cap, live), f32),
            spec((cap,), f32), spec((SAGA_ROWS,), f32),
            spec((cap,), jnp.int32)).compile()
        name = "jit_sparse_saga_table_delta"
    text = compiled.as_text()
    assert text.startswith(f"HloModule {name},"), text[:80]
    _sums_by_sorted_segments(text, slots, ELL_D)
    tall = [(n, op) for n, t, op, _ in _instructions(text)
            if re.search(r"\[%d,\d+\]" % SAGA_ROWS, t)
            and op not in ("parameter", "get-tuple-element", "bitcast")]
    assert not tall, tall


# ----------------------------------------- the wide deployment (ISSUE 37)

WIDE_ROWS, WIDE_WIDTH, WIDE_D = 4_676_222, 16, 54_686_452


def test_sparse_margins_gather_a_lane_row_an_index_from_a_model_over_vmem(
    one_chip, no_compile_cache, on_tpu
):
    """``gradients.sparse_margins`` alone at the kdd2012 step's sample,
    236,640 packed rows x 16 slots against ``d`` = 54,686,452: 219 MB, which
    no form of keeps in the v5e's VMEM.  The chooser says ``lanes128``, and
    the program compiled for the described v5e gathers the model ONLY as
    whole rows of 128 lanes (512 B that lie together in HBM), a block of
    ``SPARSE_LANES_BLOCK_SLOTS`` slots at a time (512 rows of the stored
    width 16 since ISSUE 38 sized the block at the live width 11); the
    model's one padded copy and a block's rows are all it keeps."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cap = steps.sparse_step_capacity(0.05, WIDE_ROWS)
    assert (cap, cap * WIDE_WIDTH) == (236_640, 3_786_240)
    c_sel, v_sel = (spec((cap, WIDE_WIDTH), jnp.int32),
                    spec((cap, WIDE_WIDTH), jnp.float32))
    w = spec((WIDE_D,), jnp.float32)
    assert WIDE_D % 8 == 4 and 4 * WIDE_D > gradients.SPARSE_VMEM_BYTES
    assert gradients.sparse_gather_path(w, c_sel) == "lanes128"
    compiled = jax.jit(gradients.sparse_margins).lower(
        c_sel, v_sel, w).compile()
    text = compiled.as_text()
    rows = gradients._block_rows(gradients.SPARSE_LANES_BLOCK_SLOTS,
                                 WIDE_WIDTH)
    assert rows == 512
    gathers = [(m.group(1).split("{")[0],
                re.search(r"slice_sizes=\{([\d,]+)\}", ln).group(1))
               for ln in text.splitlines()
               for m in [re.search(r"= (\S+) gather\(", ln)] if m]
    assert gathers == [(f"f32[{rows},{WIDE_WIDTH},128]", "1,128")], gathers
    assert [i for i in _instructions(text) if i[2] == "while"]
    assert not [i for i in _instructions(text) if i[2] in ("scatter", "sort")]
    temp = compiled.memory_analysis().temp_size_in_bytes
    # the padded model (219 MB) and one block's gathered rows (8 MB)
    assert temp < 4 * WIDE_D + 64e6, f"{temp} bytes of temporaries"


@pytest.mark.parametrize("program", ["step", "evaluation"])
def test_wide_sparse_programs_read_the_shard_at_its_live_width(
    one_chip, no_compile_cache, on_tpu, program
):
    """kdd2012's shard is stored ``(4,676,222, 16)`` and holds 11 values a
    row (ISSUE 38): the programs built with ``live_width=11`` take the
    first 11 ELL columns of the stored arrays, which is a ``bitcast`` of a
    shard stored rows minor and nothing else: no copy, slice or fusion
    makes an array as tall as the shard.  The step then gathers ``(236,640,
    11)`` columns and values, reads the model a lane row an index in
    blocks of live slots, and sorts and scatter-adds 2,603,040 pairs where
    the stored width made 3,786,240; the evaluation gathers ``(8, 11,
    65,536)`` a block."""
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    live = 11
    cols, vals, y = (spec((WIDE_ROWS, WIDE_WIDTH), jnp.int32),
                     spec((WIDE_ROWS, WIDE_WIDTH), jnp.float32),
                     spec((WIDE_ROWS,), jnp.float32))
    cap = steps.sparse_step_capacity(0.05, WIDE_ROWS)
    if program == "step":
        step = steps.make_sparse_asgd_worker_step(
            0.05, WIDE_D, "logistic", live_width=live)
        assert step.gather_path(WIDE_ROWS, live) == "lanes128"
        compiled = step.lower(cols, vals, y, spec((WIDE_D,), jnp.float32),
                              spec((2,), jnp.uint32)).compile()
    else:
        ev = steps.make_sparse_trajectory_loss_eval("logistic",
                                                    live_width=live)
        compiled = ev.lower(cols, vals, y,
                            spec((8, WIDE_D), jnp.float32)).compile()
    text = compiled.as_text()
    instrs = _instructions(text)
    entry = _instructions(text[text.index("ENTRY"):])
    stored = [t for _n, t, op, _ in entry
              if op == "parameter" and f"[{WIDE_ROWS},{WIDE_WIDTH}]" in t]
    assert len(stored) == 2
    for t in stored:  # rows minor: the first 11 columns lie together
        assert re.search(r"\[\d+,\d+\]\{0,1", t), t
    tall = [(name, op) for name, t, op, _ in instrs
            if re.match(r"[a-z0-9]+\[%d,\d+\]" % WIDE_ROWS, t)
            and op not in ("parameter", "get-tuple-element", "bitcast")]
    assert not tall, tall
    views = [t for _n, t, op, _ in entry
             if op == "bitcast" and f"[{WIDE_ROWS},{live}]" in t]
    assert len(views) == 2, views
    gathers = sorted(t.split("{")[0] for _n, t, op, _ in instrs
                     if op == "gather")
    if program == "step":
        rows = gradients._block_rows(gradients.SPARSE_LANES_BLOCK_SLOTS, live)
        assert rows % 128 == 0  # whole lane tiles of the sample's rows
        assert gathers == sorted([
            f"s32[{cap},{live}]", f"f32[{cap},{live}]", f"f32[{cap}]",
            f"f32[{rows},{live},128]"]), gathers
        slots = cap * live
        assert slots == 2_603_040
        scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
        assert len(scatters) == 1 and f" f32[{WIDE_D}]" in scatters[0]
        pair_sorts = [t for _n, t, op, _ in instrs
                      if op == "sort" and t.startswith("(")]
        assert len(pair_sorts) == 1 and f"s32[{slots}]" in pair_sorts[0]
        assert f"[{cap * WIDE_WIDTH}]" not in text
    else:
        assert gathers == [f"f32[8,{live},65536]"], gathers
        assert compiled.memory_analysis().temp_size_in_bytes < 64e6


# --------------------------------------- the ragged deployment (ISSUE 39)

RAGGED_ROWS, RAGGED_D = 16_406, 16_609_143
#: the narrowest and the widest of webspam's eight shard shapes, stored and
#: read: whole lane tiles of 128 (``data/sparse.py: _round_up``), read
#: whole (``SparseShardedDataset.live_widths``)
RAGGED_SHAPES = {"narrowest": (1_664, 1_664), "widest": (16_384, 16_384)}


def _ragged_specs(one_chip, stored):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (spec((RAGGED_ROWS, stored), jnp.int32),
            spec((RAGGED_ROWS, stored), jnp.float32),
            spec((RAGGED_ROWS,), jnp.float32)), spec


def _tall_as_the_ragged_shard(text):
    """What the program MAKES of the shard's height: a relayout or a copy
    of it.  Its move to VMEM as it is (``copy-start`` / ``copy-done``: the
    compiler prefetches a shard that fits there across programs, webspam's
    narrowest at 16,406 rows) is none."""
    shard = re.compile(r"\[%d,\d+\]" % RAGGED_ROWS)
    return [
        (name, op) for name, t, op, _ in _instructions(text)
        if shard.search(t) and op not in ("parameter", "get-tuple-element",
                                          "tuple", "while", "bitcast",
                                          "copy-start", "copy-done")
    ]


@pytest.mark.parametrize("shape,bound", [
    ("narrowest", None), ("widest", None), ("narrowest", 1_024)],
    ids=["narrowest", "widest", "narrowest-under-the-bound"])
def test_ragged_sparse_step_walks_the_sample_in_blocks(
    one_chip, no_compile_cache, on_tpu, monkeypatch, shape, bound
):
    """webspam's step (``b`` 0.05 of 16,406 rows, the logistic link, ``d``
    16,609,143) on the narrowest and on the widest shard, built as ASGD
    builds it: ONE step with the dataset's mapping of live widths, traced
    for the shape it is called with.  A shard stored a whole number of lane
    tiles wide is row-major, so a sampled row is one contiguous read: the
    two row gathers take ``(1, live)`` slices of the stored parameters
    themselves, by the ROW, and nothing as tall as the shard is made.  One
    single-operand sort packs the row keys, as since PR 33.

    Since ISSUE 40 the packed sample is WALKED (``gradients.walk_tile``):
    the model's gather sits in loops over row tiles and, inside, over that
    tile's chunks, and takes ``(R, C)`` blocks; the model (66 MB, ``d % 8
    == 7``: no eight-row view, over ``SPARSE_ELEMENTS_BYTES``) is read a
    lane row an index, no element of it gathered alone, its lane rows in
    VMEM through the margins' loops.  How the products are added into
    ``g`` is ``gradients.sparse_scatter_path``'s, from the list's length
    (ISSUE 54), and the program is held to both of its answers:

    THE WIDEST shard (4,008 slots a tile of ``g``) and, since ISSUE 57,
    THE NARROWEST (407: every one of webspam's eight lies over
    ``SPARSE_SEGMENT_TILE_SLOTS``, 256) sum by SORTED SEGMENTS, as a
    sample read whole does since ISSUE 52: the ``capacity x K`` (column,
    product) pairs in ONE list, ONE two-operand sort of it beside the
    single-operand sort of the row keys, the one custom call of
    ``pallas_kernels.segment_tiles_sum``, and NO scatter; no ``f32[d]``
    is a loop's carry and none is copied: ``g`` is written once, a tile at
    a time, by the kernel.  Until ISSUE 54 the ``(d,)`` accumulator was
    the carry of the walk's loops, in VMEM, and took a scatter-add a block
    of 64 x 256 slots.

    A LIST UNDER THE BOUND (the narrowest shard with the constant where
    it stood until ISSUE 57, 1,024: the form a shorter walked list keeps)
    holds that form to the letter: the scatter-add
    sits in the walk's loops and takes ``(R, C)`` blocks, a block large
    enough for the compiler to sort ITS pairs in front of its scatter-add,
    the ``(d,)`` accumulator the loops' carry, updated IN PLACE.  WHERE
    the accumulator lives is what the block's size is chosen by
    (``gradients.walk_accumulator_resident``): this shard's arrays (109 MB
    each) fit VMEM, the compiler prefetches one of them there across
    programs, the accumulator stays in HBM and the blocks are 128 x 512.

    Either way the temporaries stay under what ``solvers/base.py`` plans a
    slot."""
    if bound is not None:
        monkeypatch.setattr(gradients, "SPARSE_SEGMENT_TILE_SLOTS", bound)
    stored, live = RAGGED_SHAPES[shape]
    widths = {s: lw for s, lw in RAGGED_SHAPES.values()}
    (cols, vals, y), spec = _ragged_specs(one_chip, stored)
    step = steps.make_sparse_asgd_worker_step(
        0.05, RAGGED_D, "logistic", live_width=widths)
    cap = steps.sparse_step_capacity(0.05, RAGGED_ROWS)
    assert cap == 992 and step.gather_path(RAGGED_ROWS, live) == "lanes128"
    assert 4 * RAGGED_D < gradients.SPARSE_VMEM_BYTES and RAGGED_D % 8 == 7
    resident = gradients.walk_accumulator_resident(RAGGED_D, RAGGED_ROWS, live)
    assert resident == (shape == "widest")
    R, C = steps.sparse_walk_tile(0.05, RAGGED_D, RAGGED_ROWS, live)
    assert (R, C) == ((64, 256) if resident else (128, 512))
    segments = bound is None
    assert step.scatter_path(RAGGED_ROWS, live) == (
        "segments" if segments else "scatter")
    assert step.sorted_pairs(RAGGED_ROWS, live) == (
        -(-cap * live // 8_192) * 8_192 if segments else 0)
    compiled = step.lower(cols, vals, y, spec((RAGGED_D,), jnp.float32),
                          spec((2,), jnp.uint32)).compile()
    text = compiled.as_text()
    instrs = _instructions(text)
    entry = _instructions(text[text.index("ENTRY"):])
    params = [t for _n, t, op, _ in entry
              if op == "parameter" and f"[{RAGGED_ROWS},{stored}]" in t]
    assert len(params) == 2
    for t in params:  # row-major: a row's slots lie together
        assert re.search(r"\[\d+,\d+\]\{1,0", t), t
    assert not _tall_as_the_ragged_shard(text), _tall_as_the_ragged_shard(text)

    sorts = [t for _n, t, op, _ in instrs if op == "sort"]
    keys = [t for t in sorts if not t.startswith("(")]
    pairs = [t for t in sorts if t.startswith("(")]
    assert len(keys) == 1 and keys[0].startswith(f"s32[{RAGGED_ROWS}]"), sorts
    gathers = sorted(t.split("{")[0] for _n, t, op, _ in instrs
                     if op == "gather")
    bounds = f"s32[{_segment_tiles(RAGGED_D) + 1}]"  # ``searchsorted``'s
    assert [t for t in gathers if t != bounds] == sorted([
        f"s32[{cap},{live}]", f"f32[{cap},{live}]", f"f32[{R},{C},128]",
        f"f32[{cap}]"]), gathers
    rows_read = [ln for ln in text.splitlines()
                 if " gather(" in ln and f"slice_sizes={{1,{live}}}" in ln]
    assert len(rows_read) == 2, rows_read
    assert re.search(r"f32\[\d+,128\]\{1,0:T\(8,128\)S\(1\)\}", text)
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    carried = [(n, t) for n, t, op, _ in instrs
               if op == "while" and f"f32[{RAGGED_D}]" in t]
    if segments:
        # the ONE sort of pairs is the list's, the custom call takes it as
        # rows of 128, nothing scatters, no (d,) accumulator is carried
        _sums_by_sorted_segments(text, cap * live, RAGGED_D)
        assert not scatters and not carried, (scatters, carried)
    else:
        # nothing of the sample's size but the two row gathers: the sort
        # of pairs and the scatter take one block, in the walk's loops
        assert f"[{cap * live}]" not in text and bounds not in gathers
        assert len(pairs) == 1 and f"s32[{R * C}]" in pairs[0], sorts
        assert len(scatters) == 1 and "/while/body/" in scatters[0], scatters
        assert f" f32[{RAGGED_D}]{{0:T(1024)}} scatter(" in scatters[0]
        assert carried
        prefetched = [ln for ln in text.splitlines()
                      if "cross_program_prefetch_index" in ln
                      and f"[{RAGGED_ROWS},{stored}]" in ln]
        assert prefetched
    # the accumulator is copied nowhere, so not inside the loops either
    assert not [(n, t) for n, t, op, _ in instrs
                if op == "copy" and t.startswith(f"f32[{RAGGED_D}]")]
    # the packed sample and its list (solvers/base.py plans 20 B a slot)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 20 * cap * stored + (0 if segments else 32e6), (
        f"{temp} bytes of temporaries")


@pytest.mark.parametrize("shape", sorted(RAGGED_SHAPES))
def test_ragged_sparse_evaluation_walks_blocks_of_wide_rows(
    one_chip, no_compile_cache, shape
):
    """The blocked evaluation on the same two shards: a block is at most
    ``SPARSE_EVAL_BLOCK_SLOTS`` slots (1,568 rows of 1,664 slots, 160 of
    16,384: not a shard at once, 11.5 GB gathered), taken as the shard is
    stored, slots minor; per block ONE gather serves the eight snapshots
    and nothing as tall as the shard is made."""
    stored, live = RAGGED_SHAPES[shape]
    widths = {s: lw for s, lw in RAGGED_SHAPES.values()}
    (cols, vals, y), spec = _ragged_specs(one_chip, stored)
    ev = steps.make_sparse_trajectory_loss_eval("logistic", live_width=widths)
    rows = ev.block_rows(RAGGED_ROWS, stored)
    assert rows == {1_664: 1_568, 16_384: 160}[stored]
    assert ev.blocks(RAGGED_ROWS, stored) == -(-RAGGED_ROWS // rows)
    compiled = ev.lower(cols, vals, y,
                        spec((8, RAGGED_D), jnp.float32)).compile()
    text = compiled.as_text()
    assert not _tall_as_the_ragged_shard(text), _tall_as_the_ragged_shard(text)
    gathers = [t.split("{")[0] for _n, t, op, _ in _instructions(text)
               if op == "gather"]
    assert gathers == [f"f32[8,{rows},{live}]"], gathers
    # one block's gathered values and its products
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 3 * 8 * 4 * steps.SPARSE_EVAL_BLOCK_SLOTS, temp
