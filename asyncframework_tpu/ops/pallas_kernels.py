"""Pallas TPU kernels for the gradient hot path.

The reference's compute hot loop bottoms out in native BLAS through JNI
(``LeastSquaresGradient.compute`` -> ``BLAS.axpy/dot`` ->
``mllib-local/.../BLAS.scala:20-35`` netlib).  The TPU equivalent is mostly
*just XLA*.  This module is the layer below that, for what XLA's fusions
cannot do:

- :func:`dense_onepass` -- the dense worker step's two products from ONE
  read of the shard.  XLA runs ``r = X w - y`` and ``g = X^T (mask * r)``
  as two multiply-reduce fusions, each at 755 GB/s of the v5e's 819, and
  the second cannot start before ``r`` is complete: the shard crosses the
  HBM bus twice a step.  The kernel walks ``X.T`` -- a free ``bitcast`` of a
  shard the device stores column-major, so nothing is relaid -- in ``(d,
  block)`` blocks and computes, without leaving VMEM, the margins of the
  block's columns, the masked per-row scalar ``v`` and ``g``'s partial
  sums.  The byte model: one read of the shard (1.59 GB of bf16 at 1.0M x
  784: 2.14 ms at 740 GB/s against 4.24 ms), plus 4 bytes a row for each of
  ``y``, ``mask`` and, in ASAGA's form, ``alpha`` in and ``diff`` out.
  Both products run on the vector unit in f32, whatever the storage dtype
  (Mosaic's default f32 ``dot`` would round the shard to bf16, and the MXU
  forms that were timed beside it were no faster: the kernel is bound by
  the read, PERF.md section 6, PR 26).  Where two reads remain: every path
  ``gradients.dense_step_path`` sends to the two XLA products (the CPU,
  lane-aligned widths), and ASAGA's table delta, a product of its own on
  the accept path.
- :func:`dense_onepass_tiles` -- the same step from a read of the lane
  tiles that hold a sampled row, and of no other.  ``X.T`` lies in HBM as
  tiles of 16 features x 128 ROWS of the shard (4 KB of bf16), and a tile
  none of whose rows was drawn adds exact zeros to ``g``: under a
  Bernoulli draw at ``b`` that is ``(1 - b)^128`` of them, 27.6% at
  ASAGA's 0.01 and nothing at ASGD's 0.1.  XLA lists the other tiles in
  front of the kernel (a flag a tile, one sort, row gathers of 128 lanes
  for the vectors: 0.06 ms), the kernel fetches each listed tile's ``(d,
  128)`` window by a DMA of its own (49 pieces of 4 KB, 32 MB apart: 748
  GB/s where the contiguous block reads 757) and runs the SAME block
  arithmetic (:func:`_block_step`): 1.68 ms where the whole shard takes
  2.23 (v5e, 1.0M x 784 bf16, PERF.md section 6, PR 49).  Which of the
  two runs is ``gradients.dense_step_path``'s choice, from the draw's
  rate.
- :func:`chunk_attention` -- block attention with local softmax stats for
  the long-context path: a flash-style forward tiled over (query block,
  key block) with the running (m, l, o) in VMEM scratch, returning the
  (o, m, l) triple so ``parallel/ring.py`` can merge ring steps with the
  cheap rescale (``ring_attention(..., block_kernel="pallas")``).
- :func:`segment_tiles_sum` -- the sparse steps' ``g = sum of products
  at their columns`` by SORTED SEGMENTS.  XLA's scatter-add touches ``g``
  one index at a time, 6.7 to 8.8 ns a slot on the v5e in whatever order
  (half to three quarters of the sparse cells' device time), while its
  sort takes 2 ns a pair: so the (column, product) pairs are sorted
  once, the slots of any tile of ``g``'s columns are then ONE contiguous
  run of the list, and the kernel adds a run into its tile as dense work
  (per 1,024 slots one product on the MXU: the products, in three exact
  bf16 parts, placed by ``column // 128``, with the one-hot of ``column %
  128``).  Where that pays is ``gradients.sparse_scatter_path``'s choice,
  from the mean run of slots a tile (PERF.md section 6, PR 52).
- The sparse steps' GATHERS stay XLA's (``gradients.sparse_margins``): a
  vector gather does not map onto the VPU's strided units, and for
  rcv1-style data the SURVEY-prescribed alternative (densify per batch,
  then a dense kernel) lives in the data layer.

``interpret`` is an explicit argument everywhere: the CPU tests pass
``interpret=True``, every other caller gets the Mosaic-compiled kernel
(``chip_smoke.py`` phases E and E.segments run all four at full shapes
on the chip against precision "highest" or float64;
``tests/test_step_layout.py`` compiles the steps that hold the two
one-pass kernels and the segment kernel for a described v5e).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: lanes of a vector register: the unit the kernel's loops walk a block in
_LANE = 128
#: feature rows a loop body holds in registers at once (16 f32 registers)
_ROW_GROUP = 128
#: bytes of the shard one grid step takes (4,096 columns of a bf16
#: 784-row block, 2,048 of an f32 one: the sizes timed on the v5e)
_ONEPASS_BLOCK_BYTES = 13 << 19


#: most bytes one block may take (a very wide shard's 512 columns): two
#: such buffers must fit VMEM with room
_ONEPASS_MAX_BLOCK_BYTES = 16 << 20


def onepass_block(d: int, itemsize: int) -> int:
    """Columns of ``X.T`` a grid step of :func:`dense_onepass` takes: a
    multiple of 512 lanes, at least 512."""
    return max(512, _ONEPASS_BLOCK_BYTES // (d * itemsize) // 512 * 512)


def onepass_takes(d: int, dtype) -> bool:
    """Whether :func:`dense_onepass` takes shards of width ``d`` and this
    storage dtype: f32 or bf16, ``d`` a whole number of sublane tiles (8
    rows of f32, 16 of bf16: the row groups are sliced on tile edges), and
    a block that fits VMEM twice."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if d % (32 // dtype.itemsize) != 0:
        return False
    block_bytes = onepass_block(d, dtype.itemsize) * d * dtype.itemsize
    return block_bytes <= _ONEPASS_MAX_BLOCK_BYTES


def _row_groups(d: int):
    return [(lo, min(_ROW_GROUP, d - lo)) for lo in range(0, d, _ROW_GROUP)]


def _lanes(c):
    return pl.ds(pl.multiple_of(c * _LANE, _LANE), _LANE)


def _margins(xt_ref, wb_ref, r_ref, chunks, before=None):
    """``r = w . Xb`` over the block's first ``chunks`` 128-lane chunks (a
    Python integer, or a traced one where the block is a list of tiles),
    one chunk at a time: the products of a row group are added register
    by register down to one ``(8, 128)`` register, so a chunk costs ONE
    cross-sublane reduction.  Columns of the last chunk that lie beyond
    the shard give garbage the caller selects out: a column's margin
    depends on that column alone.  ``before(c)``, where given, runs in
    front of chunk ``c``'s reads (the tile-list kernel's DMA traffic)."""
    groups = _row_groups(xt_ref.shape[0])

    def chunk(c, carry):
        if before is not None:
            before(c)
        lanes = _lanes(c)
        acc = None
        for lo, size in groups:
            p = (xt_ref[lo:lo + size, lanes].astype(jnp.float32)
                 * wb_ref[lo:lo + size, :])
            for k in range(0, size, 8):
                acc = p[k:k + 8] if acc is None else acc + p[k:k + 8]
        r_ref[:, lanes] = jnp.sum(acc, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


def _accumulate_grad(xt_ref, v_ref, g_ref, n_full, tail: int = 0):
    """``g += Xb . v`` over the block's first ``n_full`` chunks (an integer
    or a traced one, as :func:`_margins` takes it) and the ``tail``
    columns after them: a row group's ``(rows, 128)`` partial sums stay in
    registers across the block's chunks, and lanes are reduced once, after
    the call.  Columns beyond those (the ragged tail of the last block)
    are not read, but in the chunk that straddles the end, where they are
    selected out of ``Xb``: ``0 * NaN`` is ``NaN``."""
    for lo, size in _row_groups(xt_ref.shape[0]):
        rows = slice(lo, lo + size)

        def chunk(c, acc, rows=rows):
            lanes = _lanes(c)
            return acc + (xt_ref[rows, lanes].astype(jnp.float32)
                          * v_ref[:, lanes])

        acc = jax.lax.fori_loop(
            0, n_full, chunk, jnp.zeros((size, _LANE), jnp.float32))
        if tail:  # only ever behind a Python n_full
            lanes = slice(n_full * _LANE, (n_full + 1) * _LANE)
            lane = jax.lax.broadcasted_iota(jnp.int32, (size, _LANE), 1)
            x = jnp.where(lane < tail,
                          xt_ref[rows, lanes].astype(jnp.float32), 0.0)
            acc = acc + x * v_ref[:, lanes]
        g_ref[rows, :] += acc


def _block_step(xt_ref, wb_ref, y_ref, m_ref, a_ref, g_ref, diff_ref,
                r_ref, v_ref, *, logistic: bool, chunks, cols=None,
                before=None):
    """The arithmetic of one block ``Xb`` in VMEM, for both one-pass
    kernels: the margins of its columns, the masked per-row scalar and
    ``g``'s partial sums.  ``r`` and ``v`` live in ``(1, block)`` scratch.
    ``chunks``: how many 128-lane chunks of the block hold columns;
    ``cols``: how many columns, where the last chunk is ragged (a Python
    integer; ``None``: every chunk is whole).  ``a_ref`` and ``diff_ref``
    are ``None`` outside ASAGA's form; ``before`` is :func:`_margins`'s."""
    _margins(xt_ref, wb_ref, r_ref, chunks, before)
    r = r_ref[:]
    diff = (jax.nn.sigmoid(r) if logistic else r) - y_ref[:]
    if a_ref is not None:
        diff_ref[:] = diff
        diff = diff - a_ref[:]
    v = m_ref[:] * diff
    if cols is not None:
        # select, never multiply: what lies beyond n may be NaN
        col = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(col < cols, v, 0.0)
    v_ref[:] = v
    if cols is None:
        _accumulate_grad(xt_ref, v_ref, g_ref, chunks)
    else:
        _accumulate_grad(xt_ref, v_ref, g_ref, *divmod(cols, _LANE))


def _onepass_kernel(*refs, n: int, block: int, logistic: bool, saga: bool):
    """One grid step over ``Xb = X.T[:, i*block:(i+1)*block]``, the block
    in VMEM by the pipeline's own fetch; ``g_ref`` is the ``(d, 128)``
    output block every grid step revisits."""
    if saga:
        xt_ref, wb_ref, y_ref, m_ref, a_ref, g_ref, diff_ref, r_ref, v_ref = refs
    else:
        xt_ref, wb_ref, y_ref, m_ref, g_ref, r_ref, v_ref = refs
        a_ref = diff_ref = None
    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    rem = n - (pl.cdiv(n, block) - 1) * block  # columns of the last block

    @pl.when(i == 0)
    def _():
        g_ref[:] = jnp.zeros_like(g_ref)

    def body(cols: int):
        _block_step(xt_ref, wb_ref, y_ref, m_ref, a_ref, g_ref, diff_ref,
                    r_ref, v_ref, logistic=logistic,
                    chunks=pl.cdiv(cols, _LANE),
                    cols=cols if cols < block else None)

    if rem == block:
        body(block)
    else:
        pl.when(i < last)(lambda: body(block))
        pl.when(i == last)(lambda: body(rem))


def dense_onepass(X, y, w, mask, alpha=None, *, logistic: bool = False,
                  block: Optional[int] = None, interpret=False):
    """``(g, diff)`` of one dense worker step from ONE read of the shard.

    ``diff = link(X w) - y`` and ``g = X^T (mask * (diff [- alpha]))``,
    ``link`` the identity or, with ``logistic``, the sigmoid; ``diff`` is
    returned only with ``alpha`` (ASAGA's candidate scalars), else
    ``None``.  ``X``: ``(n, d)`` f32 or bf16 with ``d`` a multiple of the
    dtype's sublane tile, read in its storage dtype through ``X.T`` -- a
    free ``bitcast`` where the device stores the shard column-major
    (PERF.md section 3), which is the only place this is worth calling:
    ``gradients.dense_step_path`` decides.  ``y``, ``mask``, ``alpha``:
    ``(n,)``; ``w``: ``(d,)``.  Every vector and both accumulations are
    f32 and both products run on the vector unit, so nothing is rounded,
    neither an f32 shard nor a vector, whatever the shard's dtype; the
    sums differ from two XLA products by their order alone.

    The grid walks ``X.T`` in ``(d, block)`` blocks; a block's columns
    beyond ``n`` (the last one's, where ``n`` is no multiple of ``block``)
    hold whatever the padding holds and are selected out inside the
    kernel.  On the v5e this runs at 740-748 GB/s of the chip's 819
    (bf16 and f32, 1.0M x 784; 700 at 253k rows), where each of the two XLA
    fusions it replaces ran at 755 (PERF.md section 6, PR 26).
    """
    n, d = X.shape
    f32 = jnp.float32
    itemsize = jnp.dtype(X.dtype).itemsize
    block = onepass_block(d, itemsize) if block is None else block
    block = min(block, _LANE * pl.cdiv(n, _LANE))
    saga = alpha is not None
    vma = getattr(jax.typeof(X), "vma", None)
    kw = {"vma": vma} if vma else {}
    row = pl.BlockSpec((1, block), lambda i: (0, i))
    resident = pl.BlockSpec((d, _LANE), lambda i: (0, 0))
    # the kernel takes the (n,) vectors as (1, n) rows: a relayout of 4 MB
    # each, 0.01 ms a step (1-D blocks reshaped INSIDE the kernel were
    # timed too: 0.05 ms a vector).  The barrier keeps their producers out
    # of that layout: fused into it, the Bernoulli mask's fusion ran at an
    # eighth of its rate (0.17 ms a step; v5e, PERF.md section 6, PR 26)
    vectors = jax.lax.optimization_barrier(
        [y, mask] + ([alpha] if saga else []))
    out_shape = [jax.ShapeDtypeStruct((d, _LANE), f32, **kw)]
    out_specs = [resident]
    if saga:
        out_shape.append(jax.ShapeDtypeStruct((1, n), f32, **kw))
        out_specs.append(row)
    out = pl.pallas_call(
        functools.partial(_onepass_kernel, n=n, block=block,
                          logistic=logistic, saga=saga),
        grid=(pl.cdiv(n, block),),
        in_specs=[pl.BlockSpec((d, block), lambda i: (0, i)), resident]
        + [row] * len(vectors),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, block), f32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two buffers of the block; the vectors' blocks, w, g and the
            # loops' temporaries are small beside them
            vmem_limit_bytes=2 * d * block * itemsize + (8 << 20),
        ),
        name="dense_onepass",
        interpret=interpret,
    )(
        X.T,
        jnp.broadcast_to(w.astype(f32)[:, None], (d, _LANE)),
        *(v.astype(f32)[None, :] for v in vectors),
    )
    return out[0].sum(axis=1), (out[1].reshape(n) if saga else None)


#: most list entries one grid step of :func:`dense_onepass_tiles` takes:
#: each window has a DMA semaphore of its own in each of the two buffers,
#: and the v5e holds 512 (a 2 KB space; a narrow shard's block of 212,992
#: columns would ask for 3,328)
_TILES_MAX_ENTRIES = 128


def _tiles_kernel(ids_ref, cnt_ref, *refs, tail: int, per_step: int,
                  logistic: bool, saga: bool):
    """One grid step over the next ``per_step`` entries of the tile list:
    ``ids_ref`` holds the ids of the lane tiles that hold a sampled row,
    ascending; ``cnt_ref`` how many there are and how many of them are
    fetched from ``xt_hbm`` (all but the shard's ragged last tile).  Entry
    ``e``'s ``(d, 128)`` window of ``X.T`` goes by a DMA of its own, with
    a semaphore of its own, into lane chunk ``e % per_step`` of buffer
    ``(e // per_step) % 2``.  The margins' loop walks the block a chunk at
    a time, and in front of chunk ``c`` it starts the NEXT grid step's
    window ``c`` and waits for its own: the descriptors' scalar work rides
    in the loop's bundles (issued in a loop of their own, 32 starts and 32
    waits a step, it cost 6% of the kernel: PERF.md section 6, PR 49), and
    a chunk is awaited when it is needed, not the block when its first is.
    Grid steps beyond the list do nothing."""
    xt_hbm, *refs = refs
    xtail_ref = refs.pop(0) if tail else None
    *blocks, buf, sem, r_ref, v_ref = refs
    if saga:
        wb_ref, y_ref, m_ref, a_ref, g_ref, diff_ref = blocks
    else:
        wb_ref, y_ref, m_ref, g_ref = blocks
        a_ref = diff_ref = None
    i = pl.program_id(0)
    count, fetched = cnt_ref[0], cnt_ref[1]
    lo, nxt, slot = i * per_step, (i + 1) * per_step, i % 2
    entries = jnp.clip(count - lo, 0, per_step)
    xb_ref = buf.at[slot]

    def window(slot, entry, c):
        at = pl.multiple_of(ids_ref[entry] * _LANE, _LANE)
        return pltpu.make_async_copy(
            xt_hbm.at[:, pl.ds(at, _LANE)], buf.at[slot, :, _lanes(c)],
            sem.at[slot, c])

    @pl.when(i == 0)
    def _():
        g_ref[:] = jnp.zeros_like(g_ref)
        jax.lax.fori_loop(
            0, jnp.minimum(fetched, per_step),
            lambda c, carry: (window(0, c, c).start(), carry)[1], 0)

    def before(c):
        @pl.when(nxt + c < fetched)
        def _():
            window(1 - slot, nxt + c, c).start()

        @pl.when(lo + c < fetched)
        def _():
            window(slot, lo + c, c).wait()

    def block(chunks):
        if tail:
            # the ragged last tile, where it is on the list, is its last
            # entry: it comes through the pipeline (the block at the
            # array's edge), what it holds beyond n selected out of it:
            # 0 * NaN is NaN
            @pl.when((fetched < count) & (count <= nxt))
            def _():
                lane = jax.lax.broadcasted_iota(jnp.int32, xtail_ref.shape, 1)
                xb_ref[:, _lanes(entries - 1)] = jnp.where(
                    lane < tail, xtail_ref[:].astype(jnp.float32), 0.0
                ).astype(xb_ref.dtype)

        _block_step(xb_ref, wb_ref, y_ref, m_ref, a_ref, g_ref, diff_ref,
                    r_ref, v_ref, logistic=logistic, chunks=chunks,
                    before=before)

    # a whole block keeps the loops' Python bounds; the list's last block
    # walks as many chunks as it has entries
    pl.when(entries == per_step)(lambda: block(per_step))
    pl.when((entries > 0) & (entries < per_step))(lambda: block(entries))


def dense_onepass_tiles(X, y, w, mask, alpha=None, *, logistic: bool = False,
                        block: Optional[int] = None, interpret=False):
    """:func:`dense_onepass` over the lane tiles that hold a sampled row,
    and over no other: the same ``(g, diff)`` from a read of that share of
    the shard.

    ``X.T`` lies in HBM as tiles of 128 ROWS of the shard (PERF.md section
    3), and a tile none of whose rows ``mask`` marks adds exact zeros to
    ``g``: at a rate ``b`` that is ``(1 - b)^128`` of them, 27.6% at
    ASAGA's 0.01.  In front of the kernel, in XLA: one flag a tile, the
    ids of the flagged tiles in ascending order (one sort of ``tiles``
    keys: no ``nonzero``, no scatter), and ``y``, ``mask`` and ``alpha``
    brought to the list's order by row gathers of 128 lanes.  The kernel
    (:func:`_tiles_kernel`) takes ``block // 128`` entries a grid step,
    each window by a DMA of its own (748 GB/s where the pipeline's
    contiguous block reads 757: v5e, PERF.md section 6, PR 49), and
    shares the block's arithmetic with :func:`dense_onepass` (a shard
    under one tile IS that kernel's): ``g`` is the same terms lane by
    lane in the same order less exact zeros, and differs from it only
    where a block's partial sums are cut.  ``diff`` comes back in the
    list's order and is expanded by a row gather; at the rows of a tile
    with no sampled row it is 0 (nobody reads it there: the commit and
    the table delta select and weigh by ``mask``), at every other row
    :func:`dense_onepass`'s value.  ``gradients.dense_step_path`` says
    where this pays."""
    n, d = X.shape
    if n < _LANE:  # no whole tile to window
        return dense_onepass(X, y, w, mask, alpha, logistic=logistic,
                             block=block, interpret=interpret)
    f32 = jnp.float32
    itemsize = jnp.dtype(X.dtype).itemsize
    block = onepass_block(d, itemsize) if block is None else block
    block = min(block, _LANE * pl.cdiv(n, _LANE), _LANE * _TILES_MAX_ENTRIES)
    per_step = block // _LANE
    tiles, tail = pl.cdiv(n, _LANE), n % _LANE
    steps = pl.cdiv(tiles, per_step)
    saga = alpha is not None
    vma = getattr(jax.typeof(X), "vma", None)
    kw = {"vma": vma} if vma else {}
    vectors = jax.lax.optimization_barrier(
        [y, mask] + ([alpha] if saga else []))

    # each vector as one row of 128 lanes a tile
    by_tile = [
        jnp.pad(v.astype(f32), (0, tiles * _LANE - n)).reshape(tiles, _LANE)
        for v in vectors]
    flag = jnp.any(by_tile[1] != 0, axis=1)
    count = jnp.sum(flag, dtype=jnp.int32)
    # the flagged ids ascending, then `tiles` (no tile) to the list's end:
    # an entry the kernel never fetches, and the gathers clip
    ids = jnp.sort(
        jnp.where(flag, jnp.arange(tiles, dtype=jnp.int32), tiles),
        stable=False)
    ids = jnp.pad(ids, (0, steps * per_step - tiles), constant_values=tiles)
    counts = jnp.stack([count, count - flag[-1]] if tail else [count, count])

    row = pl.BlockSpec((1, block), lambda i, ids, cnt: (0, i))
    resident = pl.BlockSpec((d, _LANE), lambda i, ids, cnt: (0, 0))
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [X.T]
    if tail:
        in_specs.append(
            pl.BlockSpec((d, _LANE), lambda i, ids, cnt: (0, tiles - 1)))
        operands.append(X.T)
    out_shape = [jax.ShapeDtypeStruct((d, _LANE), f32, **kw)]
    out_specs = [resident]
    if saga:
        out_shape.append(
            jax.ShapeDtypeStruct((1, steps * block), f32, **kw))
        out_specs.append(row)
    out = pl.pallas_call(
        functools.partial(_tiles_kernel, tail=tail, per_step=per_step,
                          logistic=logistic, saga=saga),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=in_specs + [resident] + [row] * len(vectors),
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((2, d, block), X.dtype),
                            pltpu.SemaphoreType.DMA((2, per_step))]
            + [pltpu.VMEM((1, block), f32)] * 2,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * d * block * itemsize + (8 << 20),
        ),
        name="dense_onepass_tiles",
        interpret=interpret,
    )(
        ids, counts, *operands,
        jnp.broadcast_to(w.astype(f32)[:, None], (d, _LANE)),
        *(jnp.take(v, ids, axis=0, mode="clip").reshape(1, -1)
          for v in by_tile),
    )
    if not saga:
        return out[0].sum(axis=1), None
    # a listed tile's place on the list; any row will do for the others
    at = jnp.cumsum(flag, dtype=jnp.int32) - 1
    diff = jnp.where(
        flag[:, None],
        jnp.take(out[1].reshape(-1, _LANE), at, axis=0, mode="clip"), 0.0)
    return out[0].sum(axis=1), diff.reshape(-1)[:n]


# ------------------------------------------------------- sorted segments
#: columns of ``g`` one grid step of :func:`segment_tiles_sum` sums: a
#: multiple of 1,024 (the output block is whole ``(8, 128)`` registers).
#: On the v5e (PERF.md section 6, PR 52; the kernel alone over criteo's
#: 5,673,408 sorted pairs, ms): 1,024 columns 2.75, 2,048 2.59, 4,096
#: 2.75, 8,192 3.43 (a tile's three parts are 192 rows of the product's
#: left side there); over ASAGA's 1,156,584 with their sort 2.44 to 2.46
#: at 4,096, 2.55 at 8,192, 2.78 at 16,384; over 2.6M pairs in 54.7M
#: columns 11.8 at 4,096 and 9.3 at 16,384 (13,352 grid steps, or 3,338)
SEGMENT_TILE = 4096
#: rows of 128 sorted pairs one DMA of :func:`segment_tiles_sum` brings:
#: a multiple of :data:`_SEGMENT_GROUP`.  It hardly matters (same probe:
#: 8 rows 2.88 ms, 16 to 256 rows 2.70 to 2.79): the pairs lie in VMEM
#: already where they fit it
_SEGMENT_BLOCK_ROWS = 64
#: rows of 128 sorted pairs one product of the kernel takes (one ``(8,
#: 128)`` register of columns, one of products: 1,024 slots)
_SEGMENT_GROUP = 8


def _bf16_parts(p):
    """``p`` (float32) as three float32 arrays, each exact in bfloat16,
    that add up to ``p`` exactly: eight bits of the significand each, the
    later two those of the remainder (exact in float32: a remainder has
    16, then 8 significant bits)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = p.astype(bf16).astype(f32)
    rest = p - hi
    mid = rest.astype(bf16).astype(f32)
    return hi, mid, (rest - mid).astype(bf16).astype(f32)


def _segment_kernel(starts_ref, c_hbm, p_hbm, g_ref, cbuf, pbuf, sem, at_ref,
                    *, tile: int, block_rows: int):
    """One grid step: the sums of the ``tile`` columns from ``t * tile``
    on, from the run ``starts_ref[t] .. starts_ref[t + 1]`` of the sorted
    pairs, which lie in HBM as rows of 128.  The run is read in groups of
    :data:`_SEGMENT_GROUP` rows, from the group that holds its first pair
    to the one that holds its last: what such a group holds of a
    neighbouring tile's columns matches none of this one's.  The groups
    come in blocks of ``block_rows`` rows, each by ONE pair of DMAs into
    buffer ``block % 2``.  Consecutive tiles read consecutive runs, so the
    whole grid reads each block once, in ascending order: ``at_ref``
    (SMEM, kept across grid steps) holds the last block started and the
    last awaited, and the block after the one in hand is started before
    the work on it begins, also where a later tile will be the first to
    read it."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    group, sub = _SEGMENT_GROUP, tile // _LANE
    t = pl.program_id(0)
    first, end = starts_ref[t], starts_ref[t + 1]
    slots = group * _LANE
    # the blocks any tile reads: the last tile's run ends the valid pairs
    blocks = pl.cdiv(starts_ref[pl.num_programs(0)], block_rows * _LANE)

    @pl.when(t == 0)
    def _():
        at_ref[0] = -1  # started
        at_ref[1] = -1  # awaited

    def copies(k):
        rows = pl.ds(pl.multiple_of(k * block_rows, block_rows), block_rows)
        return (pltpu.make_async_copy(c_hbm.at[rows], cbuf.at[k % 2],
                                      sem.at[0, k % 2]),
                pltpu.make_async_copy(p_hbm.at[rows], pbuf.at[k % 2],
                                      sem.at[1, k % 2]))

    def start(k):
        for copy in copies(k):
            copy.start()
        at_ref[0] = k

    def wait(k):
        for copy in copies(k):
            copy.wait()
        at_ref[1] = k

    lane_id = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
    sub_id = jax.lax.broadcasted_iota(jnp.int32, (sub, _LANE), 0)

    def one_group(j, acc):
        k = j * group // block_rows
        pl.when(k > at_ref[0])(lambda: start(k))
        pl.when(k > at_ref[1])(lambda: wait(k))
        pl.when((k + 1 < blocks) & (k + 1 > at_ref[0]))(lambda: start(k + 1))
        rows = pl.ds(pl.multiple_of(j * group % block_rows, group), group)
        local = cbuf[k % 2, rows, :] - t * tile
        parts = _bf16_parts(pbuf[k % 2, rows, :])
        # a column is 128 * hi + lo: g[hi, lo] = sum_k A[hi, k] B[lo, k],
        # A the products at their hi (three exact bf16 parts, stacked), B
        # the one-hot of lo; a pair of another tile has no hi in range
        hi, lo = local >> 7, local & (_LANE - 1)
        a, b = [], []
        for r in range(group):
            at = hi[r:r + 1] == sub_id
            a.append(jnp.concatenate(
                [jnp.where(at, part[r:r + 1], 0.0) for part in parts]))
            b.append(jnp.where(lo[r:r + 1] == lane_id, 1.0, 0.0))
        out = jax.lax.dot_general(
            jnp.concatenate(a, axis=1).astype(bf16),
            jnp.concatenate(b, axis=1).astype(bf16),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)
        return acc + (out[:sub] + out[sub:2 * sub] + out[2 * sub:])

    g_ref[:] = jax.lax.fori_loop(
        first // slots, jnp.where(end > first, pl.cdiv(end, slots), 0),
        one_group, jnp.zeros((sub, _LANE), f32))


def segment_tiles_sum(cols, products, d: int, *, tile: Optional[int] = None,
                      block_rows: Optional[int] = None, interpret=False):
    """``g[j] = sum of products[k] over cols[k] == j``, ``(d,)`` float32:
    a scatter-add by SORTED SEGMENTS, no read-modify-write an index.

    ``cols`` (int32) and ``products`` (float32) are one-dimensional and in
    any order; a column counts from the end where it is negative and is
    dropped where it is then outside ``[0, d)``, as ``g.at[cols].add(
    products, mode="drop")`` has it.  In XLA (:func:`_segment_sorted`):
    ONE unstable two-operand sort of the pairs by column, a dropped pair
    under a column beyond every tile.  Then (:func:`_segment_sums`) the
    first pair of each tile of ``tile`` columns by one ``searchsorted`` of
    the tiles' first columns, and the kernel (:func:`_segment_kernel`),
    which takes those bounds scalar-prefetched and walks ``g`` a tile a
    grid step: the slots of a tile's columns are ONE contiguous run of
    the sorted list, and adding 1,024 of them into the tile is one
    product on the MXU, of the pairs' products placed by ``column // 128``
    with the one-hot of ``column % 128``.  The one-hot is exact in
    bfloat16 and a product is split into three parts that are
    (:func:`_bf16_parts`), so every term is the float32 product and every
    sum float32: ``g`` differs from the scatter-add's by the order of a
    column's terms alone.  (A product that is not finite reaches every
    column of its group of 128 as NaN: ``0 * inf``; the scatter-add keeps
    it to its own.)  On the v5e (PERF.md section 6, PR 52) criteo's
    5,673,408 pairs take 10.1 ms (the kernel alone 2.75; the sort
    alone, its outputs written out to HBM, 11.4) where the scatter-add
    takes 39.0, and ``g`` is 3.4e-7 of ``max |g|`` off the float64 sum
    where the scatter-add is 6.2e-6 off.
    ``gradients.sparse_scatter_path`` says where this is chosen."""
    tile = SEGMENT_TILE if tile is None else tile
    block_rows = _SEGMENT_BLOCK_ROWS if block_rows is None else block_rows
    if tile % (8 * _LANE) or block_rows % _SEGMENT_GROUP:
        raise ValueError(f"tile {tile} or block_rows {block_rows}")
    c, p = _segment_sorted(cols, products, d, tile, block_rows * _LANE)
    return _segment_sums(c, p, d, tile, block_rows, interpret)


def segment_list_pairs(slots: int, block: Optional[int] = None) -> int:
    """The pairs :func:`segment_tiles_sum` sorts for ``slots`` of them:
    the list :func:`_segment_sorted` makes, whole blocks of ``block``
    pairs (``None``: the kernel's own DMA block)."""
    block = _SEGMENT_BLOCK_ROWS * _LANE if block is None else block
    return pl.cdiv(max(slots, 1), block) * block


def _segment_sorted(cols, products, d: int, tile: int, block: int):
    """The pairs in ascending order of column, padded to whole blocks of
    ``block`` pairs: a dropped pair and the padding under ``tile *
    ceil(d / tile)``, a column of no tile, so they sort to the end."""
    beyond = pl.cdiv(d, tile) * tile
    c = jnp.where(cols < 0, cols + d, cols)
    c = jnp.where((c < 0) | (c >= d), beyond, c)
    pad = segment_list_pairs(cols.shape[0], block) - cols.shape[0]
    return jax.lax.sort(
        (jnp.pad(c, (0, pad), constant_values=beyond),
         jnp.pad(products.astype(jnp.float32), (0, pad))),
        num_keys=1, is_stable=False)


def _segment_sums(c, p, d: int, tile: int, block_rows: int, interpret):
    """``(d,)`` sums of the sorted, padded pairs ``(c, p)``
    (:func:`_segment_sorted`): the tiles' bounds and the kernel."""
    f32 = jnp.float32
    tiles, sub = pl.cdiv(d, tile), tile // _LANE
    starts = jnp.searchsorted(
        c, jnp.arange(tiles + 1, dtype=jnp.int32) * tile).astype(jnp.int32)
    vma = getattr(jax.typeof(p), "vma", None)
    kw = {"vma": vma} if vma else {}
    g = pl.pallas_call(
        functools.partial(_segment_kernel, tile=tile, block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((sub, _LANE), lambda t, starts: (t, 0)),
            scratch_shapes=[pltpu.VMEM((2, block_rows, _LANE), jnp.int32),
                            pltpu.VMEM((2, block_rows, _LANE), f32),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((2,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * sub, _LANE), f32, **kw),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="segment_tiles_sum",
        interpret=interpret,
    )(starts, c.reshape(-1, _LANE), p.reshape(-1, _LANE))
    return g.reshape(-1)[:d]


# --------------------------------------------------------------- attention
_NEG_BIG = -1e30  # finite mask fill, same value as parallel/ring.py's _NEG

#: query/key block edge: a (512, 512) f32 score tile is 1 MiB, so the
#: whole working set (q/k/v/mask blocks double-buffered + scratch) stays
#: near 4 MiB whatever Tq and Tk are -- the whole-(Tq, Tk) kernel this
#: replaces was refused by Mosaic from Tk = 2,048 (16.6 MB of VMEM).
_ATTN_BLOCK = 512


def _chunk_attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                       m_sc, l_sc, acc_sc, *, scale: float):
    """One (batch*head, query block, key block) program of a flash-style
    forward: fold this key block into the running (m, l, acc) scratch and
    emit the un-normalized triple after the last key block, so the caller
    can merge whole chunks with the standard rescale.
    """
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_BIG)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                        # (bq, bk)
    s = jnp.where(mask_ref[:] > 0, s, _NEG_BIG)
    m_prev = m_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[:] = corr * l_sc[:] + jnp.sum(p, axis=-1, keepdims=True)
    acc_sc[:] = corr * acc_sc[:] + jnp.dot(
        p, v_ref[0], preferred_element_type=jnp.float32
    )
    m_sc[:] = m_new

    @pl.when(kv == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc_sc[:]
        m_ref[0] = m_sc[:]
        l_ref[0] = l_sc[:]


@functools.partial(
    jax.jit, static_argnames=("scale", "bq", "bk", "interpret", "vma")
)
def _chunk_attn_padded(q, k, v, mask, scale: float, bq: int, bk: int,
                       interpret: bool, vma):
    bh, tq, dp = q.shape
    tk = k.shape[1]
    kw = {} if vma is None else {"vma": frozenset(vma)}
    return pl.pallas_call(
        functools.partial(_chunk_attn_kernel, scale=scale),
        grid=(bh, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((bq, bk), lambda b, i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dp), jnp.float32, **kw),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32, **kw),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32, **kw),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, mask)


def _block_and_pad(t: int) -> "tuple[int, int]":
    """(block edge, padding) for a sequence of length ``t``: one
    sublane-rounded block while it fits, else ``_ATTN_BLOCK``-sized
    blocks (a multiple of the 128-lane tile, as the mask's minor
    dimension needs once it is no longer the full array)."""
    blk = min(_ATTN_BLOCK, 8 * ((t + 7) // 8))
    return blk, (-t) % blk


def chunk_attention(q, k, v, mask=None, interpret: bool = False, vma=None):
    """Block attention with softmax stats: ``(o, m, l)`` per query row.

    ``q``: (B, Tq, H, D); ``k``/``v``: (B, Tk, H, D); ``mask``: (Tq, Tk)
    bool/0-1 (True = attend) or None.  Returns ``o`` (B, Tq, H, D) f32
    un-normalized, ``m``/``l`` (B, H, Tq) f32 -- exactly the running-state
    triple :func:`asyncframework_tpu.parallel.ring._block_accumulate`
    folds, so a ring step can offload its block compute to this kernel
    and keep the (cheap) rescale-merge in XLA.

    Any Tq/Tk: both are tiled in ``_ATTN_BLOCK`` blocks inside the kernel,
    so VMEM use does not grow with the sequence.  Padding: Tq/Tk to a
    whole number of blocks, D to the 128-lane tile.  Padded K columns are
    masked out; padded D columns are zero so they contribute nothing;
    padded Q rows are sliced off.

    ``vma``: when called inside ``shard_map`` with vma checking, the mesh
    axes the outputs vary over (e.g. ``("sp",)``) -- pallas outputs must
    declare their varying-axes explicitly.
    """
    B, tq, H, D = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    bq, pad_q = _block_and_pad(tq)
    bk, pad_k = _block_and_pad(tk)
    pad_d = (-D) % 128

    if mask is None:
        mask_f = jnp.ones((tq, tk), jnp.float32)
    else:
        mask_f = jnp.asarray(mask, jnp.float32)
    mask_f = jnp.pad(mask_f, ((0, pad_q), (0, pad_k)))  # padded K masked

    def to_bhd(x, pad_t):
        x = jnp.asarray(x, jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, 0), (0, pad_d)))
        # (B, T, H, D) -> (B*H, T, Dp)
        return x.transpose(0, 2, 1, 3).reshape(
            B * H, x.shape[1], D + pad_d
        )

    o, m, l = _chunk_attn_padded(
        to_bhd(q, pad_q), to_bhd(k, pad_k), to_bhd(v, pad_k),
        mask_f, scale, bq, bk, interpret, tuple(vma) if vma else None,
    )
    o = o.reshape(B, H, tq + pad_q, D + pad_d)[:, :, :tq, :D]
    o = o.transpose(0, 2, 1, 3)                      # (B, Tq, H, D)
    m = m.reshape(B, H, tq + pad_q)[:, :, :tq]
    l = l.reshape(B, H, tq + pad_q)[:, :, :tq]
    return o, m, l
