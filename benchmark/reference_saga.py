"""Plain reference for the ASAGA cells: the history table and its mean.

Float32 ``jax.numpy`` at precision "highest", no code of the program, the
shard walked in row blocks as ``reference.py`` walks it (a bf16 shard is
never held whole in f32), in either storage as ``reference.py`` takes it: a
dense shard (``shard.X``) or a padded-ELL one (``shard.cols``,
``shard.vals``; a padding slot holds the value 0 and adds nothing).  SAGA
for least squares keeps one scalar a row, ``alpha_i`` (the residual ``x_i .
w - y_i`` as of the last time row ``i`` was sampled), and ``alpha_bar =
sum_i alpha_i x_i / n``, the mean history gradient.  One task over a shard,
with a Bernoulli mask ``m`` and the model ``w`` and history ``alpha_read``
it was handed:

    diff   = X w - y
    g      = X^T (m (diff - alpha_read))        what the task returns
    delta  = X^T (m (diff - alpha_cur))         on accept, against the history NOW
    alpha  = where(m, diff, alpha_cur)          the commit
    w     -= gamma g / par_recs + gamma alpha_bar ;  alpha_bar += delta / n

Over padded ELL ``X w`` is ``sum(vals * w[cols], axis=1)`` and ``X^T v`` a
scatter-add, ``acc.at[cols.ravel()].add((vals * v[:, None]).ravel())``, as
``reference._ell_grad`` writes its gradient, block by block into one
``(d,)`` accumulator a shard on the device; a block holds at most
``ELL_BLOCK_SLOTS`` slots, so a shard thousands of slots wide is walked a
few hundred rows at a time, and shards of unequal stored width each take
their own ``(rows, K)``.  On the v5e (PR 45; a 2,865,039 x 40 criteo shard,
``d`` 1,000,000) that is 6.2 ns a stored slot a vector, and a second vector
in the same jitted block costs a second scatter-add (1.50 s a shard for two
against 0.71 for one: the read is shared, and it is not what costs); a
two-wide payload into a ``(d, 2)`` or ``(2, d)`` accumulator costs 80 ns a
slot.

The device adds a column's terms ONE AFTER THE OTHER in float32, in the
order they are stored.  A column that fills 7.4% of the slots (criteo's
hottest: 8.46M terms a shard) then comes out 2.0e-2 of itself off where the
terms have one sign (a table filled at ``w = 0`` holds ``-y``) and 2.3e-4
of the largest entry where they have both, whatever the block, also where
every block scatters into zeros of its own (the compiler folds the add that
follows into the scatter).  So the ``ELL_HOT_COLUMNS`` columns that fill the
most slots of a shard's first block are summed APART (``_ell_xt``): each
row's own terms on such a column first, then the rows by a product at
precision "highest", as a dense shard's are, a block at a time, and the
blocks' sums added in float64; their entries are written over the
scatter-add's.  With 128 apart the same sums come out 5.6e-7 to 1.6e-6 and
8.2e-7 to 2.2e-6 off (three seeds; the truth: ``numpy.bincount`` in float64
on the host), for 0.05 to 0.14 s a shard.

Departures from the reference drivers, each on purpose:

- ``SparkASAGAThread.scala:210-213`` advances ``alphaBar`` by the task's own
  ``g`` (``delta == g``).  That is exact only while the slice did not change
  between dispatch and accept; a worker dispatched again before its last
  result was committed makes ``alpha_bar`` drift from the table's mean for
  good.  Here, as in the program, ``alpha_bar`` moves by the exact change of
  the table, so ``alpha_bar == history_mean(table)`` holds after every
  update.
- The drivers' acceptance test (``k - staleness <= taw``, on the iteration
  count, not the staleness) is not modelled: a replay is given the results
  that WERE accepted, in the order they were.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import BLOCK_ROWS, _block, _dot, _f32

#: the most slots one block of a padded-ELL shard holds: ``BLOCK_ROWS`` rows
#: at criteo's stored width.  A block's products and the pairs its
#: scatter-add sorts are arrays of that many: 10 MB each, where a 16,406 x
#: 16,384 webspam shard taken in one block would make them 1.1 GB each.
ELL_BLOCK_SLOTS = BLOCK_ROWS * 40
#: how many columns of a padded-ELL shard are summed apart from the
#: scatter-add (the module's docstring): behind the 128th of a Zipf(1) set
#: of a million columns a column fills 0.054% of the slots, a chain of
#: 62,000 terms a criteo shard where the hottest's is 8.46M.
ELL_HOT_COLUMNS = 128


def _rows(a, s, block):
    return jax.lax.dynamic_slice_in_dim(a, s, block)


def _is_ell(shard) -> bool:
    return hasattr(shard, "cols")


def _shard_blocks(shard, block_rows: int):
    """``(block, starts)`` of a shard's walk: ``block_rows`` rows at the
    most, and over padded ELL ``ELL_BLOCK_SLOTS`` slots at the most."""
    rows = int(shard.y.shape[0])
    block = min(block_rows, rows)
    if _is_ell(shard):
        block = min(block, max(1, ELL_BLOCK_SLOTS // int(shard.cols.shape[1])))
    return block, range(0, rows, block)


# ------------------------------------------------------------------ dense
@functools.partial(jax.jit, static_argnames=("block",))
def _mean_block(X, alpha, start, block):
    s, live = _block(X, start, block)
    Xb = _rows(X, s, block).astype(jnp.float32)
    return _dot((_rows(alpha, s, block) * live)[None, :], Xb)[0]


@functools.partial(jax.jit, static_argnames=("block",), donate_argnums=(6,))
def _task_block(X, y, w, alpha_read, alpha_cur, mask, diff, start, block):
    """One row block of a task: its part of ``g`` and ``delta``, and
    ``diff`` with the block's own rows written in."""
    s, live = _block(X, start, block)
    Xb = _rows(X, s, block).astype(jnp.float32)
    db = _dot(Xb, w) - _rows(y, s, block)
    mb = _rows(mask, s, block) * live
    g = _dot((mb * (db - _rows(alpha_read, s, block)))[None, :], Xb)[0]
    delta = _dot((mb * (db - _rows(alpha_cur, s, block)))[None, :], Xb)[0]
    diff = jax.lax.dynamic_update_slice_in_dim(
        diff, jnp.where(live, db, _rows(diff, s, block)), s, 0
    )
    return g, delta, diff


# -------------------------------------------------------------- padded ELL
def _ell_rows(cols, vals, start, block):
    s, live = _block(vals, start, block)
    return s, live, _rows(cols, s, block), _rows(vals, s, block).astype(
        jnp.float32)


def _ell_xt(acc, cb, products, hot):
    """One block of ``X^T c``, a ``(rows, K)`` array of ``products`` ``vals
    * c[:, None]`` a coefficient: each scatter-added into its ``(d,)``
    accumulator of ``acc``, and the block's sums on the ``hot`` columns
    made apart, ``(len(products), H)``: each row's own terms on such a
    column first, then the rows by a product at precision "highest", as a
    dense shard's are."""
    acc = tuple(a.at[cb.ravel()].add(p.ravel())
                for a, p in zip(acc, products))
    on_hot = cb[:, :, None] == hot
    rows = jnp.ones((1, cb.shape[0]), jnp.float32)
    apart = [_dot(rows, jnp.sum(jnp.where(on_hot, p[:, :, None], 0.0),
                                axis=1))[0] for p in products]
    return acc, jnp.stack(apart)


@functools.partial(jax.jit, static_argnames=("block", "d", "count"))
def _hot_block(cols, block, d, count):
    cb = _rows(cols, 0, block)
    slots = jnp.zeros(d, jnp.int32).at[cb.ravel()].add(1)
    return jax.lax.top_k(slots, count)[1]


def _hot_columns(shard, d: int, block: int):
    """The ``ELL_HOT_COLUMNS`` columns that fill the most slots of the
    shard's first block (an exact integer scatter-add of ones)."""
    return _hot_block(shard.cols, block=block, d=d,
                      count=min(ELL_HOT_COLUMNS, d))


@functools.partial(jax.jit, static_argnames=("block", "mass"),
                   donate_argnums=(2,))
def _ell_mean_block(cols, vals, acc, vectors, hot, start, block, mass):
    """``X_b^T v`` for every ``v`` of ``vectors`` added into ``acc``: ONE
    read of the block's ``cols`` and ``vals``, a scatter-add a vector.
    With ``mass`` the last accumulator takes the columns' own,
    ``sum_i |x_ic|``."""
    s, live, cb, vb = _ell_rows(cols, vals, start, block)
    products = [vb * (_rows(v, s, block) * live)[:, None] for v in vectors]
    if mass:
        products.append(jnp.abs(vb) * live[:, None])
    return _ell_xt(acc, cb, products, hot)


@functools.partial(jax.jit, static_argnames=("block",),
                   donate_argnums=(7, 8))
def _ell_task_block(cols, vals, y, w, alpha_read, alpha_cur, mask, acc, diff,
                    hot, start, block):
    """``_task_block`` over padded ELL: ``acc`` holds ``g`` and ``delta``."""
    s, live, cb, vb = _ell_rows(cols, vals, start, block)
    db = jnp.sum(vb * w[cb], axis=1) - _rows(y, s, block)
    mb = _rows(mask, s, block) * live
    acc, hot_part = _ell_xt(
        acc, cb, [vb * (mb * (db - _rows(a, s, block)))[:, None]
                  for a in (alpha_read, alpha_cur)], hot)
    diff = jax.lax.dynamic_update_slice_in_dim(
        diff, jnp.where(live, db, _rows(diff, s, block)), s, 0
    )
    return acc, hot_part, diff


def _ell_walk(shard, count: int, d: int, block_rows: int, block_fn,
              carry=None):
    """A padded-ELL shard's blocks through ``block_fn(acc, carry, hot,
    start, block=) -> (acc, hot_part, carry)``: ``count`` accumulators
    ``(d,)`` on the shard's device, and the blocks' sums on the hot columns
    stacked, ``(blocks, count, H)``.  Returns both, the hot columns and the
    last ``carry``."""
    dev = shard.y.device
    acc = tuple(jax.device_put(jnp.zeros(d, jnp.float32), dev)
                for _ in range(count))
    block, starts = _shard_blocks(shard, block_rows)
    hot = _hot_columns(shard, d, block)
    hot_parts = []
    for start in starts:
        acc, hot_part, carry = block_fn(acc, carry, hot, start, block=block)
        hot_parts.append(hot_part)
    return acc, jnp.stack(hot_parts), hot, carry


def _ell_sums(shard, vectors: Sequence, d: int, block_rows: int,
              mass: bool = False) -> List[np.ndarray]:
    """``X^T v`` for every ``v`` of ``vectors`` (and last, with ``mass``,
    the columns' ``sum_i |x_ic|``) over one padded-ELL shard, in ONE pass
    over its ``cols`` and ``vals``: float64 on the host, each sum read back
    once; the hot columns' entries are their blocks' sums, added in
    float64."""
    vectors = tuple(_f32(v, shard.y.device) for v in vectors)

    def block_fn(acc, _carry, hot, start, block):
        return *_ell_mean_block(shard.cols, shard.vals, acc, vectors, hot,
                                start, block=block, mass=mass), None

    acc, hot_parts, hot, _ = _ell_walk(shard, len(vectors) + mass, d,
                                       block_rows, block_fn)
    hot = np.asarray(hot)
    hot_sums = np.asarray(hot_parts, np.float64).sum(axis=0)
    out = [np.array(a, np.float64) for a in acc]
    for total, apart in zip(out, hot_sums):
        total[hot] = apart
    return out


def _width(shards: Sequence, d: Optional[int]) -> int:
    """The model's length: ``d`` where the caller says it (a padded-ELL
    shard does not know it), else a dense shard's."""
    if d is not None:
        return int(d)
    if _is_ell(shards[0]):
        raise ValueError("a padded-ELL shard does not say d: pass d=")
    return int(shards[0].X.shape[1])


def _history_sums(shards: Sequence, vector_lists: Sequence[Sequence],
                  d: Optional[int], block_rows: int,
                  mass: bool = False) -> List[np.ndarray]:
    """``sum_i v_i x_i`` over every shard, float64 on the host, for each
    list of ``vector_lists`` (``vector_lists[j][k]`` belongs to
    ``shards[k]``).  A dense shard is passed once a list, block by block to
    the host, as ever; a padded-ELL shard once for all of them, and for the
    columns' ``mass`` (``sum_i |x_ic|``, returned last) with them."""
    totals: List[Optional[np.ndarray]] = [None] * (len(vector_lists) + mass)

    def add(j, part):
        totals[j] = part if totals[j] is None else totals[j] + part

    if _is_ell(shards[0]):
        d = _width(shards, d)
        for k, shard in enumerate(shards):
            parts = _ell_sums(shard, [vs[k] for vs in vector_lists], d,
                              block_rows, mass)
            for j, part in enumerate(parts):
                add(j, part)
        return totals
    if mass:
        raise ValueError("the columns' mass is summed over padded ELL only")
    for j, vectors in enumerate(vector_lists):
        for shard, v in zip(shards, vectors):
            a = _f32(v, shard.X.device)
            block, starts = _shard_blocks(shard, block_rows)
            for start in starts:
                add(j, np.asarray(_mean_block(shard.X, a, start, block=block),
                                  np.float64))
    return totals


def history_mean(shards: Sequence, alphas: Sequence, n: int,
                 block_rows: int = BLOCK_ROWS, *,
                 d: Optional[int] = None) -> np.ndarray:
    """``sum_i alpha_i x_i / n`` over every shard, float64 on the host: what
    ``alpha_bar`` must equal.  ``alphas[k]`` is the history slice of
    ``shards[k]``.  Padded-ELL shards need ``d`` (the dataset's): for them
    the sum is a blocked scatter-add (the module's docstring)."""
    (total,) = _history_sums(shards, [alphas], d, block_rows)
    return total / n


def _history_gap(shards, alphas, alpha_bar, n, d, block_rows, mass=False):
    """``|alpha_bar - history_mean(table)|`` by column, the unit ``max |X^T
    y / n|`` and, with ``mass``, the columns' ``sum_i |x_ic| / n``."""
    mean, unit, *more = _history_sums(
        shards, [alphas, [s.y for s in shards]], d, block_rows, mass)
    off = np.abs(np.asarray(alpha_bar, np.float64) - mean / n)
    return off, np.max(np.abs(unit / n)), *(m / n for m in more)


def history_drift(shards: Sequence, alphas: Sequence, alpha_bar, n: int,
                  block_rows: int = BLOCK_ROWS, *,
                  d: Optional[int] = None) -> float:
    """How far ``alpha_bar`` is from the mean of the table it summarises:
    ``max |alpha_bar - history_mean(table)|`` over ``max |X^T y / n|``, the
    mean gradient at ``w = 0``.  The data's unit and not ``max
    |alpha_bar|``: ``alpha_bar`` goes to zero as a run converges while the
    rounding of its early updates stays.  Over padded ELL (``d`` needed)
    both vectors come from ONE pass over the shards."""
    off, unit = _history_gap(shards, alphas, alpha_bar, n, d, block_rows)
    return float(np.max(off) / unit)


def history_by_column(shards: Sequence, alphas: Sequence, alpha_bar, n: int,
                      block_rows: int = BLOCK_ROWS, *,
                      d: int) -> Dict[str, float]:
    """A padded-ELL table's two readings from ONE pass over the shards:
    ``drift``, which is :func:`history_drift`'s, and ``by_column``, the
    same gap ``|alpha_bar - history_mean(table)|`` taken column by column
    in the column's OWN unit, its mean absolute value ``sum_i |x_ic| / n``
    (slot by slot), the largest over the columns that hold anything.

    ``drift``'s one unit is the heaviest column's.  Where one column fills
    7.4% of the slots (criteo's shape on the v5e, PR 45) the vector that
    advances ``alpha_bar`` rounded to bf16 on EVERY accept moves ``drift``
    from 2.9e-6 to 3.3e-6 (what the float32 chain of the program's own
    scatter-add leaves on that column in the first cohort's deltas) to no
    more than 3.8e-6 to 2.2e-5: no limit holds both.  A light column's
    entry of ``alpha_bar`` is the sum of a few deltas, whose rounding
    nothing averages away: ``by_column`` reads 7.4e-7 to 8.1e-7 on sound
    runs (on the heaviest column) and 3.8e-4 to 6.3e-4 under that
    control."""
    off, unit, mass = _history_gap(shards, alphas, alpha_bar, n, d,
                                   block_rows, mass=True)
    held = mass > 0
    return {"drift": float(np.max(off) / unit),
            "by_column": float(np.max(off[held] / mass[held]))}


def task(shard, w, alpha_read, alpha_cur, mask,
         block_rows: int = BLOCK_ROWS) -> Dict[str, jax.Array]:
    """One task and its accept on one shard: ``g``, ``diff``, ``delta`` and
    the committed slice ``alpha``, f32 arrays on the shard's device."""
    rows = int(shard.y.shape[0])
    dev = shard.y.device
    w, alpha_read, alpha_cur, mask = (
        _f32(a, dev) for a in (w, alpha_read, alpha_cur, mask)
    )
    diff = jax.device_put(jnp.zeros(rows, jnp.float32), dev)
    if _is_ell(shard):
        acc, hot_parts, hot, diff = _ell_walk(
            shard, 2, int(w.shape[0]), block_rows,
            functools.partial(_ell_task_block, shard.cols, shard.vals,
                              shard.y, w, alpha_read, alpha_cur, mask),
            carry=diff)
        g, delta = (a.at[hot].set(apart)
                    for a, apart in zip(acc, jnp.sum(hot_parts, axis=0)))
    else:
        block, starts = _shard_blocks(shard, block_rows)
        g = delta = None
        for start in starts:
            gb, db, diff = _task_block(shard.X, shard.y, w, alpha_read,
                                       alpha_cur, mask, diff, start,
                                       block=block)
            g = gb if g is None else g + gb
            delta = db if delta is None else delta + db
    return {"g": g, "diff": diff, "delta": delta,
            "alpha": jnp.where(mask > 0, diff, alpha_cur)}


def saga_replay(shards: Sequence, masks: Sequence, order: Sequence[int],
                gamma: float, batch_rate: float, n: int, group: int = 1,
                w0: Optional[np.ndarray] = None,
                block_rows: int = BLOCK_ROWS, *,
                d: Optional[int] = None) -> Dict[str, object]:
    """Sequential SAGA with the exact table delta.

    ``order[i]`` is the shard (worker) of the ``i``-th accepted result and
    ``masks[i]`` its Bernoulli mask over that shard's rows.  ``group``
    consecutive results make one update: every task of a group reads the
    model and its slice as they are when the group starts, the commits
    follow in order (each delta against the slice as it is THEN), and the
    model takes one step with the summed ``g`` over ``par_recs = batch_rate
    * n * group / len(shards)``.  ``group=1`` is the asynchronous engine
    with no task in flight across an update; ``group=len(shards)`` with
    every shard once a group is the synchronous drain.  Padded-ELL shards
    need ``d`` (or ``w0``).  Returns ``w``, ``alpha_bar`` (float32 arrays
    on the host) and ``alpha``, a list of the slices."""
    if len(order) % group or len(masks) != len(order):
        raise ValueError("order and masks must hold whole groups")
    home = shards[0].y.device
    d = _width(shards, len(w0) if d is None and w0 is not None else d)
    w = _f32(np.zeros(d) if w0 is None else w0, home)
    alpha_bar = _f32(np.zeros(d), home)
    alpha: List[jax.Array] = [
        jax.device_put(jnp.zeros(int(s.y.shape[0]), jnp.float32), s.y.device)
        for s in shards
    ]
    par_recs = batch_rate * n * group / len(shards)
    for at in range(0, len(order), group):
        read = list(alpha)  # the slices as the group's tasks were handed them
        g_sum = jnp.zeros(d, jnp.float32)
        delta_sum = jnp.zeros(d, jnp.float32)
        for k, mask in zip(order[at:at + group], masks[at:at + group]):
            out = task(shards[k], w, read[k], alpha[k], mask, block_rows)
            alpha[k] = out["alpha"]
            g_sum = g_sum + jax.device_put(out["g"], home)
            delta_sum = delta_sum + jax.device_put(out["delta"], home)
        w = w - (gamma / par_recs) * g_sum - gamma * alpha_bar
        alpha_bar = alpha_bar + delta_sum / n
    return {"w": np.asarray(w), "alpha_bar": np.asarray(alpha_bar),
            "alpha": [np.asarray(a) for a in alpha]}
