"""Sparse (rcv1-class) device-resident sharded dataset.

Parity: the reference loads rcv1_full.binary (47,236 features, ~0.16% dense)
through ``MLUtils.loadLibSVMFile`` into sparse vectors and runs the same
ASGD/ASAGA recipes on it (``README.md:44-46,64``).

TPU-first representation: CSR's ragged rows defeat XLA's static-shape
compilation, and densifying rcv1 is impossible (47k x 700k f32 = 131 GB).
Each shard is stored as **padded ELL**: per-row fixed-width ``cols (n_p, K)``
/ ``vals (n_p, K)`` arrays where ``K`` is the shard's max row nnz rounded up
to a multiple of 8 (a sublane tile); padding entries have ``col=0, val=0``
so they contribute exactly zero to every product.  A row's values are
packed to the left, so the ELL columns from the shard's **live width** on
(the most slots any of its rows fills: ``SparseShard.live_width``, recorded
where the rows are packed) hold padding in EVERY row: kdd2012's 11 values
a row live in 16 slots, criteo's 39 in 40.  The sparse programs are built
with the dataset's live width (``SparseShardedDataset.live_width``) and
read the shard's first ``live_width`` ELL columns, a contiguous prefix of
an array stored rows minor; the stored arrays keep their shape and bytes.

Rows of UNEQUAL length (text, URLs, logs: LIBSVM ``webspam``'s rows hold 256
to 16,384 values around a mean of 3,728) are dealt to the shards in order of
their length (``nnz_partition=True`` for a loaded file; a generator given a
row-length law, ``row_nnz``, deals so itself), so that a shard is as wide as
ITS longest row and the stored slots follow the non-zeros, not the one
longest row of the set.  A shard stays ONE ``(rows, K)`` pair of arrays, and
``K`` then differs from shard to shard.  From one lane tile on ``K`` is whole
lane tiles of 128 (:func:`_round_up`): the device stores such a shard
row-major, a sampled row is one contiguous read, and the programs read it
whole (``SparseShardedDataset.live_widths``: by stored width, what a
program reads), the ASGD step its packed sample up to each row tile's last
non-zero (``ops.gradients.walk_tile``).  ASGD builds its step and its
evaluation once for every shard SHAPE; the programs that are built for ONE
shape (ASAGA's sparse step, ``ps_dcn``'s steps) keep the dataset's ONE
integer and read a narrower shard whole too.
The worker step then needs no dynamic shapes:

- residual: ``r_i = sum_k vals[i,k] * w[cols[i,k]] - y_i``  (gather + reduce)
- gradient: ``g = scatter_add(zeros(d), cols, vals * coeff[:, None])``

both of which XLA compiles to static gather/scatter kernels.  This is the
SURVEY section-7 "densify per batch" alternative done one better: the batch
is never densified at all; only the (d,) gradient is dense, which the
parameter server needs dense anyway.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

from asyncframework_tpu.data.sharded import balanced_sizes


def _round_up(k: int) -> int:
    """The stored width of rows that fill ``k`` slots at the most: whole
    sublane tiles of 8 and, from one lane tile on, whole lane tiles of
    128.  The TPU stores a shard whose width is no multiple of 128 rows
    minor (no row is padded to the lane tile: 40 slots stay 40), which a
    sampled row is then gathered from slot by slot; one that is, row-major,
    a sampled row one contiguous read.  Rows thousands of slots wide pay
    64 slots each on average for that."""
    mult = 8 if k <= 120 else 128
    return max(mult, -(-k // mult) * mult)


def _zipf_ranks(key, shape, d: int, s: float):
    """Ranks ``0 .. d-1`` (uint32) with ``P(rank r) ~ (r + 1)^-s``, by the
    inverse of the continuous law ``x^-s`` on ``[1/2, d + 1/2)`` rounded to
    the nearest rank: one uniform draw a slot and no table, so that 917M
    slots cost no gather.  At ``s`` = 1 that is ``x = (2 d + 1)^u / 2``:
    ``P(r) = ln((2 r + 3) / (2 r + 1)) / ln(2 d + 1)``, within 9% of
    Zipf's ``1 / ((r + 1) H_d)`` at the hottest rank and 1.5% from the
    second on (d = 1e6)."""
    import jax.numpy as jnp

    u = jax.random.uniform(key, shape, jnp.float32)
    lo, hi = 0.5, d + 0.5
    if s == 1.0:
        x = lo * jnp.exp(u * np.log(hi / lo))
    else:
        a, b = lo ** (1.0 - s), hi ** (1.0 - s)
        x = (a + u * (b - a)) ** (1.0 / (1.0 - s))
    return jnp.clip(jnp.floor(x + 0.5), 1, d).astype(jnp.uint32) - 1


def _zipf_share(ranks, d: int, s: float):
    """``P(rank)`` of :func:`_zipf_ranks`, f32: the law's mass on
    ``[rank + 1/2, rank + 3/2)``."""
    import jax.numpy as jnp

    lo, hi = 0.5, d + 0.5
    a, b = ranks.astype(jnp.float32) + 0.5, ranks.astype(jnp.float32) + 1.5
    if s == 1.0:
        return jnp.log(b / a) / np.log(hi / lo)
    e = 1.0 - s
    return (b ** e - a ** e) / (hi ** e - lo ** e)


def _column_bijection(d: int, seed: int) -> Tuple[int, int]:
    """``(mult, shift)`` of the seeded bijection ``r -> (mult * r + shift)
    mod d`` that scatters the Zipf ranks over the columns (a hashed
    feature's column says nothing of its frequency): ``mult`` is coprime
    to ``d`` and small enough that ``mult * r + shift`` fits 32 unsigned
    bits, so the map is elementwise arithmetic on the device and not a
    gather of a ``d``-entry permutation (7.3 ns a slot on the v5e)."""
    import math

    top = (2**32 - d) // d
    if top < 1:
        raise ValueError(f"d = {d} is too wide for 32-bit column arithmetic")
    rs = np.random.default_rng(seed)
    mult = int(rs.integers(top // 2 + 1, top + 1))
    while math.gcd(mult, d) != 1:
        mult -= 1  # 1 is coprime to every d
    return mult, int(rs.integers(0, d))


def _row_lengths(n: int, mean: int, law: Mapping[str, float],
                 seed: int) -> np.ndarray:
    """``n`` row lengths (int64, on the host) under ``law``: ``{"law":
    "lognormal", "sigma", "min", "max"}`` is ``clip(round(c * exp(sigma *
    z)), min, max)`` with ``z`` ~ N(0, 1) drawn from ``seed`` and ``c``
    found by bisection so that the mean AFTER rounding and clipping is
    ``mean`` (to a slot in ``n`` rows: the sum moves by whole slots)."""
    if law.get("law") != "lognormal":
        raise ValueError(f"unknown row-length law {law.get('law')!r}")
    lo, hi = int(law["min"]), int(law["max"])
    if not 0 < lo <= mean <= hi:
        raise ValueError(f"row lengths {lo}..{hi} cannot average {mean}")
    z = np.random.default_rng([seed, 0x4C454E]).standard_normal(n)  # "LEN"
    spread = np.exp(float(law["sigma"]) * z)

    def lengths(c):
        return np.clip(np.rint(c * spread), lo, hi).astype(np.int64)

    a, b = lo / spread.max(), hi / spread.min()  # all rows lo .. all rows hi
    for _ in range(64):
        c = np.sqrt(a * b)
        a, b = (c, b) if lengths(c).mean() < mean else (a, c)
    return lengths(b)


@dataclass
class SparseShard:
    worker_id: int
    cols: jax.Array  # (n_p, K) int32, padded with 0
    vals: jax.Array  # (n_p, K) f32, padded with 0.0
    y: jax.Array     # (n_p,)
    start: int
    size: int
    #: the most slots any row fills: ELL columns ``live_width .. K`` hold
    #: ``col=0, val=0`` in every row (the builders pack a row's values to
    #: the left and record this from the host integers they pack by)
    live_width: int
    #: the slots its rows fill, from the same integers: what the shard
    #: holds of the data (``rows * K`` is what it holds of the device)
    nnz: int
    #: the slots EACH row fills, the same integers on the host (``None``:
    #: every row fills ``live_width``): what a step that stops at a row
    #: tile's last non-zero walks (``steps.sparse_walked_slots``)
    row_lengths: Optional[np.ndarray] = None

    @property
    def device(self):
        return self.vals.device


class SparseShardedDataset:
    """Immutable row-sharded CSR data in padded-ELL device residency: one
    ``(rows, K)`` pair of arrays a shard.  ``K`` is one number where every
    row has one length or the rows are dealt in file order, and the
    shard's own where they are dealt by length (``nnz_partition``, a
    generator's ``row_nnz``): ASGD then builds a step and an evaluation
    for each shard shape (:attr:`live_widths`), so a worker pays for its
    own shard's slots, a wide shard's steps take longer than a narrow
    one's, and a cohort's steps go to the device shortest first
    (``solvers.instrumentation.DispatchTurns``)."""

    is_sparse = True

    @classmethod
    def generate_on_device(
        cls,
        n: int,
        d: int,
        nnz_per_row: int,
        num_workers: int,
        devices: Optional[Sequence] = None,
        seed: int = 42,
        noise: float = 0.01,
        column_skew: float = 0.0,
        unit_values: bool = False,
        bernoulli_labels: Optional[Mapping[str, float]] = None,
        row_nnz: Optional[Mapping[str, float]] = None,
        row_values: Optional[Mapping[str, float]] = None,
    ) -> "SparseShardedDataset":
        """Synthesize a planted rcv1-shaped sparse problem directly in HBM.

        Each row has ``nnz_per_row`` entries at uniform random columns with
        values N(0, 1/nnz), so ``E[x x^T] = I/d`` -- the same conditioning as
        the dense generator, which keeps step-size tuning commensurable
        across bench configs.  Labels are ``x . w* + noise`` computed on
        device.  Rows are padded to a lane multiple exactly like the CSR
        path; padding slots carry ``col=0, val=0``.

        Three arguments give the shape of a hashed one-hot click log (LIBSVM
        ``criteo``) in place of rcv1's; at their defaults the arrays are the
        ones above, byte for byte:

        - ``column_skew`` ``s`` > 0: a slot's column is Zipf(``s``) over a
          seeded bijection of the ``d`` columns, drawn in closed form on
          the device (:func:`_zipf_ranks`; at ``s`` = 1 and ``d`` = 1e6
          the hottest column takes 7.6% of all slots, so a row may hold a
          column more than once, which padded ELL adds up like any other).
          Under skew a planted weight shrinks with its column's share
          ``p`` of the slots, ``w*_c ~ N(0, 1) / sqrt(max(1, d p))``: no
          column carries more of the margins' variance than a column of
          average frequency would (a feature most rows hold cannot move
          every row's log-odds by a whole unit, and with N(0, 1) on the
          few hottest columns the whole descent hangs on their draw:
          four seeds crossed one target after 9 to 47 updates).
        - ``unit_values``: every live slot holds ``1 / sqrt(nnz_per_row)``,
          so each row's stored values have unit length.
        - ``bernoulli_labels`` ``{"scale", "positive_share"}``: labels in
          {0, 1}, ``y ~ Bernoulli(sigmoid(scale * (x . w* + noise) +
          bias))``, one ``bias`` for the whole dataset, found on the device
          so that ``positive_share`` of shard 0's labels are 1 in
          expectation (of every shard's, under ``row_nnz``; the planted
          margins' mean moves with the hot columns' weights, seed by seed;
          a fixed bias would move the share with it).

        Two more give rows of UNEQUAL length and real values (n-gram text:
        LIBSVM ``webspam``); at their defaults, again, nothing moves:

        - ``row_nnz`` ``{"law": "lognormal", "sigma", "min", "max"}``: a
          LENGTH for each row (:func:`_row_lengths`), ``nnz_per_row`` the
          mean after clipping.  The rows are dealt to the shards in order
          of their length, as ``nnz_partition=True`` deals a loaded file's
          (``row_perm``; equal row counts a shard), and shard ``w`` is
          stored ``K_w`` wide, its own longest row rounded up
          (:func:`_round_up`): the stored slots follow the non-zeros.  The
          values' scale (``unit_values``, the N(0, 1/nnz) default) goes by
          the row's own length.
        - ``row_values`` ``{"law": "lognormal", "sigma"}``: a live slot's
          raw weight is ``exp(N(0, sigma^2))`` and the row is divided by
          its own length (term frequencies, "each instance normalised to
          unit length").  A column drawn twice in a row stays and adds up.
        """
        import functools

        import jax.numpy as jnp

        obj = cls.__new__(cls)
        sizes = balanced_sizes(n, num_workers)
        obj.n, obj.d, obj.num_workers = n, int(d), num_workers
        devs = list(devices) if devices is not None else jax.devices()
        cum = np.concatenate([[0], np.cumsum(sizes)])
        obj.partition_cum = [int(c) for c in cum]
        if row_values is not None and (
                unit_values or row_values.get("law") != "lognormal"):
            raise ValueError(
                f"row_values {dict(row_values)!r}: the one law is "
                f"'lognormal', and unit_values is another")
        if row_nnz is None:
            obj.row_perm = np.arange(n)
            lengths = None
            live_widths = [int(nnz_per_row)] * num_workers
        else:
            all_nnz = _row_lengths(n, int(nnz_per_row), row_nnz, seed)
            obj.row_perm = np.argsort(all_nnz, kind="stable")
            lengths = [all_nnz[obj.row_perm[cum[w]:cum[w + 1]]]
                       for w in range(num_workers)]
            live_widths = [int(ln.max()) for ln in lengths]
        assert min(live_widths) > 0, live_widths

        scale = positive = None
        if bernoulli_labels is not None:
            scale = float(bernoulli_labels["scale"])
            positive = float(bernoulli_labels["positive_share"])
        # the seeded bijection's two numbers reach the device as DATA: baked
        # into the program they would make every seed its own executable
        # (12 s of compile a run at a criteo shard, v5e, PR 32)
        bijection = jnp.asarray(
            _column_bijection(d, seed) if column_skew else (1, 0), jnp.uint32
        )

        def to_columns(ranks, bijection):
            return (ranks * bijection[0] + bijection[1]) % jnp.uint32(d)

        @functools.partial(jax.jit, static_argnums=(4, 5))
        def gen_shard(key, w_true, bijection, lengths, size, K):
            """``(cols, vals, margins)``: the planted ``x . w* + noise``,
            ``K`` slots a row stored.  ``lengths``: the rows' own, as DATA
            (None: every row ``nnz_per_row``): a program for each stored
            shape, not for each draw of the lengths."""
            kc, kv, kn = jax.random.split(key, 3)
            if lengths is None:
                live = (jnp.arange(K) < int(nnz_per_row))[None, :]
                per_row = float(nnz_per_row)
            else:
                live = jnp.arange(K)[None, :] < lengths[:, None]
                per_row = lengths[:, None].astype(jnp.float32)
            if column_skew:
                ranks = _zipf_ranks(kc, (size, K), d, column_skew)
                cols = to_columns(ranks, bijection).astype(jnp.int32)
            else:
                cols = jax.random.randint(kc, (size, K), 0, d, jnp.int32)
            if unit_values:
                vals = jnp.full((size, K), per_row ** -0.5, jnp.float32)
            elif row_values is not None:
                vals = jnp.where(live, jnp.exp(float(row_values["sigma"]) * (
                    jax.random.normal(kv, (size, K), jnp.float32))), 0.0)
                vals = vals * jax.lax.rsqrt(
                    jnp.sum(vals * vals, axis=1, keepdims=True))
            else:
                vals = jax.random.normal(
                    kv, (size, K), jnp.float32
                ) / jnp.sqrt(per_row)
            cols = jnp.where(live, cols, 0)
            vals = jnp.where(live, vals, 0.0)
            yp = jnp.sum(vals * w_true[cols], axis=1) + noise * (
                jax.random.normal(kn, (size,), jnp.float32)
            )
            return cols, vals, yp

        @jax.jit
        def draw_labels(key, margins, bias):
            p = jax.nn.sigmoid(scale * margins + bias)
            return jax.random.bernoulli(key, p).astype(jnp.float32)

        @jax.jit
        def find_bias(margins):
            """Bisection: ``mean(sigmoid(scale * m + bias)) = positive``."""
            def halve(_i, lo_hi):
                lo, hi = lo_hi
                mid = 0.5 * (lo + hi)
                low = jnp.mean(
                    jax.nn.sigmoid(scale * margins + mid)) < positive
                return jnp.where(low, mid, lo), jnp.where(low, hi, mid)

            lo, hi = jax.lax.fori_loop(
                0, 40, halve, (jnp.float32(-40.0), jnp.float32(40.0))
            )
            return 0.5 * (lo + hi)

        root = jax.random.fold_in(jax.random.PRNGKey(seed), 0x53505253)  # "SPRS"
        w_true = jax.random.normal(
            jax.random.fold_in(root, 2**30), (d,), jnp.float32
        )
        if column_skew:
            ranks = jnp.arange(d, dtype=jnp.uint32)
            share = _zipf_share(ranks, d, column_skew)
            w_true = jnp.zeros(d, jnp.float32).at[
                to_columns(ranks, bijection)
            ].set(w_true / jnp.sqrt(jnp.maximum(1.0, d * share)))
        keys, made = {}, {}
        # the widest shard first, while the device is emptiest: its
        # generator's temporaries are several times the shard (a 21,875 x
        # 16,384 webspam shard is 2.9 GB), and one shard is made at a time
        for w in sorted(range(num_workers), key=lambda w: -live_widths[w]):
            dev = devs[w % len(devs)]
            keys[w] = jax.device_put(jax.random.fold_in(root, w), dev)
            made[w] = gen_shard(
                keys[w], jax.device_put(w_true, dev),
                jax.device_put(bijection, dev),
                None if lengths is None else jax.device_put(
                    jnp.asarray(lengths[w], jnp.int32), dev),
                sizes[w], _round_up(live_widths[w]),
            )
            if lengths is not None:
                jax.block_until_ready(made[w])
        obj.shards = {}
        if bernoulli_labels is not None:
            # shard 0 speaks for every shard where the rows are of one
            # length; dealt by length it holds the SHORTEST rows, whose
            # margins are not the set's (the share of ones then moved by
            # 0.005 from seed to seed, and the whole trajectory with the
            # labels' entropy: 0.3% of f(0), PR 39), so there every
            # shard's margins are asked
            asked = range(num_workers) if lengths is not None else (0,)
            bias = find_bias(jnp.concatenate([
                jax.device_put(made[w][2], devs[0]) for w in asked]))
        for w in range(num_workers):
            cols, vals, yp = made.pop(w)
            if bernoulli_labels is not None:
                yp = draw_labels(
                    jax.random.fold_in(keys[w], 1), yp,
                    jax.device_put(bias, yp.device)
                )
            obj.shards[w] = SparseShard(
                worker_id=w, cols=cols, vals=vals, y=yp,
                start=obj.partition_cum[w], size=sizes[w],
                live_width=live_widths[w],
                nnz=(sizes[w] * live_widths[w] if lengths is None
                     else int(lengths[w].sum())),
                row_lengths=None if lengths is None else lengths[w],
            )
        return obj

    #: warn when a shard's padded footprint exceeds its true nnz by this
    #: factor AND the max/mean row-nnz ratio exceeds SKEW_RATIO -- one dense
    #: outlier row multiplies the whole shard's HBM cost under padded ELL
    PAD_OVERHEAD_WARN = 4.0
    SKEW_RATIO_WARN = 8.0

    def __init__(
        self,
        indptr: np.ndarray,
        indices: np.ndarray,
        values: np.ndarray,
        y: np.ndarray,
        d: int,
        num_workers: int,
        devices: Optional[Sequence] = None,
        nnz_partition: bool = False,
    ):
        """``nnz_partition=True`` assigns rows to shards in row-nnz-sorted
        order (a stable permutation, recorded in ``row_perm``) so each
        shard's pad width tracks its own densest row instead of the global
        outlier -- the skew guard's *fix*.  Statistically neutral for the
        solvers (workers Bernoulli-sample within their shard either way);
        ``start``/``partition_cum`` then index the permuted order, and
        shard ``j``'s original row id is ``row_perm[start + j]``.  The
        shards then differ in width: ASGD builds its step and evaluation
        for each (:attr:`live_widths`); ASAGA and the DCN steps read every
        shard at the dataset's ONE live width.  Without
        it, a skewed matrix still loads but emits a detailed warning
        (``skew_report``).
        """
        n = len(indptr) - 1
        if y.shape[0] != n:
            raise ValueError(f"indptr implies {n} rows but y has {y.shape[0]}")
        self.n, self.d, self.num_workers = n, int(d), num_workers
        sizes = balanced_sizes(n, num_workers)
        devs = list(devices) if devices is not None else jax.devices()
        cum = np.concatenate([[0], np.cumsum(sizes)])
        self.partition_cum: List[int] = [int(c) for c in cum]
        self.shards: Dict[int, SparseShard] = {}
        indptr = np.asarray(indptr, np.int64)
        all_nnz = indptr[1:] - indptr[:-1]
        if nnz_partition:
            self.row_perm = np.argsort(all_nnz, kind="stable")
        else:
            self.row_perm = np.arange(n)
        y = np.asarray(y, np.float32)
        for w in range(num_workers):
            lo, hi = self.partition_cum[w], self.partition_cum[w + 1]
            rows = self.row_perm[lo:hi]
            row_nnz = all_nnz[rows]
            live_width = max(1, int(row_nnz.max())) if len(row_nnz) else 1
            K = _round_up(live_width)
            size = hi - lo
            cols = np.zeros((size, K), np.int32)
            vals = np.zeros((size, K), np.float32)
            # vectorized CSR -> ELL packing (a Python per-row loop would be
            # an interpreter-speed O(n) pass on exactly the rcv1-scale data
            # this class exists for): the shard's j-th nonzero comes from
            # source position indptr[row]+slot and lands at (row, slot)
            total = int(row_nnz.sum())
            if total > 0:
                dst_rows = np.repeat(np.arange(size), row_nnz)
                slots = np.arange(total) - np.repeat(
                    np.cumsum(row_nnz) - row_nnz, row_nnz
                )
                src = np.repeat(indptr[rows], row_nnz) + slots
                assert int(slots.max()) < live_width <= K, (live_width, K)
                cols[dst_rows, slots] = indices[src]
                vals[dst_rows, slots] = values[src]
            dev = devs[w % len(devs)]
            self.shards[w] = SparseShard(
                worker_id=w,
                cols=jax.device_put(cols, dev),
                vals=jax.device_put(vals, dev),
                y=jax.device_put(y[rows], dev),
                start=lo,
                size=size,
                live_width=live_width,
                nnz=total,
                row_lengths=row_nnz,
            )
        # the guard only *suggests* nnz_partition when it is off; with it on,
        # residual padding is inherent (a dense row among light rows in the
        # same shard) and re-warning would be noise
        if not nnz_partition:
            self._maybe_warn_skew(all_nnz)

    # ----------------------------------------------------------- skew guard
    def skew_report(self) -> Dict[str, float]:
        """Padding-cost accounting: the true nnz, what padded ELL actually
        occupies, and the worst per-shard max/mean row-nnz ratio."""
        true_nnz = 0
        padded = 0
        worst_ratio = 0.0
        for s in self.shards.values():
            v = np.asarray(s.vals)
            row_nnz = np.count_nonzero(v, axis=1)
            true_nnz += int(row_nnz.sum())
            padded += int(np.prod(v.shape))
            mean = max(float(row_nnz.mean()), 1e-9)
            worst_ratio = max(worst_ratio, float(row_nnz.max()) / mean)
        return {
            "nnz": true_nnz,
            "padded_nnz": padded,
            "pad_overhead": padded / max(true_nnz, 1),
            "worst_shard_skew": worst_ratio,
        }

    def _maybe_warn_skew(self, all_nnz: np.ndarray) -> None:
        """rcv1-class real data is skewed: one dense row pads the whole
        shard to its width.  Computed from host-side CSR stats (free) --
        not :meth:`skew_report`, which reads device buffers back."""
        import warnings

        padded = sum(int(np.prod(s.vals.shape)) for s in self.shards.values())
        true_nnz = max(int(all_nnz.sum()), 1)
        overhead = padded / true_nnz
        mean = max(float(all_nnz.mean()), 1e-9)
        skew = float(all_nnz.max()) / mean
        if overhead > self.PAD_OVERHEAD_WARN and skew > self.SKEW_RATIO_WARN:
            warnings.warn(
                f"padded-ELL overhead {overhead:.1f}x true nnz (max/mean "
                f"row nnz = {skew:.1f}): a few dense rows are inflating "
                f"every shard's pad width; rebuild with nnz_partition=True "
                f"to bound padding per shard (ASGD then builds a step for "
                f"each shard width and pays each shard's own slots)",
                RuntimeWarning,
                stacklevel=3,
            )

    # ------------------------------------------------------------------ views
    def shard(self, worker_id: int) -> SparseShard:
        return self.shards[worker_id]

    def partition_sizes(self) -> Dict[int, int]:
        return {w: s.size for w, s in self.shards.items()}

    @property
    def live_width(self) -> int:
        """The most slots any row of any shard fills: ONE integer for the
        dataset, what a program built for ONE shard shape reads (a shard
        stored narrower than this is read whole)."""
        return max(s.live_width for s in self.shards.values())

    @property
    def live_widths(self) -> Dict[int, int]:
        """``{stored width: the width a program reads}``, for a program
        built once for every shard SHAPE (``steps._live_width`` takes the
        mapping where it takes the one integer): shards of one shape share
        one executable.  A shard stored in sublane tiles (rows of 120
        slots at the most: 16, 40) is read at the most slots any row of
        the shards stored that wide fills (kdd2012 11, criteo 39: a third
        of the slots, and ONE integer where every row has one length).  A
        shard stored in lane tiles is read WHOLE: the slack is under 128
        slots of thousands (webspam: 1,560 of 1,664 on the narrowest
        shard, 1.4% over the eight), and the program is then a function of
        the shard's shape alone.  Read at the exact count, which moves by
        a few slots from one data set to the next, every seed compiled its
        own seven steps and evaluations (100 to 150 s a run, v5e, PR 39)."""
        out: Dict[int, int] = {}
        for s in self.shards.values():
            stored = int(s.cols.shape[1])
            live = stored if stored % 128 == 0 else s.live_width
            out[stored] = max(out.get(stored, 0), live)
        return out

    def _refuse_values_beyond(self, live_of: Dict[int, int]) -> None:
        """One pass on the device over what lies beyond the live width a
        program reads each shard at (``live_of``: by stored width): it
        never reads those ELL columns, so a value there would silently
        leave every product.  Refused here, where a solver builds its
        steps from the dataset."""
        import jax.numpy as jnp

        read = {w: live_of[s.vals.shape[1]] for w, s in self.shards.items()}
        beyond = {  # every shard's pass dispatched before one is read back
            w: jnp.any(s.vals[:, read[w]:] != 0)
            for w, s in self.shards.items() if read[w] < s.vals.shape[1]
        }
        bad = [w for w, found in beyond.items() if bool(found)]
        if bad:
            lives = sorted({read[w] for w in bad})
            raise ValueError(
                f"shards {bad} hold a value in an ELL column at or beyond "
                f"the live width {', '.join(map(str, lives))} the dataset "
                f"records for them: a step built from it would drop that "
                f"value"
            )

    def checked_live_width(self) -> int:
        """:attr:`live_width`, after :meth:`_refuse_values_beyond` it."""
        live = self.live_width
        self._refuse_values_beyond(
            {int(s.vals.shape[1]): live for s in self.shards.values()})
        return live

    def checked_live_widths(self) -> Dict[int, int]:
        """:attr:`live_widths`, each shard checked beyond its OWN."""
        live_of = self.live_widths
        self._refuse_values_beyond(live_of)
        return live_of

    def nnz(self) -> int:
        """True non-padding entries across all shards (for HBM accounting
        use ``padded_nnz``; padding occupies real memory)."""
        total = 0
        for s in self.shards.values():
            total += int(np.count_nonzero(np.asarray(s.vals)))
        return total

    def padded_nnz(self) -> int:
        return sum(int(np.prod(s.vals.shape)) for s in self.shards.values())

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"SparseShardedDataset(n={self.n}, d={self.d}, "
            f"workers={self.num_workers})"
        )


def densify(ds: SparseShardedDataset) -> Tuple[np.ndarray, np.ndarray]:
    """Small-fixture helper (tests / baselines): padded-ELL -> dense host X.

    Rows come back in SHARD order (the dataset's own ordering): under
    ``nnz_partition`` that is the permuted order, with original row ids in
    ``ds.row_perm`` -- X and y stay mutually consistent either way."""
    X = np.zeros((ds.n, ds.d), np.float32)
    ys = []
    for w in range(ds.num_workers):
        s = ds.shard(w)
        cols = np.asarray(s.cols)
        vals = np.asarray(s.vals)
        for j in range(s.size):
            # unbuffered accumulate: fancy += would drop duplicate indices
            # (padding shares col 0 with real entries)
            np.add.at(X[s.start + j], cols[j], vals[j])
        ys.append(np.asarray(s.y))
    return X, np.concatenate(ys)
