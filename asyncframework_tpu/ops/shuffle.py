"""Device-side shuffle for array-typed pair data.

Parity role: ``shuffle/sort/SortShuffleManager.scala:69`` -- the engine
component that moves (key, value) records to their key's partition and
reduces them there.  The reference sorts spill files and fetches blocks over
TCP because its partitions live in different JVMs; the TPU build's pair ops
normally route through the driver (data/pairs.py -- fine at control-plane
sizes).  THIS module is the data-plane path for numeric-array payloads: the
whole shuffle -- hash partitioning, bucketing, the exchange, and the
reduce -- is jitted XLA, and the exchange is ONE ``lax.all_to_all`` over a
device mesh (ICI, no host round-trip).

Pipeline (per device, all inside one shard_map):

1. map-side combine: sort local keys, segment-reduce duplicates (the
   reference's map-side ``Aggregator``),
2. bucket by target partition ``key mod P`` into a (P, cap) send buffer
   (sentinel key -1 pads unused slots),
3. ``all_to_all`` the buffers (tiled: row i of every sender lands on
   device i),
4. reduce-side: mask sentinels, sort received keys, segment-reduce into
   the output partition (padded; hosts strip sentinels on materialize).

Keys must be non-negative int32/int64 (word ids, user ids -- the shapes the
data plane exists for); arbitrary Python keys stay on the host path.
Single-device meshes skip the collective and run ONE fused
sort + segment-reduce over the concatenated blocks (a single dispatch
instead of a per-partition multi-stage pipeline).

:func:`host_reduce_by_key` is the vectorized HOST twin (numpy
bincount / sort+reduceat) for CPU backends, where the emulated collective
lost 2.4-9x to host execution (CPU rig).  The dispatch rule lives in
``data/pairs.py`` (``async.shuffle.data.plane``); the device path is not
measured on the local chip (ROADMAP Speed 9).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

SENTINEL = -1  # invalid-slot key; real keys must be >= 0

_OPS = ("sum", "max", "min")


def _identity(op: str, dtype):
    """Reduction identity valid for the VALUE dtype (inf converted to an
    int dtype is implementation-defined in XLA -- integers use iinfo
    extremes instead)."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.min if op == "max" else info.max, dtype)
    return jnp.asarray(-jnp.inf if op == "max" else jnp.inf, dtype)


def _reduce_into(seg, vals, n: int, op: str):
    init = jnp.full(n, _identity(op, vals.dtype), vals.dtype)
    at = init.at[seg]
    if op == "sum":
        return at.add(vals, indices_are_sorted=True, mode="drop")
    if op == "max":
        return at.max(vals, indices_are_sorted=True, mode="drop")
    return at.min(vals, indices_are_sorted=True, mode="drop")


def _segment_reduce(keys: jax.Array, vals: jax.Array, op: str,
                    out_cap: int) -> Tuple[jax.Array, jax.Array]:
    """Sorted segment reduction with sentinel padding.

    ``keys`` may contain SENTINEL entries (sorted to the FRONT as -1);
    output: (out_keys, out_vals) with distinct keys leading, sentinel-padded
    to ``out_cap``.
    """
    order = jnp.argsort(keys)
    sk = keys[order]
    sv = vals[order]
    valid = sk != SENTINEL
    # segment boundaries among VALID sorted keys
    first = valid & jnp.concatenate(
        [jnp.ones(1, bool), sk[1:] != sk[:-1]]
    )
    seg = jnp.cumsum(first) - 1  # -1 for leading invalid run; clamp below
    seg = jnp.where(valid, seg, out_cap)  # invalid slots dropped by mode
    out_vals = _reduce_into(seg, jnp.where(valid, sv, 0), out_cap, op)
    out_keys = jnp.full(out_cap, SENTINEL, sk.dtype).at[seg].set(
        sk, indices_are_sorted=True, mode="drop"
    )
    if op in ("max", "min"):
        out_vals = jnp.where(
            out_keys == SENTINEL, jnp.zeros((), out_vals.dtype), out_vals
        )
    return out_keys, out_vals


def _bucket(keys: jax.Array, vals: jax.Array, p: int, cap: int):
    """(P, cap) send buffers: row t holds this device's pairs for target
    partition t = key mod P, sentinel-padded."""
    t = jnp.where(keys == SENTINEL, p, keys % p)
    order = jnp.argsort(t)
    sk, sv, st = keys[order], vals[order], t[order]
    counts = jnp.bincount(st, length=p + 1)[:p]
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    col = jnp.arange(sk.shape[0]) - offsets[jnp.clip(st, 0, p - 1)]
    ok = (st < p) & (col < cap)
    # invalid entries scatter OUT OF BOUNDS and are dropped -- routing them
    # to any real slot would race a valid entry's write (duplicate-index
    # .set order is unspecified)
    rows = jnp.where(ok, st, p)
    cols = jnp.where(ok, col, 0)
    bk = jnp.full((p, cap), SENTINEL, sk.dtype).at[rows, cols].set(
        sk, mode="drop"
    )
    bv = jnp.zeros((p, cap), sv.dtype).at[rows, cols].set(sv, mode="drop")
    return bk, bv


def host_reduce_by_key(
    parts: Dict[int, Tuple[np.ndarray, np.ndarray]],
    op: str = "sum",
) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
    """Vectorized host shuffle-reduce: the same contract as
    :func:`device_reduce_by_key` (key-mod-P output partitioning) computed
    with numpy -- ``bincount`` when the key range is dense enough, else one
    stable sort + ``reduceat``.  The CPU-backend winner: ~10x the
    driver-routed dict path and well ahead of the EMULATED collective on
    10M pairs (CPU rig)."""
    if op not in _OPS:
        raise ValueError(f"op must be one of {sorted(_OPS)}, got {op!r}")
    pids = sorted(parts)
    p = len(pids)
    if p == 0:
        return {}
    ks = np.concatenate([np.asarray(parts[pid][0]) for pid in pids])
    vs = np.concatenate([np.asarray(parts[pid][1]) for pid in pids])
    if ks.size == 0:
        return {pid: (ks[:0], vs[:0]) for pid in pids}
    uk = uv = None
    if op == "sum" and ks.dtype.kind in "iu":
        kmax = int(ks.max())
        # dense-enough key space: one bincount beats the sort.  Bound the
        # count/sum temporaries by the INPUT size (not a multiple of it):
        # a sparse 40M-key space over 10M pairs would otherwise allocate
        # ~640 MB of scratch where the sort path needs none
        if kmax + 1 <= max(ks.size, 1 << 20):
            present = np.bincount(ks, minlength=kmax + 1) > 0
            inexact = False
            if vs.dtype.kind in "iu":
                # bincount's float64 weight sums silently round integer
                # totals past 2^53.  |any key's sum| <= max|v| * n, so only
                # cross to exact accumulation when that bound can round --
                # wordcount-shaped inputs (small values, many pairs) keep
                # the fast bincount path
                bound = max(abs(int(vs.min())), abs(int(vs.max()))) * ks.size
                inexact = bound >= (1 << 53)
            if inexact:
                # exact int64 accumulation (np.add.at is slower than
                # bincount, but correctness beats speed past the boundary)
                sums = np.zeros(kmax + 1, np.int64)
                np.add.at(sums, ks, vs.astype(np.int64, copy=False))
            else:
                sums = np.bincount(ks, weights=vs, minlength=kmax + 1)
            uk = np.nonzero(present)[0].astype(ks.dtype)
            uv = sums[uk].astype(vs.dtype, copy=False)
    if uk is None:
        order = np.argsort(ks, kind="stable")
        sk, sv = ks[order], vs[order]
        first = np.ones(sk.size, bool)
        first[1:] = sk[1:] != sk[:-1]
        idx = np.nonzero(first)[0]
        uk = sk[idx]
        red = {"sum": np.add, "max": np.maximum, "min": np.minimum}[op]
        uv = red.reduceat(sv, idx).astype(vs.dtype, copy=False)
    t = uk % p
    order2 = np.argsort(t, kind="stable")
    st, suk, suv = t[order2], uk[order2], uv[order2]
    bounds = np.searchsorted(st, np.arange(p + 1))
    return {
        pid: (suk[bounds[i]:bounds[i + 1]], suv[bounds[i]:bounds[i + 1]])
        for i, pid in enumerate(pids)
    }


@functools.partial(jax.jit, static_argnames=("op", "out_cap"))
def _segment_reduce_kernel(keys, vals, op, out_cap):
    return _segment_reduce(keys, vals, op, out_cap)


def device_reduce_by_key(
    parts: Dict[int, Tuple[jax.Array, jax.Array]],
    op: str = "sum",
    devices: Optional[Sequence] = None,
    distinct_hint: Optional[int] = None,
) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
    """All-device shuffle-reduce: ``{pid: (keys, vals)}`` ->
    ``{pid: (unique_keys, reduced_vals)}`` with key-mod-P partitioning.

    When the partitions sit on P distinct devices the exchange is one
    ``lax.all_to_all`` inside a shard_map over a (P,) mesh; a shared/single
    device skips the collective (the data never needed to move).  Returns
    HOST arrays with sentinels stripped (the payload boundary).

    ``distinct_hint``: an upper bound on distinct keys per partition block
    (e.g. the vocabulary size for a word count).  It caps the post-combine
    buffer sizes -- without it every stage sizes for the worst case (all
    pairs distinct, all to one target).  Too small a hint DROPS overflow
    keys; it is a capacity promise, not a suggestion.
    """
    if op not in _OPS:
        raise ValueError(f"op must be one of {sorted(_OPS)}, got {op!r}")
    pids = sorted(parts)
    p = len(pids)
    if p == 0:
        return {}
    n_max = max(int(parts[pid][0].shape[0]) for pid in pids)
    n_max = max(n_max, 1)
    key_dt = jnp.asarray(parts[pids[0]][0]).dtype
    val_dt = jnp.asarray(parts[pids[0]][1]).dtype

    devs = []
    for pid in pids:
        k = jnp.asarray(parts[pid][0])
        devs.append(list(k.devices())[0] if hasattr(k, "devices") else None)
    distinct = len(set(devs)) == p and None not in devs

    # post-combine block size: worst case n_max, capped by the caller's
    # distinct-keys promise
    comb = n_max if distinct_hint is None else min(n_max, int(distinct_hint))
    comb = max(comb, 1)
    cap = comb  # worst case: every combined pair targets one partition
    out_cap = p * cap

    if distinct and p > 1:
        # pad local blocks to one common length so every device runs the
        # same program (static shapes)
        padded_k: List[jax.Array] = []
        padded_v: List[jax.Array] = []
        for pid in pids:
            k, v = parts[pid]
            k = jnp.asarray(k)
            v = jnp.asarray(v)
            pad = n_max - k.shape[0]
            if pad:
                k = jnp.concatenate([k, jnp.full(pad, SENTINEL, key_dt)])
                v = jnp.concatenate([v, jnp.zeros(pad, val_dt)])
            padded_k.append(k)
            padded_v.append(v)
        mesh = Mesh(np.array([d for d in devs]), ("w",))
        # lazy: ops.__init__ is imported from parallel-side modules, so a
        # top-level ops -> parallel import would be cyclic
        from asyncframework_tpu.parallel.mesh import resolve_shard_map

        @functools.partial(
            resolve_shard_map(), mesh=mesh,
            in_specs=(P("w"), P("w")), out_specs=(P("w"), P("w")),
        )
        def shuffle(k, v):
            k = k.reshape(-1)
            v = v.reshape(-1)
            ck, cv = _segment_reduce(k, v, op, comb)  # map-side combine
            bk, bv = _bucket(ck, cv, p, cap)
            rk = jax.lax.all_to_all(bk, "w", split_axis=0, concat_axis=0,
                                    tiled=True)
            rv = jax.lax.all_to_all(bv, "w", split_axis=0, concat_axis=0,
                                    tiled=True)
            ok, ov = _segment_reduce(rk.reshape(-1), rv.reshape(-1), op,
                                     out_cap)
            return ok[None, :], ov[None, :]

        # assemble the global sharded views IN PLACE: every block is already
        # on its own device, so this is metadata-only (no host round-trip)
        sharding = jax.sharding.NamedSharding(mesh, P("w"))
        gk = jax.make_array_from_single_device_arrays(
            (p, n_max), sharding, [k.reshape(1, -1) for k in padded_k]
        )
        gv = jax.make_array_from_single_device_arrays(
            (p, n_max), sharding, [v.reshape(1, -1) for v in padded_v]
        )
        ok, ov = shuffle(gk, gv)
        ok_h = np.asarray(ok)
        ov_h = np.asarray(ov)
        out = {}
        for i, pid in enumerate(pids):
            keep = ok_h[i] != SENTINEL
            out[pid] = (ok_h[i][keep], ov_h[i][keep])
        return out

    # shared-device (or host-backed) path: the blocks already live
    # together, so the whole shuffle is ONE fused sort + segment-reduce
    # over the concatenated pairs (single dispatch, where a per-partition
    # pipeline pays ~3 kernel launches x P), then a tiny host split of the
    # distinct set by key mod P
    n_total = sum(int(parts[pid][0].shape[0]) for pid in pids)
    if n_total == 0:
        empty_k = np.empty(0, np.dtype(key_dt))
        empty_v = np.empty(0, np.dtype(val_dt))
        return {pid: (empty_k, empty_v) for pid in pids}
    gk = jnp.concatenate([jnp.asarray(parts[pid][0]) for pid in pids])
    gv = jnp.concatenate([jnp.asarray(parts[pid][1]) for pid in pids])
    cap_global = (n_total if distinct_hint is None
                  else min(n_total, int(distinct_hint) * p))
    ok, ov = _segment_reduce_kernel(gk, gv, op=op, out_cap=cap_global)
    ok_h = np.asarray(ok)
    ov_h = np.asarray(ov)
    keep = ok_h != SENTINEL
    uk, uv = ok_h[keep], ov_h[keep]
    t = uk % p
    return {pid: (uk[t == i], uv[t == i]) for i, pid in enumerate(pids)}
