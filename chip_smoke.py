#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python3 chip_smoke.py`` drives the ASGD/ASAGA main path once through the
entry points a user would call -- ``asyncframework_tpu.cli.main([...])``
with the reference's 13 positional arguments, and ``python -m
asyncframework_tpu.cluster`` for the multi-process path -- at the full width
of the epsilon (400,000 x 2,000 f32) and rcv1 (697,641 x 47,236 padded-ELL)
shapes, and checks what comes out: every requested update accepted, finite
and at least halved objectives, the platform stamped ``tpu``, one gradient
against float64, the Pallas kernels compiled natively against their
references (E: the dense one-pass kernels and the attention block;
E.segments: the sparse steps' sum by sorted segments at criteo's sample).  With more than one device it also asserts placement over all
of them and runs one worker process per chip.

Process model (a chip belongs to one process at a time): this parent never
imports JAX.  It rebuilds the native libraries, runs the gate and phases
A-F in ONE child that holds the chip(s), and only after that child has
exited launches the cluster, whose launcher also stays off JAX and hands
each worker process its own chip.

Every phase is fatal: there is no exception handler around any of them, and
a failed check raises.  Exit code 0 and the last stdout line ``{"ok": true,
"device": {"platform": "tpu", "kind": "...", "count": N}}`` -- exactly those
keys, the device as JAX reports it -- mean every phase passed on a TPU; the
stdout line before it, ``{"report": {...}}``, carries the versions, the
compile cache, the native build and every phase's record.  With no TPU (or
run alone, outside the repo) it exits non-zero and prints no result.

``--dry-run`` is the same flow at tiny shapes on whatever platform JAX has,
Pallas kernels in interpret mode -- what ``tests/test_chip_smoke.py`` runs on
the CPU.  Its report is marked ``"dry_run": true`` and its verdict names the
platform it ran on (``cpu``): it proves nothing about a chip.
"""

from __future__ import annotations

import contextlib
import functools
import importlib.metadata
import io
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

from asyncframework_tpu import native_build
from asyncframework_tpu.utils import devices as devices_util

REPO = os.path.dirname(os.path.abspath(__file__))
TAW_INF = "2147483647"

#: the shapes.  "full": shape, workers, gamma and batch rate of the
#: reference's epsilon and rcv1 recipes.  The sparse gamma is NOT
#: 2361.8 (= 0.05 * d, right for unit-norm rows): the CLI's synthetic
#: sparse rows carry nnz = int(density * d) = 75 N(0,1) entries, so
#: E[x x^T] = (nnz/d) I and the same contraction needs
#: gamma = 0.05 * d / nnz = 31.25 (picked on the CPU at 69,764 x 4,724,
#: where 300 updates took the objective from 6.74 to 0.014).
SIZES = {
    "full": dict(
        dense=dict(d=2000, n=400_000, iters=400, gamma=100.0, b=0.1,
                   bucket=0.7, pfreq=50),
        saga_gamma=100.0,
        sparse=dict(d=47_236, n=697_641, iters=300, gamma=31.25, b=0.05,
                    bucket=0.7, pfreq=50, density=0.0016),
        mesh_iters=200,
        kernel_grad=(50_000, 2000),       # one epsilon shard
        # one shard of mnist8m-asaga.steady and its rate: the tile-list
        # kernel reads 72% of its 7,911 lane tiles
        kernel_tiles=(1_012_500, 784, 0.01),
        # the packed sample of one criteo-logistic-asgd.steady shard: the
        # (column, product) pairs the sorted-segment sum adds into g
        kernel_segments=(145_472 * 39, 1_000_000),
        # the block ring_attention feeds chunk_attention for T = 8,192 over
        # four devices: (B, T/4, H, D)
        kernel_attn=(1, 2048, 8, 128),
    ),
    "tiny": dict(
        dense=dict(d=16, n=512, iters=80, gamma=1.0, b=0.3, bucket=0.5,
                   pfreq=10),
        # at d=16 constant-step ASAGA is unstable at ASGD's gamma when a
        # loaded host stretches staleness (seen: 1.59 -> 3.24)
        saga_gamma=0.3,
        sparse=dict(d=256, n=2048, iters=60, gamma=2.0, b=0.3, bucket=0.5,
                    pfreq=10, density=0.025),
        mesh_iters=50,
        kernel_grad=(300, 48),
        kernel_tiles=(2_500, 48, 0.01),
        kernel_segments=(300 * 8, 5_000),
        kernel_attn=(1, 64, 2, 16),
    ),
}

#: relative error of one full-shard f32 gradient at the chip's default
#: matmul precision against float64 on the host (one run on the v5e: the
#: XLA matvec showed 0.0 against precision "highest")
GRAD_TOL = 1e-3
#: dense_onepass and dense_onepass_tiles (the dense worker step's one-pass
#: kernels: the whole shard, and its listed lane tiles) against the same
#: contraction at precision "highest", relative to max |g| (and for ASAGA's
#: ``diff``, to max |diff|): f32 sums in another order, nothing rounded,
#: whatever the shard's dtype (v5e, PR 26: 4.1e-7 on this shard in f32,
#: 7.1e-7 on 1.0M x 784 in bf16; PR 25 read 7.8e-7 for f32 sums of 1M terms)
KERNEL_GRAD_TOL = 5e-6
#: segment_tiles_sum (the sparse steps' sum of (column, product) pairs by
#: sorted segments) against the float64 sum on the host, relative to max
#: |g|: every product float32 (three exact bf16 parts on the MXU), f32 sums
#: by group of 1,024 slots (v5e, PR 52: 3.4e-7 on a criteo shard's packed
#: sample, 427,277 of its 5,673,408 slots on the hottest column, and 6.4e-7
#: on a whole shard's 55,868,280; the scatter-add it replaces, one term
#: after the other, 6.2e-6 and 5.0e-5)
KERNEL_SEGMENT_TOL = 2e-6
#: chunk_attention against reference_attention at precision "highest",
#: absolute on O(1) outputs (one run on the v5e: 8.8e-3 causal; XLA's own
#: default-precision reference sat 1.1e-2 from "highest")
KERNEL_ATTN_TOL = 3e-2


class SmokeFailure(AssertionError):
    """A phase's check did not hold (never caught: it ends the run)."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def recipe(driver: str, s: dict, *extra: str) -> list:
    """The reference's 13 positional arguments for one synthetic run."""
    return [driver, "synthetic", "x", str(s["d"]), str(s["n"]), "8",
            str(s["iters"]), str(s["gamma"]), TAW_INF, str(s["b"]),
            str(s["bucket"]), str(s["pfreq"]), "0", "42", *extra]


# ------------------------------------------------- phases (chip-holding child)
def run_cli(argv: list):
    """``cli.main(argv)`` in this process; returns (summary, objectives,
    set-up seconds, run seconds).  The trajectory comes from ``--output``'s
    CSV (the summary holds only the last objective)."""
    from asyncframework_tpu import cli

    with tempfile.TemporaryDirectory() as td:
        csv = os.path.join(td, "trajectory.csv")
        out = io.StringIO()
        t0 = time.monotonic()
        with contextlib.redirect_stdout(out):
            rc = cli.main([*argv, "--quiet", "--output", csv])
        wall = time.monotonic() - t0
        with open(csv) as f:
            objs = [float(line.split(",")[1]) for line in f.readlines()[1:]]
    require(rc == 0, f"cli.main({argv[0]}) returned {rc}")
    summary = json.loads(out.getvalue().strip().splitlines()[-1])
    run_s = float(summary["elapsed_s"])
    return summary, objs, wall - run_s, run_s


def training_record(name: str, summary: dict, objs: list, setup_s: float,
                    run_s: float, requested: int, platform: str,
                    **extra) -> dict:
    """The pass rule of every training phase, and its line of the result
    (``extra`` rides along in the record)."""
    accepted = summary.get("accepted", summary.get("iterations"))
    rec = {
        "accepted": accepted, "requested": requested,
        "first_objective": objs[0], "last_objective": objs[-1],
        "setup_s": round(setup_s, 1), "run_s": round(run_s, 2),
        "platform": summary["platform"], "n_devices": summary["n_devices"],
        **extra,
    }
    log(f"phase {name}: {json.dumps(rec)}")
    require(accepted == requested,
            f"{name}: accepted {accepted} of {requested}")
    require(all(math.isfinite(o) for o in objs),
            f"{name}: non-finite objective in {objs}")
    require(objs[-1] <= 0.5 * objs[0],
            f"{name}: objective {objs[0]} -> {objs[-1]} did not halve")
    require(summary["platform"] == platform,
            f"{name}: summary says platform {summary['platform']!r}, "
            f"the gate found {platform!r}")
    return rec


def phase_train(name: str, argv: list, requested: int, platform: str) -> dict:
    summary, objs, setup_s, run_s = run_cli(argv)
    return training_record(name, summary, objs, setup_s, run_s, requested,
                           platform)


def one_shard(size: dict):
    """(shard, w): one dense shard of the ``kernel_grad`` shape on the
    first device and a random model, shared by phases D and E."""
    import jax
    import jax.numpy as jnp

    from asyncframework_tpu.data.sharded import ShardedDataset

    rows, d = size["kernel_grad"]
    shard = ShardedDataset.generate_on_device(
        rows, d, 1, devices=jax.devices()[:1], seed=42).shard(0)
    return shard, jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32)


def phase_gradient(shard, w) -> dict:
    """D: one full-shard gradient as the main path computes it (default
    matmul precision) against the same contraction at precision "highest"
    on the device and in float64 on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from asyncframework_tpu.ops.gradients import least_squares_grad_sum

    rows, d = shard.X.shape
    ones = jnp.ones(rows, jnp.float32)
    g = np.asarray(least_squares_grad_sum(shard.X, shard.y, w, ones),
                   np.float64)
    with jax.default_matmul_precision("highest"):
        g_hi = np.asarray(
            jax.jit(lambda X, y, w: X.T @ (X @ w - y))(shard.X, shard.y, w),
            np.float64)
    X64 = np.asarray(shard.X, np.float64)
    g64 = X64.T @ (X64 @ np.asarray(w, np.float64)
                   - np.asarray(shard.y, np.float64))
    scale = np.max(np.abs(g64))
    rec = {
        "shape": [rows, d],
        "rel_err_vs_float64": float(np.max(np.abs(g - g64)) / scale),
        "rel_err_vs_highest": float(np.max(np.abs(g - g_hi)) / scale),
        "tolerance": GRAD_TOL,
    }
    log(f"phase D: {json.dumps(rec)}")
    require(rec["rel_err_vs_float64"] <= GRAD_TOL
            and rec["rel_err_vs_highest"] <= GRAD_TOL,
            f"D: gradient off its reference: {rec}")
    return rec


def phase_kernels(shard, w, size: dict, interpret: bool) -> dict:
    """E: both Pallas kernels compiled by Mosaic (``interpret=False``
    everywhere but the CPU dry run) against their references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from asyncframework_tpu.ops import pallas_kernels as pk
    from asyncframework_tpu.parallel.ring import reference_attention

    rows, d = shard.X.shape
    k_mask, k_alpha = jax.random.split(jax.random.PRNGKey(2))
    mask = jax.random.bernoulli(k_mask, 0.1, (rows,)).astype(jnp.float32)
    alpha = jax.random.normal(k_alpha, (rows,), jnp.float32)

    @jax.jit
    def reference(X, y, w, mask, alpha):
        X = X.astype(jnp.float32)
        diff = X @ w - y
        return X.T @ (mask * (diff - alpha)), diff

    # the full shard in each storage dtype: the ASGD form on the f32 one,
    # ASAGA's (alpha in, diff out) on the bf16 one
    grad_err, grad_s = {}, {}
    for name, X, a in (("f32", shard.X, None),
                       ("bf16", shard.X.astype(jnp.bfloat16), alpha)):
        t0 = time.monotonic()
        g, diff = jax.jit(functools.partial(
            pk.dense_onepass, interpret=interpret))(X, shard.y, w, mask, a)
        g = np.asarray(g)
        grad_s[name] = round(time.monotonic() - t0, 2)
        with jax.default_matmul_precision("highest"):
            g_ref, diff_ref = reference(
                X, shard.y, w, mask, jnp.zeros_like(alpha) if a is None else a)
        g_ref = np.asarray(g_ref)
        grad_err[name] = float(np.max(np.abs(g - g_ref)) / np.max(np.abs(g_ref)))
        if a is not None:
            diff_ref = np.asarray(diff_ref)
            grad_err[name + "_diff"] = float(
                np.max(np.abs(np.asarray(diff) - diff_ref))
                / np.max(np.abs(diff_ref)))

    # the tile-list kernel beside it, on a bf16 shard of its own at the
    # rate that leaves lane tiles without a sampled row: ASAGA's form,
    # ``diff`` held to the reference at the rows of the tiles it lists
    # (0 at the others, by the kernel's contract)
    t_rows, t_d, t_rate = size["kernel_tiles"]
    kx, ky, ka, km = jax.random.split(jax.random.PRNGKey(6), 4)
    Xt = jax.jit(lambda k: jax.random.normal(k, (t_rows, t_d), jnp.bfloat16)
                 / jnp.bfloat16(np.sqrt(t_d)))(kx)
    yt = jax.random.normal(ky, (t_rows,), jnp.float32)
    at = jax.random.normal(ka, (t_rows,), jnp.float32)
    mt = jax.random.bernoulli(km, t_rate, (t_rows,)).astype(jnp.float32)
    wt = jnp.ones((t_d,), jnp.float32)
    t0 = time.monotonic()
    g, diff = jax.jit(functools.partial(
        pk.dense_onepass_tiles, interpret=interpret))(Xt, yt, wt, mt, at)
    g, diff = np.asarray(g), np.asarray(diff)
    tiles_s = round(time.monotonic() - t0, 2)
    with jax.default_matmul_precision("highest"):
        g_ref, diff_ref = (np.asarray(a) for a in reference(
            Xt, yt, wt, mt, at))
    listed = np.pad(np.asarray(mt), (0, -t_rows % 128)).reshape(
        -1, 128).any(axis=1)
    at_rows = listed.repeat(128)[:t_rows]
    tiles_err = {
        "g": float(np.max(np.abs(g - g_ref)) / np.max(np.abs(g_ref))),
        "diff": float(np.max(np.abs(diff - diff_ref)[at_rows])
                      / np.max(np.abs(diff_ref))),
    }
    tiles_rest = float(np.max(np.abs(diff[~at_rows]), initial=0.0))

    B, T, H, D = size["kernel_attn"]
    q, k, v = (jax.random.normal(jax.random.PRNGKey(s), (B, T, H, D),
                                 jnp.float32) for s in (3, 4, 5))
    attn_err = {}
    t0 = time.monotonic()
    # the two masks a causal ring step passes: the diagonal block
    # (lower-triangular) and a block wholly in the past (all true)
    for name, causal in (("diagonal", True), ("past", False)):
        m = (jnp.tril(jnp.ones((T, T), bool)) if causal
             else jnp.ones((T, T), bool))
        o, _m, l = pk.chunk_attention(q, k, v, m, interpret=interpret)
        out = np.asarray(o / l.transpose(0, 2, 1)[..., None])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(
                lambda q, k, v: reference_attention(q, k, v, causal=causal)
            )(q, k, v))
        attn_err[name] = float(np.max(np.abs(out - ref)))
    attn_s = time.monotonic() - t0
    rec = {
        "interpret": interpret,
        "dense_onepass": {"shape": [rows, d], "rel_err": grad_err,
                          "tolerance": KERNEL_GRAD_TOL, "seconds": grad_s},
        "dense_onepass_tiles": {
            "shape": [t_rows, t_d], "rate": t_rate,
            "tiles_listed": int(listed.sum()), "tiles": int(listed.size),
            "rel_err": tiles_err, "diff_off_the_list": tiles_rest,
            "tolerance": KERNEL_GRAD_TOL, "seconds": tiles_s},
        "chunk_attention": {"shape": [B, T, H, D], "abs_err": attn_err,
                            "tolerance": KERNEL_ATTN_TOL,
                            "seconds": round(attn_s, 2)},
    }
    log(f"phase E: {json.dumps(rec)}")
    require(max(grad_err.values()) <= KERNEL_GRAD_TOL,
            f"E: dense_onepass off its reference by {grad_err}")
    require(max(tiles_err.values()) <= KERNEL_GRAD_TOL and tiles_rest == 0.0
            and 0 < listed.sum() < listed.size,
            f"E: dense_onepass_tiles off its reference by {tiles_err}, "
            f"{tiles_rest} off the list, {listed.sum()} tiles listed")
    require(max(attn_err.values()) <= KERNEL_ATTN_TOL,
            f"E: chunk_attention off its reference by {attn_err}")
    return rec


def phase_segments(size: dict, interpret: bool) -> dict:
    """E.segments: ``pallas_kernels.segment_tiles_sum`` compiled by Mosaic
    (interpreted in the CPU dry run) at the criteo step's sample, Zipf(1)
    columns (the hottest takes 7.6% of the slots) with every 97th pair out
    of range, against the float64 sum on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from asyncframework_tpu.ops import pallas_kernels as pk

    n, d = size["kernel_segments"]
    ku, kp = jax.random.split(jax.random.PRNGKey(8))
    # rank = round((2d + 1)^u / 2), the generator's closed form
    u = jax.random.uniform(ku, (n,), jnp.float32)
    cols = jnp.clip(jnp.round(jnp.exp(u * np.log(2.0 * d + 1.0)) / 2.0)
                    .astype(jnp.int32) - 1, 0, d - 1)
    cols = jnp.where(jnp.arange(n) % 97 == 0, d + 5, cols)  # dropped
    products = jax.random.normal(kp, (n,), jnp.float32)
    kept = np.asarray(cols) < d
    want = np.bincount(np.asarray(cols)[kept],
                       weights=np.asarray(products, np.float64)[kept],
                       minlength=d)
    t0 = time.monotonic()
    g = np.asarray(jax.jit(functools.partial(
        pk.segment_tiles_sum, d=d, interpret=interpret))(cols, products))
    rec = {"interpret": interpret, "slots": n, "d": d,
           "hottest_column_slots": int(np.bincount(
               np.asarray(cols)[kept]).max()),
           "rel_err": float(np.max(np.abs(g - want)) / np.max(np.abs(want))),
           "dropped_kept_out": bool(np.all(g[want == 0] == 0)),
           "tolerance": KERNEL_SEGMENT_TOL,
           "seconds": round(time.monotonic() - t0, 2)}
    log(f"phase E.segments: {json.dumps(rec)}")
    require(rec["rel_err"] <= KERNEL_SEGMENT_TOL and rec["dropped_kept_out"],
            f"E.segments: segment_tiles_sum off the float64 sum: {rec}")
    return rec


def phase_engine_all_devices(size: dict, platform: str) -> dict:
    """F (engine path): phase A's recipe with every device, through the
    library so that placement can be asserted: shards on all devices,
    every update accepted on the driver device, memory touched on each."""
    import jax
    import numpy as np

    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.solvers import ASGD, SolverConfig

    s = size["dense"]
    devs = jax.devices()
    t0 = time.monotonic()
    ds = ShardedDataset.generate_on_device(s["n"], s["d"], 8, devices=devs,
                                           seed=42)
    cfg = SolverConfig(
        num_workers=8, num_iterations=s["iters"], gamma=s["gamma"],
        taw=int(TAW_INF), batch_rate=s["b"], bucket_ratio=s["bucket"],
        printer_freq=s["pfreq"], coeff=0.0, seed=42,
    )
    solver = ASGD(ds, None, cfg, devices=devs)
    res = solver.run()
    wall = time.monotonic() - t0
    homes = sorted({ds.shard(w).X.device.id for w in range(8)})
    shard_bytes = ds.shard(0).X.nbytes
    peaks = {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs}
    rec = training_record(
        "F.engine",
        {"accepted": res.accepted, "platform": devs[0].platform,
         "n_devices": len(devs)},
        [o for _t, o in res.trajectory], wall - res.elapsed_s,
        res.elapsed_s, s["iters"], platform,
        shard_devices=homes, driver_device=solver.driver_device.id,
        peak_bytes_in_use=peaks)
    require(len(homes) == min(8, len(devs)),
            f"F.engine: 8 shards sit on devices {homes} of {len(devs)}")
    require(np.all(np.isfinite(res.final_w)), "F.engine: non-finite model")
    if platform != "cpu":  # the CPU backend keeps no memory statistics
        require(all(p and p >= shard_bytes for p in peaks.values()),
                f"F.engine: a device never held a shard: {peaks}")
    return rec


def main_phases(dry_run: bool) -> int:
    """Gate + phases A-F in the one process that holds the chip(s); its
    report is the last stdout line, which the parent completes."""
    cache_dir = devices_util.setup_compile_cache()
    entries_before = devices_util.cache_entries(cache_dir)
    import jax
    import jaxlib

    t_start = time.monotonic()
    devs = jax.devices()
    platform = devs[0].platform
    log(f"gate: JAX {jax.__version__} found {len(devs)} x {platform} "
        f"({devs[0].device_kind})")
    if platform != "tpu" and not dry_run:
        print(f"chip_smoke: no TPU: JAX found platform {platform!r} "
              f"({devs[0].device_kind}); nothing was run", file=sys.stderr)
        return 2
    size = SIZES["tiny" if dry_run else "full"]
    dense, sparse = size["dense"], size["sparse"]
    phases = {}
    phases["A"] = phase_train(
        "A", recipe("asgd", dense, "--devices", "1"), dense["iters"],
        platform)
    phases["B"] = phase_train(
        "B", recipe("asaga", dict(dense, gamma=size["saga_gamma"]),
                    "--devices", "1"), dense["iters"], platform)
    phases["C"] = phase_train(
        "C", recipe("asgd", sparse, "--sparse", "--sparse-density",
                    str(sparse["density"])), sparse["iters"], platform)
    shard, w = one_shard(size)
    phases["D"] = phase_gradient(shard, w)
    phases["E"] = phase_kernels(shard, w, size, interpret=dry_run)
    del shard, w
    phases["E.segments"] = phase_segments(size, interpret=dry_run)
    if len(devs) > 1:
        phases["F.engine"] = phase_engine_all_devices(size, platform)
        mesh = dict(dense, iters=size["mesh_iters"])
        phases["F.mesh"] = phase_train(
            "F.mesh", recipe("sgd-mllib", mesh), mesh["iters"], platform)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    print(json.dumps({
        "device": {"platform": platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
        "dry_run": dry_run,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "cache": {"dir": cache_dir, "entries_before": entries_before,
                  "entries_after": devices_util.cache_entries(cache_dir)},
        "phases": phases,
        "main_wall_s": round(time.monotonic() - t_start, 1),
    }), flush=True)
    return 0


# ------------------------------------------------------ parent (never on JAX)
def rebuild_native() -> dict:
    """Item 8: the evidence must not rest on ``native/*.so`` that a copy of
    the disk happened to bring along -- remove them and build from the
    sources git tracks.  A missing toolchain is stated, not absorbed."""
    cxx = os.environ.get("CXX", "g++")
    toolchain = shutil.which(cxx)
    for name in native_build.SOURCES:
        for path in (native_build.lib_path(name),
                     os.path.join(native_build.native_dir(),
                                  f"{name}.flags")):
            if os.path.exists(path):
                os.remove(path)
    built = {name: native_build.ensure_built(name, quiet=False) is not None
             for name in native_build.SOURCES}
    rec = {
        "toolchain": toolchain,
        "check": {name: native_build.check_status(name)
                  for name in native_build.SOURCES},
    }
    log(f"native: {json.dumps(rec)}")
    if toolchain is None:
        log(f"native: NO TOOLCHAIN ({cxx} not found): the data plane will "
            f"run on its Python oracles and python_fallbacks will say so")
    else:
        require(all(built.values()),
                f"native: {cxx} is present but the build failed: {built}")
    return rec


def subprocess_env() -> dict:
    """The parent's environment plus the repo on the path and the resolved
    compile-cache directory (launchers that stay off JAX pass it on)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env[devices_util.CACHE_ENV] = devices_util.compile_cache_dir()
    return env


def phase_cluster(size: dict, device: dict, dry_run: bool,
                  native_built: bool) -> dict:
    """F (multi-process path): ``python -m asyncframework_tpu.cluster`` with
    one worker process per chip and the parameter server on the CPU backend
    by assignment.  Runs only after the chip-holding child has exited."""
    dense = size["dense"]
    chips = 0 if dry_run else device["count"]
    workers = max(chips, 1)
    cmd = [sys.executable, "-m", "asyncframework_tpu.cluster",
           str(workers + 1), "--chips", str(chips), "--devices-per-process",
           "1", "--", *recipe("asgd", dense)]
    log("cluster: " + " ".join(cmd))
    t0 = time.monotonic()
    res = subprocess.run(cmd, env=subprocess_env(), cwd=REPO, text=True,
                         capture_output=True, timeout=900)
    wall = time.monotonic() - t0
    sys.stderr.write(res.stderr)
    require(res.returncode == 0, f"cluster exited {res.returncode}")
    lines = res.stdout.strip().splitlines()
    summary = json.loads(lines[-1])
    objs = [float(l.strip("()").split(",")[1]) for l in lines[:-1]
            if l.startswith("(")]
    marker = "record: "
    records = [json.loads(l.split(marker, 1)[1])
               for l in res.stderr.splitlines()
               if l.startswith("async-cluster: process") and marker in l]
    rec = {
        "processes": workers + 1, "chips": chips,
        "accepted": summary["accepted"], "requested": dense["iters"],
        "done": summary["done"],
        "first_objective": objs[0] if objs else None,
        "last_objective": objs[-1] if objs else None,
        "wall_s": round(wall, 1),
        "server": {k: summary.get(k) for k in
                   ("platform", "device_kind", "assigned",
                    "python_fallbacks")},
        "workers": [{k: r.get(k) for k in
                     ("process_id", "platform", "device_kind", "n_devices",
                      "assigned", "gradients", "python_fallbacks")}
                    for r in records],
    }
    log(f"phase F.cluster: {json.dumps(rec)}")
    require(summary["done"] and summary["accepted"] == dense["iters"],
            f"F.cluster: accepted {summary['accepted']} of "
            f"{dense['iters']}, done={summary['done']}")
    require(len(objs) >= 2 and all(math.isfinite(o) for o in objs)
            and objs[-1] <= 0.5 * objs[0],
            f"F.cluster: objectives {objs}")
    require(len(records) == workers,
            f"F.cluster: {len(records)} worker records of {workers}")
    want = ([devices_util.CPU] * workers if chips == 0
            else [f"tpu:{i}" for i in range(workers)])
    got = sorted(r["assigned"] for r in records)
    require(got == want, f"F.cluster: workers assigned {got}, want {want}")
    require(summary["assigned"] == devices_util.CPU,
            f"F.cluster: server assigned {summary['assigned']!r}")
    for r in records + [summary]:
        require(r["platform"] == r["assigned"].split(":")[0],
                f"F.cluster: a role assigned {r['assigned']!r} ran on "
                f"{r['platform']!r}")
        if native_built:
            require(r["python_fallbacks"] == 0,
                    f"F.cluster: native data plane fell back to Python: {r}")
    return rec


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    dry_run = "--dry-run" in argv
    if "--main-phases" in argv:
        return main_phases(dry_run)
    t0 = time.monotonic()
    native = rebuild_native()
    cmd = [sys.executable, os.path.abspath(__file__), "--main-phases"]
    if dry_run:
        cmd.append("--dry-run")
    child = subprocess.run(cmd, env=subprocess_env(), cwd=REPO, text=True,
                           stdout=subprocess.PIPE, timeout=1100)
    if child.returncode != 0:
        print(f"chip_smoke: the chip-holding child exited "
              f"{child.returncode}; no result", file=sys.stderr)
        return child.returncode or 1
    report = json.loads(child.stdout.strip().splitlines()[-1])
    size = SIZES["tiny" if dry_run else "full"]
    report["phases"]["F.cluster"] = phase_cluster(
        size, report["device"], dry_run,
        native_built=native["toolchain"] is not None)
    report["native"] = native
    report["cache"]["entries_end"] = devices_util.cache_entries()
    report["wall_s"] = round(time.monotonic() - t0, 1)
    print(json.dumps({"report": report}), flush=True)
    # the verdict: reached only when no phase raised; these keys and no others
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
