#!/usr/bin/env python
"""Benchmark: ASGD wall-clock to target objective on the reference's three
dataset shapes -- epsilon (400k x 2000 dense f32), mnist8m (8.1M x 784 dense
bf16), rcv1 (~700k x 47,236 sparse) -- with fresh-process medians.

Metric of record (BASELINE.md): wall-clock to target loss, asynchronous SGD.
The reference repo publishes recipes but no absolute numbers (its figures
live in the IPDPS 2020 paper, arXiv:1907.08526); the per-config baseline is
derived from the reference's own recipe (BASELINE.md "Derived baseline"):
Spark's driver-mediated per-task path has a ~5 ms floor, plus gradient
compute at an optimistic 6 GFLOP/s for the recipe's 2-core executor, across
8 pipelined workers; capped by the recipe-length bound with the same
generosity ratio that put the round-1 epsilon cap at 120 s (below the 200 s
derived lower bound).

Process model: a chip belongs to one process at a time, so the parent
never imports JAX and EVERY measurement runs in a fresh subprocess
(`bench.py --config NAME`), one at a time; the parent reports per-config
MEDIANS of >= BENCH_REPEATS runs.

Workloads are planted problems generated directly in device HBM (nothing
of 3-13 GB crosses the host link).  All three share E[x x^T] = I/d
conditioning so the gamma = 0.05*d step-size rule transfers; targets are
0.1% of the initial objective -- deep enough that steady-state update
throughput decides wall-clock, a decade above each problem's noise floor.

Every run exercises the REAL framework hot path: executor threads, result
queue, tau filter, partial barrier, versioned model handles, on-device
updates.  The bf16 config stores shards in bfloat16 with f32 accumulation
(the MXU-native mixed-precision path); the sparse config runs the
padded-ELL gather/scatter kernels.

Output: ONE json line {"metric", "value", "unit", "vs_baseline", "configs",
"gflops", "mfu"}.  value = epsilon median time-to-target; vs_baseline = the
MINIMUM of the three per-config median ratios (the conservative claim: every
dataset beats its reference estimate by at least this factor); gflops/mfu =
achieved compute rate of the flop-heaviest config (mnist8m).

No chip, no benchmark: the probe must find platform `tpu` (or the one
`BENCH_PLATFORM` names), else the run prints nothing on stdout and exits
non-zero; a config that fails, wedges or cannot finish its fused arm exits
non-zero too.  Every child record carries `platform`/`device_kind`.  The
cells, metrics and bounds of the on-chip benchmark are ROADMAP Speed 1;
`chip_smoke.py` is the quick proof that the main path runs on the chip.
"""

import faulthandler
import json
import os
import statistics
import subprocess
import sys
import threading
import time
import traceback
from typing import Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)) or ".")

NUM_WORKERS = 8
SPARK_TASK_FLOOR_S = 0.005   # per-gradient driver-mediated floor (BASELINE.md)
SPARK_GFLOPS = 6e9           # optimistic 2-core executor gradient compute rate
CAP_GENEROSITY = 0.6         # epsilon: 320k * 5ms / 8 * 0.6 = 120 s (round-1 cap)
TARGET_FRACTION = 0.001
RUN_TIMEOUT_S = 240.0
CHILD_WATCHDOG_S = 420.0     # child hard-kill (a wedged device op never returns)
CHILD_TIMEOUT_S = 480.0      # parent's per-child subprocess timeout
PROBE_TIMEOUT_S = 75.0       # backend probe (a process reaches the chip in ~15 s)
PROBE_ATTEMPTS = 2
# hard bound on the WHOLE probe (all attempts + child reaping): subprocess
# timeouts alone are not enough (a killed child whose grandchild still
# holds the pipe can block the post-kill reap forever; reaping is pushed
# to a daemon thread and this deadline caps everything else)
PROBE_BUDGET_S = float(os.environ.get("BENCH_PROBE_BUDGET_S",
                                      2 * PROBE_TIMEOUT_S + 15))
TOTAL_BUDGET_S = float(os.environ.get("BENCH_TOTAL_BUDGET_S", 2400.0))
REPEATS = int(os.environ.get("BENCH_REPEATS", 3))
# per-arm watchdog: total wall one config may burn across its repeats
# (one wedging config must not eat the whole budget)
ARM_BUDGET_S = float(os.environ.get("BENCH_ARM_BUDGET_S", 900.0))

# Each config mirrors one reference dataset's shape and recipe
# (README.md:44-74; BASELINE.md).  gamma follows the 0.05*d conditioning
# rule validated in round 2 (rows ~ N(0, I/d) -> contraction ~ lr/d).
CONFIGS = {
    "epsilon": dict(
        n=400_000, d=2_000, dtype="float32", sparse=False, nnz=None,
        gamma=100.0, batch_rate=0.1, iters=5_000,
        ref_iters=320_000, ref_dims=2_000,   # README.md:64 ASGD epsilon row
    ),
    "mnist8m": dict(
        n=8_100_000, d=784, dtype="bfloat16", sparse=False, nnz=None,
        gamma=39.2, batch_rate=0.1, iters=5_000,
        ref_iters=300_000, ref_dims=784,     # README.md:64 ASGD mnist8m row
    ),
    "rcv1": dict(
        n=697_641, d=47_236, dtype="float32", sparse=True, nnz=75,
        # iters capped lower than the dense configs: target is reached by
        # ~k=300 and each sparse task costs real device milliseconds even
        # compacted -- a 5k budget would pay for nothing but drain time
        gamma=2361.8, batch_rate=0.05, iters=1_200, printer_freq=50,
        ref_iters=100_000, ref_dims=75,      # README.md:64 ASGD rcv1 row;
        # reference compute scales with nnz, not d, on sparse vectors
    ),
}

# BENCH_SCALE=small shrinks every config for off-TPU flow validation
if os.environ.get("BENCH_SCALE") == "small":
    for _name, _c in CONFIGS.items():
        _c.update(
            n=20_000, d=128, gamma=0.05 * 128, iters=600,
            nnz=(8 if _c["sparse"] else None),
        )

def _guarded(fn, what: str):
    """Local copy of utils/threads.guarded (the thread exception policy):
    the probe/reaper paths run in the parent, which stays off JAX and the
    package."""
    def _run(*a, **k):
        try:
            fn(*a, **k)
        except Exception:  # noqa: BLE001 - report, never die silently
            print(f"bench: unhandled exception in thread {what!r}",
                  file=sys.stderr, flush=True)
            traceback.print_exc()
    return _run


def emit(payload: dict) -> None:
    print(json.dumps(payload))
    sys.stdout.flush()


def telemetry_block(trajectory, updates_per_sec) -> dict:
    """Per-config statistical-efficiency record (ISSUE 7): the convergence
    curve summarized as loss at 25/50/100% of the run's wallclock plus its
    trailing-half slope, and the conf SLO rule set's static verdicts --
    BENCH_*.json captures how well the run CONVERGED, not just how fast it
    pushed updates."""
    from asyncframework_tpu.metrics import slo
    from asyncframework_tpu.metrics.timeseries import (
        loss_at_fractions,
        loss_slope,
    )

    out: dict = {}
    try:
        traj = [(t, l) for (t, l) in (trajectory or [])]
        out["loss_at"] = loss_at_fractions(traj)
        slope = loss_slope(traj)
        out["slope_per_s"] = (round(slope, 8) if slope is not None
                              else None)
        out["samples"] = len(traj)
        out["slo"] = slo.bench_verdicts(updates_per_sec, traj)
    except Exception as e:  # evidence-only: never fail the run on it
        out["error"] = f"{type(e).__name__}: {str(e)[:120]}"
    return out


class ArmObserver:
    """Per-arm cluster-observer harness for the DCN bench: a bare
    telemetry server (role ``ps`` -- the in-process PS registers its
    ``ps`` series source and ``ps_workers`` section there) scraped by a
    real ClusterObserver over HTTP while the arm runs, so every
    BENCH_*.json dcn arm carries the fleet series + derived signals the
    observer would have seen (ISSUE 14).  Never-dark: any failure
    becomes an ``{"error": ...}`` block, not a hole."""

    SERIES_KEEP = ("ps.accepted", "ps.queue_depth", "ps.max_staleness",
                   "observer.push_rate", "observer.merge_queue_depth",
                   "observer.straggler_score")

    def __init__(self):
        self.err = None
        self.srv = self.obs = None
        self._scrapes0 = 0
        try:
            from asyncframework_tpu.metrics.live import LiveUIServer
            from asyncframework_tpu.metrics.observer import (
                ClusterObserver,
                RoleTarget,
                observer_totals,
            )

            # process-global counter: delta it so each arm reports its
            # OWN scrape count, not the run's cumulative one
            self._scrapes0 = observer_totals().get("scrapes", 0)
            self.srv = LiveUIServer(None, port=0, role="ps").start()
            self.obs = ClusterObserver(
                targets=[RoleTarget(
                    "ps", "ps", f"http://127.0.0.1:{self.srv.port}")],
                interval_s=0.25, history_dir="", persist_s=0.0,
            ).start()
        except Exception as e:  # noqa: BLE001 - never-dark per arm
            self.err = f"{type(e).__name__}: {str(e)[:120]}"

    def finish(self) -> dict:
        if self.err is not None or self.obs is None:
            if self.srv is not None:
                self.srv.stop()
            return {"error": self.err or "observer harness unavailable"}
        try:
            self.obs.scrape_once()  # final fold before teardown
            snap = self.obs.fleet_snapshot()
            series = {}
            for role in self.obs.history.roles():
                per = self.obs.history.series_of(role)
                for key in self.SERIES_KEEP:
                    pts = per.get(key)
                    if pts:
                        series[f"{role}:{key}"] = {
                            "points": len(pts),
                            "first": pts[0][1], "last": pts[-1][1],
                        }
            return {
                "derived": snap.get("derived"),
                "stragglers": snap.get("stragglers"),
                "roles_up": (snap.get("derived") or {}).get("roles_up"),
                "scrapes": ((snap.get("totals") or {}).get("scrapes", 0)
                            - self._scrapes0),
                "series": series,
            }
        except Exception as e:  # noqa: BLE001 - never-dark per arm
            return {"error": f"{type(e).__name__}: {str(e)[:120]}"}
        finally:
            try:
                self.obs.stop()
                self.srv.stop()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass


#: stated tolerance for the profile-vs-trace consistency cross-check:
#: exact wire-zone milliseconds must not exceed this factor times the
#: traced stage total (p50 x count).  Loose by design -- zones count
#: BOTH sides of the loopback wire while traces are client-side, and
#: p50 x count underestimates a skewed stage -- but it catches the
#: failure class that matters: a zone accumulator whose clock math is
#: off by orders of magnitude.
PROFILE_TRACE_TOLERANCE = 3.0


def profile_block(prof_mod, stages: dict) -> dict:
    """Per-arm ``profile`` block (never-dark): zone shares + exact zone
    ms, samples collected, compile count/time, and the consistency
    cross-check of exact zone nanoseconds against the PR 3 trace-stage
    p50s (tolerance stated above)."""
    try:
        snap = prof_mod.last_snapshot()
        if not snap:
            return {"error": "ProfileUnavailable: profiler not installed"}
        zones = snap.get("zones") or {}
        zone_ms = {z: round(float(d.get("ns", 0)) / 1e6, 3)
                   for z, d in zones.items()}
        comp = snap.get("compile") or {}
        disp = snap.get("dispatch") or {}
        block = {
            "samples": snap.get("samples", 0),
            "zone_share": {z: round(float(d.get("share", 0.0)), 4)
                           for z, d in zones.items() if d.get("samples")},
            "zone_ms": zone_ms,
            "compile_count": comp.get("count", 0),
            "compile_ms": round(float(comp.get("ns", 0)) / 1e6, 1),
            "dispatch_count": disp.get("count", 0),
            "dispatch_ms": round(float(disp.get("ns", 0)) / 1e6, 1),
        }
        wire_ms = sum(v for z, v in zone_ms.items()
                      if z.startswith("wire."))
        traced_ms = sum(
            float(d.get("p50", 0.0)) * int(d.get("count", 0))
            for d in (stages or {}).values())
        tol = PROFILE_TRACE_TOLERANCE
        if traced_ms <= 0:
            block["trace_xcheck"] = {
                "ok": None, "tolerance": tol,
                "detail": "no trace stages to check against"}
        else:
            ok = wire_ms <= tol * traced_ms
            block["trace_xcheck"] = {
                "ok": ok, "tolerance": tol,
                "wire_zone_ms": round(wire_ms, 1),
                "trace_total_ms": round(traced_ms, 1),
                "detail": (f"exact wire-zone ms within {tol}x traced "
                           f"p50*count" if ok else
                           f"wire zones {wire_ms:.0f}ms exceed {tol}x "
                           f"traced {traced_ms:.0f}ms")}
        return block
    except Exception as e:  # noqa: BLE001 - never-dark discipline
        return {"error": f"{type(e).__name__}: {str(e)[:200]}"}


# --------------------------------------------------------------------- child
def arm_watchdog(config_name: str) -> None:
    """Emit a parseable failure line and hard-exit NON-ZERO if the process
    wedges (a device op blocked in C code never reaches normal interpreter
    shutdown)."""
    faulthandler.dump_traceback_later(CHILD_WATCHDOG_S - 30, file=sys.stderr)

    def fire():
        emit({"config": config_name, "ok": False,
              "note": f"WATCHDOG: wedged past {CHILD_WATCHDOG_S:.0f}s"})
        os._exit(1)

    t = threading.Timer(CHILD_WATCHDOG_S, fire)
    t.daemon = True
    t.start()


def init_devices():
    """``jax.devices()`` on the platform this benchmark is for: ``tpu``,
    or the one ``BENCH_PLATFORM`` asks for (``cpu`` for flow validation
    with BENCH_SCALE=small).  Anything else is a failure, never a
    fallback."""
    import jax

    from asyncframework_tpu.utils.devices import setup_compile_cache

    forced = os.environ.get("BENCH_PLATFORM")
    if forced:
        jax.config.update("jax_platforms", forced)
    setup_compile_cache()
    devices = jax.devices()
    want = forced or "tpu"
    if devices[0].platform != want:
        raise SystemExit(
            f"bench: wanted platform {want!r}, JAX found "
            f"{devices[0].platform!r} ({devices[0].device_kind})"
        )
    return devices


def build_dataset(cfg: dict, devices):
    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.data.sparse import SparseShardedDataset

    if cfg["sparse"]:
        return SparseShardedDataset.generate_on_device(
            cfg["n"], cfg["d"], cfg["nnz"], NUM_WORKERS,
            devices=devices, seed=7, noise=0.01,
        )
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if cfg["dtype"] == "bfloat16" else jnp.float32
    return ShardedDataset.generate_on_device(
        cfg["n"], cfg["d"], NUM_WORKERS, devices=devices, seed=7,
        noise=0.01, dtype=dtype,
    )


def spark_equal_recipe_baseline(cfg: dict, k_hit: int) -> float:
    """Reference cost to produce k_hit accepted gradients on this recipe
    (scheduling floor + compute, 8 pipelined workers), capped by the
    recipe-length bound at round-1's generosity ratio."""
    par_recs = cfg["batch_rate"] * cfg["n"] / NUM_WORKERS
    per_grad_s = SPARK_TASK_FLOOR_S + 2.0 * par_recs * cfg["ref_dims"] / SPARK_GFLOPS
    equal = k_hit * per_grad_s / NUM_WORKERS
    cap = cfg["ref_iters"] * SPARK_TASK_FLOOR_S / NUM_WORKERS * CAP_GENEROSITY
    return min(max(equal, 1e-3), cap)


def run_child(config_name: str) -> None:
    """One fresh-process measurement; prints one JSON line."""
    cfg = CONFIGS[config_name]
    devices = init_devices()
    import jax
    import jax.numpy as jnp

    from asyncframework_tpu.solvers import ASGD, SolverConfig
    from asyncframework_tpu.utils import flops as fl
    from asyncframework_tpu.utils.devices import device_stamp

    stamp = device_stamp()  # platform/device_kind/n_devices on every record
    t0 = time.monotonic()
    ds = build_dataset(cfg, devices)
    for wid in range(NUM_WORKERS):
        ds.shard(wid).y.block_until_ready()
    print(f"# {config_name}: data {cfg['n']}x{cfg['d']} "
          f"({'sparse' if cfg['sparse'] else cfg['dtype']}) generated on "
          f"device in {time.monotonic() - t0:.1f}s", file=sys.stderr)

    scfg = SolverConfig(
        num_workers=NUM_WORKERS,
        num_iterations=cfg["iters"],
        gamma=cfg["gamma"],
        taw=2**31 - 1,
        batch_rate=cfg["batch_rate"],
        bucket_ratio=0.7,
        printer_freq=cfg.get("printer_freq", 25),
        coeff=0.0,
        seed=42,
        calibration_iters=100,
        run_timeout_s=RUN_TIMEOUT_S,
        trace_sample=0.0,  # tracing only when the parent asks (BENCH_TRACE)
    )
    # latency decomposition alongside throughput (bench.py --trace-jsonl):
    # sample update lifecycles through metrics/trace.py so the BENCH
    # artifact records per-stage p50/p95/p99 and staleness-in-ms, not just
    # updates/s -- every later perf PR becomes judgeable stage by stage
    if os.environ.get("BENCH_TRACE") == "1":
        from asyncframework_tpu.metrics import trace as trace_mod

        trace_mod.reset_aggregator()
        scfg.trace_sample = float(
            os.environ.get("BENCH_TRACE_SAMPLE", "0.125")
        )
    solver = ASGD(ds, None, scfg, devices=devices)

    # warm the XLA compile caches outside the timed region (the reference's
    # first blocking iteration plays the same role for Spark's caches)
    shard = ds.shard(0)
    key = jax.random.PRNGKey(0)
    w0 = jax.device_put(np.zeros(cfg["d"], np.float32), devices[0])
    if cfg["sparse"]:
        g, _ = solver._step(shard.cols, shard.vals, shard.y, w0, key)
    else:
        g, _ = solver._step(shard.X, shard.y, w0, key)
    solver._apply(
        jax.device_put(np.zeros(cfg["d"], np.float32), devices[0]),
        jax.device_put(g, devices[0]),
        jax.device_put(np.float32(0), devices[0]),
    )
    print("# compile warm-up done", file=sys.stderr)

    # dispatch round-trip diagnostic: the floor one host-driven update
    # pays whatever the framework does
    probe = jax.device_put(np.zeros(8, np.float32), devices[0])
    t0 = time.monotonic()
    for _ in range(20):
        probe = (probe + 1.0).block_until_ready()
    rtt_ms = (time.monotonic() - t0) / 20 * 1e3
    print(f"# device dispatch round-trip ~{rtt_ms:.2f} ms", file=sys.stderr)

    # kernel-window rate, measured APART from end-to-end and labeled so.
    # Chained step->apply reps at two depths; the SLOPE
    # (T_hi - T_lo)/(hi - lo) cancels constant dispatch overhead, and
    # scaling with depth proves execution is real.
    task_fl = solver._task_flops(0)

    def chained(reps: int) -> float:
        wk = jax.device_put(np.zeros(cfg["d"], np.float32), devices[0])
        kk = jax.device_put(np.float32(0.0), devices[0])
        kkey = jax.device_put(jax.random.PRNGKey(1), devices[0])
        t0 = time.monotonic()
        for _ in range(reps):
            if cfg["sparse"]:
                gg, kkey = solver._step(
                    shard.cols, shard.vals, shard.y, wk, kkey
                )
            else:
                gg, kkey = solver._step(shard.X, shard.y, wk, kkey)
            wk, kk = solver._apply(wk, gg, kk)
        wk.block_until_ready()
        return time.monotonic() - t0

    chained(2)  # absorb first-call overhead outside both measured depths
    t_lo, t_hi = chained(8), chained(40)
    per_update_s = (t_hi - t_lo) / 32.0
    if per_update_s > 0:
        kernel_gflops = task_fl / per_update_s / 1e9
    else:  # slope lost in timer noise: kernel is too fast to resolve here
        kernel_gflops = None
        per_update_s = None
    print(f"# kernel window: {per_update_s} s/update chained "
          f"(ceiling {kernel_gflops} GFLOP/s; t8={t_lo:.3f}s "
          f"t40={t_hi:.3f}s)", file=sys.stderr)

    res = solver.run()

    trace_snap = None
    if os.environ.get("BENCH_TRACE") == "1":
        from asyncframework_tpu.metrics import trace as trace_mod

        trace_snap = trace_mod.aggregator().snapshot()

    initial = res.trajectory[0][1]
    target = initial * TARGET_FRACTION
    t_hit_traj = None
    k_hit = None
    for i, (t_ms, obj) in enumerate(res.trajectory):
        if obj <= target:
            t_hit_traj = t_ms / 1e3
            k_hit = max(i * scfg.printer_freq, 1)
            break
    # time-to-target: trajectory timestamps are host DISPATCH times (JAX
    # returns before the device finishes), so attribute wall-clock by the
    # run's fenced throughput: t_hit = k_hit / (accepted / elapsed).
    # elapsed_s is measured after a full device sync (the solvers read the
    # final model back before taking it).
    t_hit = None
    if k_hit is not None and res.accepted > 0 and res.elapsed_s > 0:
        t_hit = k_hit * res.elapsed_s / res.accepted
    gflops = res.total_flops / res.elapsed_s / 1e9 if res.elapsed_s > 0 else 0.0
    mfu = fl.mfu(res.total_flops, res.elapsed_s, devices[0])
    print(
        f"# {config_name}: accepted={res.accepted} dropped={res.dropped} "
        f"rounds={res.rounds} updates/s={res.updates_per_sec:.0f} "
        f"elapsed={res.elapsed_s:.1f}s obj {initial:.4f}->"
        f"{res.trajectory[-1][1]:.6f} target={target:.6f} t_hit={t_hit} "
        f"(traj={t_hit_traj}) gflops={gflops:.1f} mfu={mfu}",
        file=sys.stderr,
    )
    if t_hit is None:
        emit({"config": config_name, "ok": False, **stamp,
              "note": "TARGET NOT REACHED",
              "elapsed_s": round(res.elapsed_s, 2),
              "final_over_initial": res.trajectory[-1][1] / initial,
              "trace": trace_snap,
              "telemetry": telemetry_block(res.trajectory,
                                           res.updates_per_sec)})
        sys.exit(1)
    baseline = spark_equal_recipe_baseline(cfg, k_hit)

    # device-resident accept loop: the same recipe with the host dispatch
    # bound removed (taw=inf full-wave rounds fused into lax.scan on the PS
    # chip).  Recorded ALONGSIDE the engine number, both labeled.  No
    # exception handler: a fused arm that fails fails the config.
    fused = None
    if os.environ.get("BENCH_FUSED", "1") != "0":
        fres = ASGD(ds, None, scfg, devices=devices).run_fused()
        f_initial = fres.trajectory[0][1]
        f_target = f_initial * TARGET_FRACTION
        f_khit = None
        for i, (_t, obj) in enumerate(fres.trajectory):
            if obj <= f_target:
                f_khit = max(i * max(scfg.printer_freq, 1), 1)
                break
        f_thit = (
            f_khit * fres.elapsed_s / fres.accepted
            if f_khit is not None and fres.accepted else None
        )
        fused = {
            "updates_per_sec": round(fres.updates_per_sec, 1),
            "elapsed_s": round(fres.elapsed_s, 2),
            "accepted": fres.accepted,
            "t_hit": round(f_thit, 4) if f_thit is not None else None,
            "vs_baseline": (
                round(spark_equal_recipe_baseline(cfg, f_khit) / f_thit, 2)
                if f_thit else None
            ),
            "gflops": round(
                fres.total_flops / fres.elapsed_s / 1e9, 2
            ) if fres.elapsed_s > 0 else None,
        }
        print(f"# {config_name}: FUSED updates/s="
              f"{fres.updates_per_sec:.0f} t_hit={f_thit} "
              f"(engine updates/s={res.updates_per_sec:.0f})",
              file=sys.stderr)
    emit({
        "config": config_name,
        "ok": True,
        **stamp,
        "t_hit": round(t_hit, 3),
        "t_hit_traj": (round(t_hit_traj, 3) if t_hit_traj is not None
                       else None),
        "k_hit": k_hit,
        "vs_baseline": round(baseline / t_hit, 2),
        "baseline_s": round(baseline, 3),
        "updates_per_sec": round(res.updates_per_sec, 1),
        "accepted": res.accepted,
        "elapsed_s": round(res.elapsed_s, 2),
        "gflops": round(gflops, 2),           # END-TO-END: run flops/elapsed
        "mfu": (round(mfu, 6) if mfu is not None else None),
        "kernel_gflops": (round(kernel_gflops, 2)
                          if kernel_gflops is not None else None),
        "kernel_ms_per_update": (round(per_update_s * 1e3, 4)
                                 if per_update_s is not None else None),
        "fused": fused,   # device-resident accept loop, labeled apart
        "rtt_ms": round(rtt_ms, 2),
        # per-stage latency decomposition + staleness-in-ms (None unless
        # the parent ran with --trace-jsonl / BENCH_TRACE=1)
        "trace": trace_snap,
        # statistical efficiency: loss at 25/50/100% wallclock, trailing
        # slope, and the conf SLO rule set's verdicts for this run
        "telemetry": telemetry_block(res.trajectory, res.updates_per_sec),
    })


# ----------------------------------------------------------------- DCN bench
# Wire-plane microbench (always CPU: it measures the data plane, not the
# chip): the REAL ParameterServer + worker loop over loopback TCP, once per
# pull mode, recording updates/s, wire bytes per update, and pull/push
# payload shapes.  This is the artifact the delta-pull/vectored-framing/
# batched-apply overhaul is judged by.
DCN_CONFIGS = {
    # dense gradients touch every coordinate, so deltas degrade to full --
    # this config guards the "delta mode must not cost throughput" side
    "dense": dict(sparse=False, n=8192, d=2048, nnz=None, nw=4,
                  gamma=0.05 * 2048, batch_rate=0.05, iters=300),
    # rcv1-shaped: sparse pushes touch few coordinates, so consecutive
    # pulls reconstruct from small XOR deltas -- the bytes-per-update win
    "sparse": dict(sparse=True, n=4096, d=16384, nnz=8, nw=4,
                   gamma=500.0, batch_rate=0.02, iters=300),
}


def run_dcn_child() -> None:
    """One fresh-process DCN wire bench; prints one JSON line.

    Four arms per config: pull mode (full/delta) x update-loop pipelining
    (off/on, ``async.pipeline.depth``).  The ``*_pipe`` arms are the
    pipelined-update-loop A-B the tentpole is judged by: same wire modes,
    prefetched pulls + decoupled pushes + lock-free PULL serving on top.
    Each arm also records the trace decomposition (pull.wait/push.wait/
    pipeline p50s) and the pipeline counters."""
    import jax

    from asyncframework_tpu.conf import AsyncConf, set_global_conf
    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.data.sparse import SparseShardedDataset
    from asyncframework_tpu.metrics import profiler as prof_mod
    from asyncframework_tpu.metrics import trace as trace_mod
    from asyncframework_tpu.net import frame, reset_net_totals
    from asyncframework_tpu.parallel import ps_dcn
    from asyncframework_tpu.solvers import SolverConfig

    devices = jax.devices()
    # continuous-profiling plane, once per child process; each arm
    # resets the accumulators so its profile block is arm-local
    prof_mod.install("bench-dcn", hz=197.0)
    # BENCH_DCN_PIPELINE=0 drops the pipelined arms entirely
    pipe_depth = max(0, int(os.environ.get("BENCH_DCN_PIPELINE", "2")))
    out = {}
    for name, c in DCN_CONFIGS.items():
        if c["sparse"]:
            ds = SparseShardedDataset.generate_on_device(
                c["n"], c["d"], c["nnz"], c["nw"], devices=devices,
                seed=7, noise=0.01,
            )
        else:
            ds = ShardedDataset.generate_on_device(
                c["n"], c["d"], c["nw"], devices=devices, seed=7,
                noise=0.01,
            )
        out[name] = {}
        arms = [("full", 0), ("delta", 0)]
        if pipe_depth > 0:
            arms += [("full", pipe_depth), ("delta", pipe_depth)]
        for mode, depth in arms:
            label = mode if depth == 0 else f"{mode}_pipe"
            conf = AsyncConf()
            conf.set("async.pull.mode", mode)
            conf.set("async.pipeline.depth", depth)
            # per-stage latency decomposition rides the artifact (same
            # sampling cost in every arm, so the A-B stays fair)
            conf.set("async.trace.sample", 1.0 / 8.0)
            set_global_conf(conf)
            reset_net_totals()
            ps_dcn.reset_pipeline_totals()
            trace_mod.reset_aggregator()
            prof_mod.reset_profile_totals()
            cfg = SolverConfig(
                num_workers=c["nw"], num_iterations=c["iters"],
                gamma=c["gamma"], taw=2**31 - 1,
                batch_rate=c["batch_rate"], bucket_ratio=0.5,
                printer_freq=100, coeff=0.0, seed=42,
                calibration_iters=20, run_timeout_s=120.0,
            )
            ps = ps_dcn.ParameterServer(
                cfg, c["d"], c["n"], device=devices[0], port=0
            ).start()
            arm_obs = ArmObserver()  # fleet-series artifact per arm
            shards = {w: ds.shard(w) for w in range(c["nw"])}
            t0 = time.monotonic()
            ps_dcn.run_worker_process(
                "127.0.0.1", ps.port, list(range(c["nw"])), shards, cfg,
                c["d"], c["n"], deadline_s=120.0,
            )
            done = ps.wait_done(timeout_s=5.0)
            elapsed = time.monotonic() - t0
            observer_block = arm_obs.finish()
            ps.stop()
            bt = frame.bytes_totals()
            pulls = max(sum(ps.pull_replies.values()), 1)
            pushes = max(ps.accepted + ps.dropped, 1)
            stages = trace_mod.aggregator().snapshot().get("stages_ms", {})
            rec = {
                "ok": bool(done),
                "accepted": ps.accepted,
                "updates_per_sec": round(ps.accepted / elapsed, 1)
                if elapsed > 0 else None,
                # sent counts both directions of the loopback pair once
                # (client requests + server replies): the wire volume
                "wire_bytes_per_update": round(
                    bt.get("sent", 0) / max(ps.accepted, 1)
                ),
                "pull_model_bytes_avg": round(ps.pull_model_bytes / pulls),
                "pull_replies": dict(ps.pull_replies),
                "push_payload_bytes_avg": round(ps.push_bytes / pushes),
                "max_staleness": ps.max_staleness,
                "merge": {"batches": ps.merge_batches,
                          "pushes": ps.merge_merged,
                          "max_batch": ps.merge_batch_max},
                # worker-loop stall decomposition: the pipelined arms
                # should show pull.wait/push.wait p50 shrinking with the
                # residual stall surfacing under "pipeline"
                "trace_p50_ms": {
                    st: round(s["p50"], 3) for st, s in stages.items()
                },
                # per-arm cluster-observer artifact (ISSUE 14): the
                # fleet series + derived signals a collector scraped
                # off this arm's PS while it ran (never-dark: an error
                # string on failure)
                "observer": observer_block,
                # per-arm continuous-profiling artifact (ISSUE 18):
                # zone decomposition + the trace consistency cross-check
                "profile": profile_block(prof_mod, stages),
            }
            if depth > 0:
                rec["pipeline"] = ps_dcn.pipeline_totals()
            out[name][label] = rec
        full_b = out[name]["full"]["wire_bytes_per_update"]
        delta_b = out[name]["delta"]["wire_bytes_per_update"]
        out[name]["wire_bytes_ratio_full_over_delta"] = (
            round(full_b / delta_b, 2) if delta_b else None
        )
        for mode in ("full", "delta"):
            if f"{mode}_pipe" not in out[name]:
                continue
            off = out[name][mode]["updates_per_sec"]
            on = out[name][f"{mode}_pipe"]["updates_per_sec"]
            out[name][f"pipeline_speedup_{mode}"] = (
                round(on / off, 3) if off and on else None
            )
    # sharded-PS arm (parallel/shardgroup.py): 1 vs 3 REAL shard child
    # processes serving the dense config, full and delta wire modes.  The
    # 1-shard control crosses the same process boundary (a managed child,
    # classic single-PS wire), so the A-B isolates the range-partition
    # fan-out cost/win rather than loopback-vs-process noise.
    # BENCH_DCN_SHARDS=0 drops the arm.
    if os.environ.get("BENCH_DCN_SHARDS", "1") != "0":
        from asyncframework_tpu.parallel.shardgroup import ShardGroup

        c = DCN_CONFIGS["dense"]
        ds = ShardedDataset.generate_on_device(
            c["n"], c["d"], c["nw"], devices=devices, seed=7, noise=0.01,
        )
        out["shards"] = {}
        for shard_count in (1, 3):
            for mode in ("full", "delta"):
                label = f"s{shard_count}_{mode}"
                conf = AsyncConf()
                conf.set("async.pull.mode", mode)
                conf.set("async.pipeline.depth", 0)
                set_global_conf(conf)
                reset_net_totals()
                cfg = SolverConfig(
                    num_workers=c["nw"], num_iterations=c["iters"],
                    gamma=c["gamma"], taw=2**31 - 1,
                    batch_rate=c["batch_rate"], bucket_ratio=0.5,
                    printer_freq=100, coeff=0.0, seed=42,
                    calibration_iters=20, run_timeout_s=120.0,
                    pull_mode=mode,
                )
                group = ShardGroup(
                    cfg, c["d"], c["n"], shard_count,
                    conf_overlays=conf.to_dict(),
                ).start()
                try:
                    primary_port = group.port_of(0)
                    shards = {w: ds.shard(w) for w in range(c["nw"])}
                    t0 = time.monotonic()
                    counts = ps_dcn.run_worker_process(
                        "127.0.0.1", primary_port, list(range(c["nw"])),
                        shards, cfg, c["d"], c["n"], deadline_s=120.0,
                    )
                    elapsed = time.monotonic() - t0
                    group.finish()
                    result = group.result_of(0, timeout_s=30.0) or {}
                finally:
                    group.stop()
                bt = frame.bytes_totals()
                accepted = int(result.get("accepted", 0))
                out["shards"][label] = {
                    "ok": bool(result.get("done")),
                    "shards": shard_count,
                    "accepted": accepted,
                    "gradients": int(sum(counts.values())),
                    "updates_per_sec": round(accepted / elapsed, 1)
                    if elapsed > 0 and accepted else None,
                    "wire_bytes_per_update": round(
                        bt.get("sent", 0) / max(accepted, 1)
                    ),
                    "restarts": group.restarts_of(0),
                }
        for mode in ("full", "delta"):
            one = out["shards"][f"s1_{mode}"]["updates_per_sec"]
            three = out["shards"][f"s3_{mode}"]["updates_per_sec"]
            out["shards"][f"shard_speedup_{mode}"] = (
                round(three / one, 3) if one and three else None
            )
    # failover arm (ISSUE 13): p99 pull latency through a seeded
    # primary SIGKILL, checkpoint-restart vs hot-standby promotion --
    # the number ROADMAP item 5's acceptance is judged by.  Per-arm
    # never-dark: an arm that wedges or errors records its error
    # string, not a hole.  BENCH_DCN_FAILOVER=0 drops the arm.
    if os.environ.get("BENCH_DCN_FAILOVER", "1") != "0":
        out["failover"] = {}
        for label, sb in (("restart", 0), ("promote", 1)):
            try:
                out["failover"][label] = _dcn_failover_arm(sb)
            except Exception as e:  # noqa: BLE001 - never-dark per arm
                out["failover"][label] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        r = out["failover"].get("restart", {}).get("gap_s")
        p = out["failover"].get("promote", {}).get("gap_s")
        out["failover"]["gap_ratio_restart_over_promote"] = (
            round(r / p, 2) if r and p else None
        )
    # adaptive arm (ISSUE 15): static conf vs controller-on under the
    # wan/DELAY deterministic heterogeneous cluster (the SAME seeded
    # wan wire schedule + the cloud long-tail DelayModel in both arms),
    # reporting time-to-target, updates/s, staleness p95, and the
    # controller's decision trace.  Per-arm never-dark: a wedged or
    # erroring arm records its error string, not a hole.
    # BENCH_DCN_ADAPTIVE=0 drops the arm.
    if os.environ.get("BENCH_DCN_ADAPTIVE", "1") != "0":
        out["adaptive"] = {}
        for label, on in (("static", False), ("controller", True)):
            try:
                out["adaptive"][label] = _dcn_adaptive_arm(on)
            except Exception as e:  # noqa: BLE001 - never-dark per arm
                out["adaptive"][label] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        s = out["adaptive"].get("static", {})
        a = out["adaptive"].get("controller", {})
        tts, tta = s.get("time_to_target_s"), a.get("time_to_target_s")
        out["adaptive"]["time_to_target_ratio_static_over_controller"] = (
            round(tts / tta, 3) if tts and tta else None
        )
        us, ua = s.get("updates_per_sec"), a.get("updates_per_sec")
        out["adaptive"]["updates_ratio_controller_over_static"] = (
            round(ua / us, 3) if us and ua else None
        )
    emit({"dcn": out})


def _dcn_adaptive_arm(control_on: bool) -> dict:
    """One adaptive-control measurement: the dense config on a
    deterministic heterogeneous cluster -- every op pays the seeded wan
    profile's delay/jitter/loss, and the cloud long-tail DelayModel
    (``coeff=-1``) makes some logical workers persistently slow -- with
    the knobs static vs closed-loop (AsyncController on the PS).  The
    A-B shares the wire schedule and data seed, so the only difference
    is who tunes the knobs."""
    import jax

    import numpy as np

    from asyncframework_tpu.conf import AsyncConf, set_global_conf
    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.metrics import trace as trace_mod
    from asyncframework_tpu.net import faults, reset_net_totals
    from asyncframework_tpu.parallel import controller as ctrl_mod
    from asyncframework_tpu.parallel import ps_dcn
    from asyncframework_tpu.parallel.controller import AsyncController
    from asyncframework_tpu.solvers import SolverConfig

    devices = jax.devices()
    c = DCN_CONFIGS["dense"]
    seed = int(os.environ.get("BENCH_ADAPTIVE_SEED", "7"))
    conf = AsyncConf()
    conf.set("async.pull.mode", "delta")
    conf.set("async.pipeline.depth", 0)
    conf.set("async.trace.sample", 1.0 / 8.0)
    # fast decision cadence: bench arms run tens of seconds, not hours
    conf.set("async.control.interval.s", 0.25)
    conf.set("async.control.cooldown.s", 0.5)
    set_global_conf(conf)
    reset_net_totals()
    ps_dcn.reset_pipeline_totals()
    trace_mod.reset_aggregator()
    ctrl_mod.reset_control_totals()
    cfg = SolverConfig(
        num_workers=c["nw"], num_iterations=c["iters"],
        gamma=c["gamma"], taw=2**31 - 1, batch_rate=c["batch_rate"],
        bucket_ratio=0.75, printer_freq=50, coeff=-1.0, seed=42,
        calibration_iters=20, run_timeout_s=180.0,
    )
    ds = ShardedDataset.generate_on_device(
        c["n"], c["d"], c["nw"], devices=devices, seed=7, noise=0.01,
    )
    inj = faults.FaultInjector(faults.wan_profile_schedule(seed))
    ps = None
    ctl = None
    try:
        # inside the try: a startup failure must still clear the global
        # injector and stop the PS, or the OTHER adaptive arm (and any
        # later dcn measurement in this child) runs with a stacked wan
        # schedule -- corrupting the very A/B this arm exists for
        faults.install(inj)
        ps = ps_dcn.ParameterServer(
            cfg, c["d"], c["n"], device=devices[0], port=0
        ).start()
        if control_on:
            ctl = AsyncController(ps, conf=conf).start()
        shards = {w: ds.shard(w) for w in range(c["nw"])}
        t0 = time.monotonic()
        ps_dcn.run_worker_process(
            "127.0.0.1", ps.port, list(range(c["nw"])), shards, cfg,
            c["d"], c["n"], deadline_s=180.0,
        )
        done = ps.wait_done(timeout_s=5.0)
        elapsed = time.monotonic() - t0
        times, W = ps.snapshot_stack()
        losses = (ps_dcn.evaluate_snapshots_on_shards(
            shards, times, W) / c["n"])
        target = float(losses[0]) * 0.05
        t_target = None
        for t_ms, loss in zip(times, losses):
            if float(loss) <= target:
                t_target = round(float(t_ms) / 1e3, 3)
                break
        stal = trace_mod.aggregator().snapshot().get(
            "staleness_versions", {})
        rec = {
            "ok": bool(done),
            "control": bool(control_on),
            "accepted": ps.accepted,
            "dropped": ps.dropped,
            "updates_per_sec": round(ps.accepted / elapsed, 1)
            if elapsed > 0 else None,
            "time_to_target_s": t_target,
            "target_loss": round(target, 6),
            "final_loss": round(float(losses[-1]), 6),
            "staleness_p95": stal.get("p95"),
            "max_staleness": ps.max_staleness,
            "wan_faults_fired": len(inj.fired),
        }
        if ctl is not None:
            decisions = ctl.decision_log()
            rec["decisions"] = decisions
            rec["control_totals"] = ctrl_mod.control_totals()
            rec["knobs"] = ctl.status()["knobs"]
            # controller_converged verdict on the REAL decision trace:
            # cumulative change count as a synthesized control.changes
            # series (flat tail = converged), judged by the conf rule
            changes = [[d["t"] * 1e3, i + 1]
                       for i, d in enumerate(decisions)]
            changes.append([elapsed * 1e3, float(len(decisions))])
            from asyncframework_tpu.metrics.slo import bench_verdicts

            verdicts = bench_verdicts(
                rec["updates_per_sec"],
                [[t, float(l)] for t, l in zip(times, losses)],
                extra_series={"control.changes": changes},
            )
            rec["slo"] = {"controller_converged":
                          verdicts.get("controller_converged")}
        return rec
    finally:
        if ctl is not None:
            ctl.stop()
        if ps is not None:
            ps.stop()
        faults.clear()


def _dcn_failover_arm(standbys: int) -> dict:
    """One failover measurement: a 2-shard REAL-process group (fence
    on; ``standbys`` warm standbys per shard) with in-process workers
    training through it, SIGKILL of shard 1's primary mid-run, and a
    20 ms-cadence read probe against the range's CURRENT endpoint.
    Records the availability gap (kill -> first answer from the
    recovered endpoint), p99 probe latency across the window, and HOW
    the range recovered (promotion vs restart-from-checkpoint)."""
    import signal as _signal
    import tempfile
    import threading

    import numpy as np  # noqa: F811 - child-scope import, bench style
    import jax

    from asyncframework_tpu.conf import AsyncConf, set_global_conf
    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.parallel import ps_dcn
    from asyncframework_tpu.parallel import shardgroup as sgm
    from asyncframework_tpu.solvers import SolverConfig

    n, d, nw = 2048, 64, 4
    kill_after = int(os.environ.get("BENCH_FAILOVER_KILL_AFTER", "60"))
    cfg = SolverConfig(
        num_workers=nw, num_iterations=10**6, gamma=0.5, taw=2**31 - 1,
        batch_rate=0.2, bucket_ratio=0.5, printer_freq=50, coeff=0.0,
        seed=42, calibration_iters=20, run_timeout_s=120.0,
    )
    conf = AsyncConf({"async.fence.enabled": True,
                      "async.ps.standby": standbys})
    set_global_conf(conf)
    tmp = tempfile.mkdtemp(prefix="bench-failover-")
    group = sgm.ShardGroup(
        cfg, d, n, 2, checkpoint_dir=tmp, conf_overlays=conf.to_dict(),
        dead_after_s=1.0, check_interval_s=0.2, stderr_dir=tmp,
    ).start()
    ds = ShardedDataset.generate_on_device(
        n, d, nw, devices=jax.devices(), seed=7, noise=0.01,
    )
    shards = {w: ds.shard(w) for w in range(nw)}

    def train():
        try:
            ps_dcn.run_worker_process(
                "127.0.0.1", group.port_of(0), list(range(nw)), shards,
                cfg, d, n, deadline_s=90.0,
            )
        except Exception:  # noqa: BLE001 - the probe owns the verdict
            pass

    worker = threading.Thread(target=train, name="bench-failover-worker",
                              daemon=True)
    worker.start()
    try:
        # wait for shard 1 to merge past the kill threshold (its
        # cadence checkpoint must exist so the restart arm actually
        # replays one)
        deadline = time.monotonic() + 45.0
        while time.monotonic() < deadline:
            try:
                hdr = sgm._oneshot("127.0.0.1", group.port_of(1),
                                   {"op": "SUBSCRIBE"}, timeout_s=1.0)
                if int(hdr.get("clock", 0)) >= kill_after:
                    break
            except (ConnectionError, OSError):
                pass
            time.sleep(0.02)
        else:
            return {"error": "shard 1 never reached the kill threshold"}
        lat_ms = []

        def probe_until(deadline_s, stop_when=None):
            """20 ms-cadence reads of range 1 at its CURRENT endpoint;
            successful round trips land in lat_ms.  Returns the
            monotonic time stop_when first held, else None."""
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                t0 = time.monotonic()
                try:
                    sgm._oneshot("127.0.0.1", group.port_of(1),
                                 {"op": "SUBSCRIBE"}, timeout_s=1.0)
                    lat_ms.append((time.monotonic() - t0) * 1e3)
                    if stop_when is not None and stop_when():
                        return time.monotonic()
                except (ConnectionError, OSError):
                    pass
                time.sleep(0.02)
            return None

        probe_until(2.0)  # healthy baseline window
        os.kill(group.pid_of(1), _signal.SIGKILL)
        t_kill = time.monotonic()
        recovered_at = probe_until(
            60.0,
            stop_when=lambda: (group.promotions_of(1) >= 1
                               or group.restarts_of(1) >= 1),
        )
        gap_s = (recovered_at - t_kill) if recovered_at is not None \
            else None
        probe_until(2.0)  # recovered window: post-failover latency
        group.finish()
        worker.join(timeout=30.0)
        result1 = group.result_of(1, timeout_s=15.0) or {}
        return {
            "ok": gap_s is not None,
            "standbys": standbys,
            "gap_s": round(gap_s, 3) if gap_s is not None else None,
            "pull_p99_ms": (round(float(np.percentile(lat_ms, 99)), 3)
                            if lat_ms else None),
            "pull_p50_ms": (round(float(np.percentile(lat_ms, 50)), 3)
                            if lat_ms else None),
            "probes": len(lat_ms),
            "recovered_by": ("promotion" if group.promotions_of(1)
                             else "restart" if group.restarts_of(1)
                             else None),
            "resumed_from": result1.get("resumed_from"),
            "promoted": result1.get("promoted"),
        }
    finally:
        group.stop()


def run_dcn_mesh_child() -> None:
    """Mesh-arm DCN bench (ISSUE 11): the dense config with the worker
    gradient step single-device (``async.mesh.devices=0``, the control)
    vs batch-parallel over an 8-device mesh, in a child whose platform
    is 8 forced-host CPU devices (the parent sets JAX_PLATFORMS and
    XLA_FLAGS).  Records updates/s, the per-step compute p50 from the
    trace decomposition, the actual mesh shape, and
    ``jax.device_count()`` + platform.

    Loopback reality check (same story as PR 4's delta bytes and PR 8's
    shard fan-out): on virtual CPU devices the psum and the P-way
    emulated dispatch are pure overhead -- the win this arm exists to
    price appears when the per-device partial gradient runs on a real
    chip and the all-reduce rides ICI.  The compute-p50 decomposition is
    what makes the A-B readable either way.
    """
    import jax

    from asyncframework_tpu.conf import AsyncConf, set_global_conf
    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.metrics import trace as trace_mod
    from asyncframework_tpu.net import reset_net_totals
    from asyncframework_tpu.parallel import ps_dcn
    from asyncframework_tpu.solvers import SolverConfig

    devices = jax.devices()
    mesh_n = max(1, int(os.environ.get("BENCH_DCN_MESH_DEVICES", "8")))
    c = DCN_CONFIGS["dense"]
    ds = ShardedDataset.generate_on_device(
        c["n"], c["d"], c["nw"], devices=devices, seed=7, noise=0.01,
    )
    out = {
        "device_count": jax.device_count(),
        "platform": devices[0].platform,
        "requested_mesh_devices": mesh_n,
    }
    for label, mesh_dev in (("mesh_off", 0), ("mesh_on", mesh_n)):
        conf = AsyncConf()
        conf.set("async.pull.mode", "full")
        conf.set("async.pipeline.depth", 0)
        conf.set("async.mesh.devices", mesh_dev)
        conf.set("async.trace.sample", 1.0 / 8.0)
        set_global_conf(conf)
        reset_net_totals()
        trace_mod.reset_aggregator()
        cfg = SolverConfig(
            num_workers=c["nw"], num_iterations=c["iters"],
            gamma=c["gamma"], taw=2**31 - 1,
            batch_rate=c["batch_rate"], bucket_ratio=0.5,
            printer_freq=100, coeff=0.0, seed=42,
            calibration_iters=20, run_timeout_s=120.0,
        )
        ps = ps_dcn.ParameterServer(
            cfg, c["d"], c["n"], device=devices[0], port=0
        ).start()
        shards = {w: ds.shard(w) for w in range(c["nw"])}
        t0 = time.monotonic()
        ps_dcn.run_worker_process(
            "127.0.0.1", ps.port, list(range(c["nw"])), shards, cfg,
            c["d"], c["n"], deadline_s=120.0,
        )
        done = ps.wait_done(timeout_s=5.0)
        elapsed = time.monotonic() - t0
        ps.stop()
        stages = trace_mod.aggregator().snapshot().get("stages_ms", {})
        eff = min(mesh_dev, len(devices)) if mesh_dev else 0
        out[label] = {
            "ok": bool(done),
            "accepted": ps.accepted,
            "updates_per_sec": round(ps.accepted / elapsed, 1)
            if elapsed > 0 else None,
            # the worker-side gradient step is the stage the mesh
            # parallelizes: its p50 is the per-step compute cost
            "compute_p50_ms": round(
                stages.get(trace_mod.COMPUTE, {}).get("p50", 0.0), 3
            ) or None,
            "mesh_shape": {"dp": eff} if eff >= 2 else None,
            "max_staleness": ps.max_staleness,
        }
    off = out["mesh_off"]["updates_per_sec"]
    on = out["mesh_on"]["updates_per_sec"]
    out["mesh_speedup"] = round(on / off, 3) if off and on else None
    emit({"dcn_mesh": out})


def collect_dcn_mesh_block(env: dict) -> dict:
    """Run the mesh arm in a disposable subprocess whose platform is
    forced to 8 virtual host devices (XLA latches the flag at backend
    init, so the fan-out must happen at process birth)."""
    env2 = dict(env)
    flags = env2.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env2["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--dcn-mesh"],
            capture_output=True, text=True, timeout=600, env=env2,
        )
    except subprocess.TimeoutExpired:
        return {"error": "dcn mesh bench timed out"}
    sys.stderr.write(res.stderr)
    line = next((l for l in reversed(res.stdout.splitlines())
                 if l.startswith("{")), None)
    if line is None:
        return {"error": f"no JSON from dcn mesh child "
                         f"(rc={res.returncode})"}
    return json.loads(line).get(
        "dcn_mesh", {"error": "malformed dcn mesh payload"}
    )


def collect_dcn_block(env: dict) -> dict:
    """Run the DCN wire bench in a disposable subprocess (same discipline
    as every other measurement: fresh process, parent owns the timeout)."""
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--dcn"],
            capture_output=True, text=True, timeout=600, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": "dcn bench timed out"}
    sys.stderr.write(res.stderr)
    line = next((l for l in reversed(res.stdout.splitlines())
                 if l.startswith("{")), None)
    if line is None:
        return {"error": f"no JSON from dcn child (rc={res.returncode})"}
    return json.loads(line).get("dcn", {"error": "malformed dcn payload"})


# --------------------------------------------------------------- serve bench
# Serving-tier bench (always CPU: it measures the read path's QPS vs
# freshness lag, not the chip): a REAL ParameterServer with training
# running on a worker thread, REAL replica OS processes subscribed over
# loopback TCP, a ServingFrontend routing a multi-threaded client load --
# and one arm where a replica is SIGKILLed mid-load to price failover.
SERVE_CONFIG = dict(n=4096, d=512, nw=2, gamma=0.05 * 512,
                    batch_rate=0.1, iters=200_000)
SERVE_LOAD_S = float(os.environ.get("BENCH_SERVE_LOAD_S", 3.0))
SERVE_CLIENTS = int(os.environ.get("BENCH_SERVE_CLIENTS", 4))
SERVE_BATCH = int(os.environ.get("BENCH_SERVE_BATCH", 16))


def _spawn_replica(ps_port: int, rid: int, env: dict,
                   timeout_s: float = 60.0):
    """One replica OS process; returns (Popen, predict_port).  The replica
    announces its bound port as one JSON line on stdout."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "asyncframework_tpu.serving.cli", "replica",
         "--ps", f"127.0.0.1:{ps_port}", "--host", "127.0.0.1",
         "--rid", str(rid)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env,
    )
    line_box = {}

    def read_line():
        line_box["line"] = proc.stdout.readline()

    t = threading.Thread(target=read_line, name="bench-probe-read",
                         daemon=True)
    t.start()
    t.join(timeout=timeout_s)
    line = line_box.get("line")
    if not line:
        proc.kill()
        raise RuntimeError(f"replica {rid} did not announce within "
                           f"{timeout_s:.0f}s")
    return proc, int(json.loads(line)["port"])


def _pcts(vals, nd=3):
    if not vals:
        return None
    v = sorted(vals)
    rank = lambda q: v[min(len(v) - 1, max(0, int(round(q * len(v))) - 1))]
    return {"p50": round(rank(0.50), nd), "p95": round(rank(0.95), nd),
            "p99": round(rank(0.99), nd), "max": round(v[-1], nd)}


def run_serve_child() -> None:
    """One fresh-process serving bench; prints one JSON line.

    Three arms: 1 replica, 2 replicas, and 2 replicas with one SIGKILLed
    mid-load.  Every arm runs with training concurrently advancing the
    model (the freshness-lag numbers are meaningless against a frozen
    PS), and records QPS, predict latency, freshness lag in versions AND
    ms, failovers, and the error rate."""
    import jax

    import signal

    from asyncframework_tpu.data.sharded import ShardedDataset
    from asyncframework_tpu.metrics import reset_totals
    from asyncframework_tpu.parallel import ps_dcn
    from asyncframework_tpu.serving import ServingFrontend
    from asyncframework_tpu.serving import metrics as smetrics
    from asyncframework_tpu.solvers import SolverConfig

    c = SERVE_CONFIG
    devices = jax.devices()
    ds = ShardedDataset.generate_on_device(
        c["n"], c["d"], c["nw"], devices=devices, seed=7, noise=0.01
    )
    shards = {w: ds.shard(w) for w in range(c["nw"])}
    from asyncframework_tpu.utils.devices import CPU, child_env

    env = child_env(os.environ, CPU)  # replicas: CPU backend by assignment
    rng = np.random.default_rng(3)
    X = rng.normal(size=(SERVE_BATCH, c["d"])).astype(np.float32)
    out = {}
    # replica count for the top arm comes from the declared knob (default
    # 2 keeps the historical r1/r2/r2_kill arms byte-identical); operators
    # bench wider via --conf async.serve.replicas / ASYNCTPU_ env
    from asyncframework_tpu.conf import SERVE_REPLICAS, global_conf

    n_top = max(1, int(global_conf().get(SERVE_REPLICAS)))
    arms = [("r1", 1, False)]
    if n_top > 1:
        arms.append((f"r{n_top}", n_top, False))
        # the kill arm needs a survivor to fail over to: with one replica
        # a SIGKILL measures a guaranteed outage, not failover
        arms.append((f"r{n_top}_kill", n_top, True))
    for label, n_rep, kill in arms:
        reset_totals()
        cfg = SolverConfig(
            num_workers=c["nw"], num_iterations=c["iters"],
            gamma=c["gamma"], taw=2**31 - 1, batch_rate=c["batch_rate"],
            bucket_ratio=0.5, printer_freq=10_000, coeff=0.0, seed=42,
            calibration_iters=20, run_timeout_s=SERVE_LOAD_S + 30.0,
        )
        ps = ps_dcn.ParameterServer(
            cfg, c["d"], c["n"], device=devices[0], port=0
        ).start()
        replicas = []
        try:
            for rid in range(n_rep):
                replicas.append(_spawn_replica(ps.port, rid, env))
            fe = ServingFrontend(
                [("127.0.0.1", port) for (_p, port) in replicas],
                deadline_s=1.0,
            ).start()
            # training runs CONCURRENTLY for the whole load window; the
            # worker deadline, not the iteration budget, ends it
            trainer = threading.Thread(
                target=ps_dcn.run_worker_process,
                args=("127.0.0.1", ps.port, list(range(c["nw"])), shards,
                      cfg, c["d"], c["n"]),
                kwargs=dict(deadline_s=SERVE_LOAD_S + 6.0),
                name=f"bench-serve-trainer-{label}", daemon=True,
            )
            trainer.start()
            # warm: first predict proves replicas refreshed and compiled
            warm_deadline = time.monotonic() + 30.0
            while True:
                try:
                    fe.predict(X)
                    break
                except Exception:
                    if time.monotonic() > warm_deadline:
                        raise
                    time.sleep(0.1)
            accepted0 = ps.accepted
            # counter baseline AFTER warm-up: boot-window failovers
            # (replicas still compiling/refreshing) must not pollute the
            # load window's numbers -- nonzero failovers is the KILL
            # arm's discriminator
            totals0 = smetrics.serving_totals()
            stats_lock = threading.Lock()
            oks, errs, lags_v, lags_ms, lat_ms = [0], [0], [], [], []
            stop_at = time.monotonic() + SERVE_LOAD_S
            kill_at = time.monotonic() + SERVE_LOAD_S / 2.0

            def client_loop():
                while time.monotonic() < stop_at:
                    t0 = time.monotonic()
                    try:
                        _y, meta = fe.predict_ex(X)
                    except Exception:
                        with stats_lock:
                            errs[0] += 1
                        continue
                    with stats_lock:
                        oks[0] += 1
                        lags_v.append(meta["lag_versions"])
                        lags_ms.append(meta["lag_ms"])
                        lat_ms.append((time.monotonic() - t0) * 1e3)

            clients = [threading.Thread(target=client_loop,
                                        name=f"bench-serve-client-{i}",
                                        daemon=True)
                       for i in range(SERVE_CLIENTS)]
            for t in clients:
                t.start()
            if kill:
                while time.monotonic() < kill_at:
                    time.sleep(0.01)
                os.kill(replicas[0][0].pid, signal.SIGKILL)
            for t in clients:
                t.join(timeout=SERVE_LOAD_S + 10.0)
            accepted_during = ps.accepted - accepted0
            totals = smetrics.serving_totals()
            n_ok, n_err = oks[0], errs[0]
            out[label] = {
                "replicas": n_rep,
                "killed_mid_load": kill,
                "load_s": SERVE_LOAD_S,
                "clients": SERVE_CLIENTS,
                "batch": SERVE_BATCH,
                "predicts": n_ok,
                "errors": n_err,
                "error_rate": round(n_err / max(n_ok + n_err, 1), 4),
                "qps": round(n_ok / SERVE_LOAD_S, 1),
                "rows_per_sec": round(n_ok * SERVE_BATCH / SERVE_LOAD_S),
                "failovers": (totals.get("failovers", 0)
                              - totals0.get("failovers", 0)),
                "unhealthy_rejects": (
                    totals.get("unhealthy_rejects", 0)
                    - totals0.get("unhealthy_rejects", 0)
                ),
                "predict_ms": _pcts(lat_ms),
                "lag_versions": _pcts(lags_v, nd=0),
                "lag_ms": _pcts(lags_ms),
                "train_accepted_during_load": accepted_during,
                "train_updates_per_sec": round(
                    accepted_during / SERVE_LOAD_S, 1
                ),
                "subscribe_replies": dict(ps.subscribe_replies),
            }
            print(f"# serve {label}: {json.dumps(out[label])}",
                  file=sys.stderr)
            fe.stop()
        finally:
            for proc, _port in replicas:
                try:
                    proc.kill()
                except OSError:
                    pass
            ps.stop()
    emit({"serve": out})


def collect_serve_block(env: dict) -> dict:
    """Run the serving bench in a disposable subprocess (fresh process,
    parent owns the timeout -- the same discipline as every arm)."""
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--serve"],
            capture_output=True, text=True, timeout=420, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": "serve bench timed out"}
    sys.stderr.write(res.stderr)
    line = next((l for l in reversed(res.stdout.splitlines())
                 if l.startswith("{")), None)
    if line is None:
        return {"error": f"no JSON from serve child (rc={res.returncode})"}
    return json.loads(line).get("serve", {"error": "malformed serve payload"})


# --------------------------------------------------------------- relay bench
# Relaycast wire bench (ISSUE 12; always CPU -- it measures wire bytes,
# not chips): an in-process PS plus N relay sources driven
# DETERMINISTICALLY (topo order per version, no background loops), so
# the byte counters are exact.  Three distribution arms -- direct
# SUBSCRIBE (the N x control), relay tree raw, relay tree compressed --
# plus the quantized-PUSH codec arm (off/fp16/int8 wire bytes per
# update).  Never-dark: each arm records its error instead of killing
# the block.
RELAY_REPLICAS = int(os.environ.get("BENCH_RELAY_REPLICAS", 8))
RELAY_VERSIONS = int(os.environ.get("BENCH_RELAY_VERSIONS", 18))


def run_relay_child() -> None:
    import numpy as np

    from asyncframework_tpu.metrics import profiler as prof_mod
    from asyncframework_tpu.metrics import reset_totals
    from asyncframework_tpu.net import wirecodec
    from asyncframework_tpu.parallel import ps_dcn
    from asyncframework_tpu.relaycast import (
        ROOT,
        RelayNode,
        RelaySource,
        parent_index,
    )
    from asyncframework_tpu.relaycast import metrics as rmetrics
    from asyncframework_tpu.solvers import SolverConfig

    d, n = 4096, 1024
    fanout = 2

    def make_ps():
        cfg = SolverConfig(
            num_workers=2, num_iterations=10_000, gamma=0.5,
            taw=2 ** 31 - 1, batch_rate=0.3, bucket_ratio=0.0,
            printer_freq=1000, seed=42, calibration_iters=4,
            run_timeout_s=120.0,
        )
        return ps_dcn.ParameterServer(cfg, d, n, port=0).start()

    def push_version(cl, rng, v):
        ts, _w, _avg, _cal = cl.pull(0)
        # decaying update magnitudes: versions sweep from the hard
        # near-incompressible early regime (big random updates) into
        # the converged regime a serving fleet actually lives in (tiny
        # relative updates) -- the steady-state tail is reported
        # separately below
        scale = 0.5 * (0.45 ** v) + 1e-5
        cl.push(0, ts, (scale * rng.normal(size=d)).astype(np.float32))

    def distribution_arm(relay: bool, compress: bool) -> dict:
        reset_totals()
        ps = make_ps()
        nodes, sources = [], []
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full")
            for rid in range(RELAY_REPLICAS):
                node = RelayNode(rid=rid, port=0,
                                 compress=compress).start()
                p = parent_index(rid, fanout)
                parent = (None if (not relay or p == ROOT)
                          else ("127.0.0.1", nodes[p].port))
                nodes.append(node)
                sources.append(RelaySource("127.0.0.1", ps.port, node,
                                           parent=parent, rid=rid))
            rng = np.random.default_rng(7)
            fetch_by_version = []
            prev_fetch = 0
            for v in range(RELAY_VERSIONS):
                push_version(cl, rng, v)
                for rid in range(RELAY_REPLICAS):
                    got = sources[rid].subscribe(rid)
                    assert got[0] == v + 1
                cur = rmetrics.relay_totals().get("fetch_bytes_out", 0)
                fetch_by_version.append(cur - prev_fetch)
                prev_fetch = cur
            rt = rmetrics.relay_totals()
            ct = wirecodec.codec_totals()
            out = {
                "ps_subscribe_bytes_per_version":
                    round(ps.subscribe_model_bytes / RELAY_VERSIONS),
                "ps_subscribe_replies": dict(ps.subscribe_replies),
                "relay_fetch_bytes_per_version":
                    round(rt.get("fetch_bytes_out", 0) / RELAY_VERSIONS),
                "relay_fetch_bytes_by_version": fetch_by_version,
                "parent_fetches": rt.get("parent_fetches", 0),
                "root_fallbacks": rt.get("root_fallbacks", 0),
            }
            if ct.get("snap_bytes_wire"):
                out["snap_compression_ratio"] = round(
                    ct["snap_bytes_raw"] / ct["snap_bytes_wire"], 2)
            return out
        finally:
            for node in nodes:
                node.stop()
            ps.stop()

    def codec_arm(codec: str) -> dict:
        # reset_totals() clears every registry family, including the
        # profiler's -- so this arm's profile block is arm-local, and
        # `bin/async-prof --diff` between the codec-on and codec-off
        # arms shows wire.quantize only where encode_grad actually ran
        prof_mod.install("bench-relay", hz=197.0)
        reset_totals()
        ps = make_ps()
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full",
                                 push_codec=codec)
            rng = np.random.default_rng(11)
            K = 40
            for v in range(K):
                push_version(cl, rng, v % 8)
            return {
                "push_payload_bytes_per_update":
                    round(ps.push_bytes / K),
                "accepted": ps.accepted,
                "profile": profile_block(prof_mod, {}),
            }
        finally:
            ps.stop()

    out = {"replicas": RELAY_REPLICAS, "versions": RELAY_VERSIONS,
           "d": d, "fanout": fanout, "platform": "cpu",
           "arms": {}, "codec": {}}
    for name, (relay, compress) in (
            ("direct", (False, False)),
            ("relay_raw", (True, False)),
            ("relay_z", (True, True))):
        try:
            out["arms"][name] = distribution_arm(relay, compress)
        except Exception as e:  # noqa: BLE001 - never-dark discipline
            out["arms"][name] = {
                "error": f"{type(e).__name__}: {str(e)[:200]}"}
    raw_bv = out["arms"].get("relay_raw", {}).get(
        "relay_fetch_bytes_by_version")
    z_bv = out["arms"].get("relay_z", {}).get(
        "relay_fetch_bytes_by_version")
    if raw_bv and z_bv:
        # steady-state compression: the converged-regime tail (last
        # half of the deterministic schedule), which is the serving
        # fleet's actual operating point; the whole-run average above
        # includes the incompressible warm-up transient
        half = len(raw_bv) // 2
        raw_tail, z_tail = sum(raw_bv[half:]), sum(z_bv[half:])
        if z_tail > 0:
            out["steady_state_compression_ratio"] = round(
                raw_tail / z_tail, 2)
    for codec in ("off", "fp16", "int8"):
        try:
            out["codec"][codec] = codec_arm(codec)
        except Exception as e:  # noqa: BLE001 - never-dark discipline
            out["codec"][codec] = {
                "error": f"{type(e).__name__}: {str(e)[:200]}"}
    emit({"relay": out})


def collect_relay_block(env: dict) -> dict:
    """Run the relaycast bench in a disposable subprocess (fresh
    process, parent owns the timeout -- the discipline of every arm)."""
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--relay"],
            capture_output=True, text=True, timeout=420, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": "relay bench timed out"}
    sys.stderr.write(res.stderr)
    line = next((l for l in reversed(res.stdout.splitlines())
                 if l.startswith("{")), None)
    if line is None:
        return {"error": f"no JSON from relay child (rc={res.returncode})"}
    return json.loads(line).get("relay",
                                {"error": "malformed relay payload"})


def run_native_child() -> None:
    """Native data-plane bench (PR 19, CPU loopback, device-independent):
    python vs native per wire-codec unit (bytes/s per core), DCN
    updates/s with the codecs in the loop, and shm-ring vs loopback-TCP
    transport throughput.  Per-pass profiler snapshots ride the payload
    so `bin/async-prof --diff` shows the wire.* zone shares shrinking."""
    import numpy as np

    from asyncframework_tpu import conf as _conf
    from asyncframework_tpu.metrics import profiler as prof_mod
    from asyncframework_tpu.metrics import reset_totals
    from asyncframework_tpu.native_build import ensure_built, native_totals
    from asyncframework_tpu.net import wirecodec, wiredelta
    from asyncframework_tpu.parallel import ps_dcn
    from asyncframework_tpu.solvers import SolverConfig

    built = all(ensure_built(n) is not None
                for n in ("wiredelta", "wirecodec", "shmring"))
    cf = _conf.global_conf()
    prof_mod.install("bench-native", hz=197.0)

    # ------------------------------------------------ codec micro units
    d = 1 << 20  # 4 MiB f32: big enough that per-call overhead vanishes
    rng = np.random.default_rng(3)
    basis = rng.normal(size=d).astype(np.float32)
    cur = basis.copy()
    touched = rng.choice(d, size=d // 50, replace=False)
    cur[touched] += rng.normal(size=touched.size).astype(np.float32)
    cur_bytes = cur.tobytes()
    grad = (0.01 * rng.normal(size=d)).astype(np.float32)
    want_crc = wiredelta.crc(cur_bytes)  # backend-independent by contract

    def timed_mb_s(fn, nbytes: float, budget_s: float = 0.2) -> float:
        fn()  # warm: first-dispatch costs (CDLL config, allocations)
        reps, t0 = 0, time.perf_counter()
        while True:
            fn()
            reps += 1
            dt = time.perf_counter() - t0
            if dt >= budget_s:
                return round(nbytes * reps / dt / 1e6, 1)

    wenc, dpayload, nnz = wiredelta.encode(cur, basis, cur_bytes=cur_bytes)
    fhdr, fpay, _ = wirecodec.encode_grad(grad, "fp16", None)
    ihdr, ipay, _ = wirecodec.encode_grad(grad, "int8", None)
    units = {
        "crc": (lambda: wiredelta.crc(cur_bytes), d * 4),
        "delta_encode": (
            lambda: wiredelta.encode(cur, basis, cur_bytes=cur_bytes),
            d * 4),
        "delta_decode": (
            lambda: wiredelta.decode(wenc, dpayload, nnz, basis, want_crc),
            d * 4),
        "fp16_encode": (
            lambda: wirecodec.encode_grad(grad, "fp16", None), d * 4),
        "fp16_decode": (
            lambda: wirecodec.decode_grad(fhdr, fpay, d), d * 4),
        "int8_encode": (
            lambda: wirecodec.encode_grad(grad, "int8", None), d * 4),
        "int8_decode": (
            lambda: wirecodec.decode_grad(ihdr, ipay, d), d * 4),
        "shuffle4": (
            lambda: wirecodec._shuffle4(cur_bytes), d * 4),
    }

    backends = ["python"] + (["native"] if built else [])
    codec_out: dict = {u: {} for u in units}
    prof_out: dict = {}
    for backend in backends:
        cf.set("async.native.enabled", backend == "native")
        reset_totals()
        for unit, (fn, nbytes) in units.items():
            try:
                codec_out[unit][f"{backend}_mb_s"] = timed_mb_s(fn, nbytes)
            except Exception as e:  # noqa: BLE001 - never-dark per unit
                codec_out[unit][f"{backend}_error"] = (
                    f"{type(e).__name__}: {str(e)[:120]}")
        prof_out[backend] = profile_block(prof_mod, {})
        prof_out[backend]["native_totals"] = native_totals()
    for unit, row in codec_out.items():
        if row.get("python_mb_s") and row.get("native_mb_s"):
            row["speedup"] = round(row["native_mb_s"] / row["python_mb_s"],
                                   2)

    # ------------------------------------------- DCN loop with codecs in
    dcn_d, pushes, pulls = 1 << 18, 120, 60

    def make_ps():
        scfg = SolverConfig(
            num_workers=2, num_iterations=10_000, gamma=0.5,
            taw=2 ** 31 - 1, batch_rate=0.3, bucket_ratio=0.0,
            printer_freq=1000, seed=42, calibration_iters=4,
            run_timeout_s=120.0,
        )
        return ps_dcn.ParameterServer(scfg, dcn_d, 1024, port=0).start()

    def dcn_pass(codec: str, shm: bool) -> dict:
        ps = make_ps()
        try:
            cl = ps_dcn.PSClient("127.0.0.1", ps.port, pull_mode="full",
                                 push_codec=codec, shm=shm)
            g = (0.01 * np.random.default_rng(5).normal(size=dcn_d)
                 ).astype(np.float32)
            ts, _w, _avg, _cal = cl.pull(0)
            t0 = time.perf_counter()
            for _ in range(pushes):
                cl.push(0, ts, g)
            push_dt = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(pulls):
                ts, _w, _avg, _cal = cl.pull(0)
            pull_dt = time.perf_counter() - t0
            return {
                "push_updates_s": round(pushes / push_dt, 1),
                "pull_mb_s": round(pulls * dcn_d * 4 / pull_dt / 1e6, 1),
            }
        finally:
            ps.stop()

    dcn_out: dict = {}
    for backend in backends:
        cf.set("async.native.enabled", backend == "native")
        for codec in ("off", "int8"):
            try:
                dcn_out[f"{backend}_{codec}"] = dcn_pass(codec, shm=False)
            except Exception as e:  # noqa: BLE001 - never-dark per arm
                dcn_out[f"{backend}_{codec}"] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"}

    # ------------------------------------- shm ring vs loopback transport
    shm_out: dict = {}
    cf.set("async.native.enabled", built)
    for label, use_shm in (("tcp", False), ("shm", True)):
        cf.set("async.shm.enabled", use_shm)
        reset_totals()
        try:
            shm_out[label] = dcn_pass("off", shm=use_shm)
            nt = native_totals()
            if use_shm:
                shm_out[label]["upgrades"] = nt.get("shm_upgrades", 0)
                shm_out[label]["frames"] = nt.get("shm_frames_sent", 0)
        except Exception as e:  # noqa: BLE001 - never-dark per arm
            shm_out[label] = {
                "error": f"{type(e).__name__}: {str(e)[:200]}"}
    cf.set("async.shm.enabled", False)
    for key in ("push_updates_s", "pull_mb_s"):
        t, s = shm_out.get("tcp", {}).get(key), shm_out.get(
            "shm", {}).get(key)
        if t and s:
            shm_out[f"{key}_speedup"] = round(s / t, 2)
    # a sub-1x shm speedup on cpus=1 is a scheduling artifact, not a
    # transport regression: two user-space ring endpoints cannot overlap
    # their copies on one core, while loopback TCP hands off through
    # kernel buffers with exact wakeups.  Record the count so artifacts
    # from single-core CI boxes explain themselves.
    shm_out["cpus"] = os.cpu_count()

    emit({"native": {
        "built": built, "platform": "cpu", "d_codec": d, "d_dcn": dcn_d,
        "codec": codec_out, "dcn": dcn_out, "shm": shm_out,
        "profile": prof_out,
    }})


def collect_native_block(env: dict) -> dict:
    """Run the native data-plane bench in a disposable subprocess (same
    never-dark discipline as every arm)."""
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--native"],
            capture_output=True, text=True, timeout=300, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": "native bench timed out"}
    sys.stderr.write(res.stderr)
    line = next((l for l in reversed(res.stdout.splitlines())
                 if l.startswith("{")), None)
    if line is None:
        return {"error": f"no JSON from native child (rc={res.returncode})"}
    return json.loads(line).get("native",
                                {"error": "malformed native payload"})


def run_probe() -> None:
    """Backend check in a disposable process (the parent stays off JAX so
    that the chip is free for each child): init the backend and print one
    JSON line naming the platform found.  The PARENT owns the timeout."""
    from asyncframework_tpu.utils.devices import device_stamp

    t0 = time.monotonic()
    init_devices()
    emit({"probe": True, **device_stamp(),
          "init_s": round(time.monotonic() - t0, 1)})


# Probe FAILURES are cached per target platform for the life of this
# invocation: an absent backend costs its probe budget ONCE, not once per
# config.  Successes are deliberately NOT cached -- the wedge path re-probes
# precisely to detect a device that went away mid-run.
_PROBE_FAILURES: dict = {}


def _reap_detached(proc: subprocess.Popen) -> None:
    """Reap a killed probe child WITHOUT ever blocking the parent: the
    post-kill communicate() can hang forever when a grandchild inherited
    the pipe fds, so it runs on a throwaway daemon thread."""
    def reap():
        try:
            proc.communicate(timeout=10)
        except Exception:  # noqa: BLE001 - best-effort cleanup only
            pass

    threading.Thread(target=_guarded(reap, "bench-probe-reap"),
                     name="bench-probe-reap", daemon=True).start()


def probe_backend(env: dict) -> Tuple[bool, str]:
    """Run the probe subprocess with a hard per-attempt timeout, bounded
    retries, AND a hard bound on the whole probe (BENCH_PROBE_BUDGET_S).
    "Alive" means the probe found the WANTED platform (``tpu`` unless
    ``BENCH_PLATFORM`` names another; ``init_devices`` refuses anything
    else, so a CPU backend never counts as the chip being up).  Returns
    (alive, note); a failure is memoized per platform."""
    platform = env.get("BENCH_PLATFORM") or "default"
    cached = _PROBE_FAILURES.get(platform)
    if cached is not None:
        print(f"# backend probe: cached failure for platform "
              f"{platform!r} -- {cached[1]}", file=sys.stderr)
        return cached
    deadline = time.monotonic() + PROBE_BUDGET_S
    attempts_run = 0
    for attempt in range(1, PROBE_ATTEMPTS + 1):
        left = deadline - time.monotonic()
        if left <= 1.0:
            print(f"# backend probe: budget {PROBE_BUDGET_S:.0f}s "
                  f"exhausted after {attempts_run} attempt(s)",
                  file=sys.stderr)
            break
        attempts_run = attempt
        t0 = time.monotonic()
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--probe"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        try:
            out_s, err_s = proc.communicate(
                timeout=min(PROBE_TIMEOUT_S, left)
            )
        except subprocess.TimeoutExpired:
            proc.kill()
            _reap_detached(proc)
            print(f"# backend probe {attempt}/{PROBE_ATTEMPTS}: hung past "
                  f"{min(PROBE_TIMEOUT_S, left):.0f}s", file=sys.stderr)
            continue
        line = next((l for l in reversed(out_s.splitlines())
                     if l.startswith("{")), None)
        if line is not None and json.loads(line).get("probe"):
            rec = json.loads(line)
            note = (f"{rec['platform']} ({rec['device_kind']}) "
                    f"x{rec['n_devices']} (init {rec['init_s']}s)")
            print(f"# backend probe {attempt}: up -- {note} "
                  f"({time.monotonic() - t0:.0f}s)", file=sys.stderr)
            return True, note
        print(f"# backend probe {attempt}/{PROBE_ATTEMPTS}: rc="
              f"{proc.returncode} {line or ''} stderr tail: "
              f"{err_s[-300:]}", file=sys.stderr)
    failed = (False,
              f"backend unavailable: {attempts_run} probe attempts "
              f"failed/hung inside the {PROBE_BUDGET_S:.0f}s budget")
    _PROBE_FAILURES[platform] = failed
    return failed


# -------------------------------------------------------------------- parent
def median_or_none(xs):
    return round(statistics.median(xs), 3) if xs else None


def trace_jsonl_path():
    """--trace-jsonl PATH (or BENCH_TRACE_JSONL env): capture each run's
    per-stage latency decomposition + staleness-in-ms alongside throughput,
    one JSONL record per child sample."""
    if "--trace-jsonl" in sys.argv:
        i = sys.argv.index("--trace-jsonl")
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return os.environ.get("BENCH_TRACE_JSONL") or None


def run_parent() -> None:
    names = [
        s for s in os.environ.get(
            "BENCH_CONFIGS", "epsilon,mnist8m,rcv1"
        ).split(",") if s
    ]
    deadline = time.monotonic() + TOTAL_BUDGET_S
    samples = {name: [] for name in names}
    from asyncframework_tpu.utils.devices import CACHE_ENV, compile_cache_dir

    env = dict(os.environ)
    env[CACHE_ENV] = compile_cache_dir()  # every child shares one cache
    trace_out = trace_jsonl_path()
    if trace_out:
        env["BENCH_TRACE"] = "1"
    # gate BEFORE spending any child budget: no chip (or not the platform
    # BENCH_PLATFORM asked for), no benchmark -- nothing on stdout, non-zero
    skip_note = None
    alive, note = probe_backend(env)
    if not alive:
        print(f"bench: {note}", file=sys.stderr)
        sys.exit(1)
    # round-robin repeats so every config gets one sample before the budget
    # can run out
    arm_spent = {name: 0.0 for name in names}  # per-arm watchdog ledger
    for rep in range(REPEATS):
        if skip_note is not None:
            break
        for name in names:
            have = len(samples[name])
            if rep > 0 and have == 0:
                continue  # config is failing; don't burn budget re-proving it
            if arm_spent[name] > ARM_BUDGET_S:
                # per-arm watchdog: this config already burned its own
                # budget (wedged children count their full timeout) --
                # the remaining arms keep their share of the total
                print(f"# arm budget exhausted for {name} "
                      f"({arm_spent[name]:.0f}s > {ARM_BUDGET_S:.0f}s); "
                      f"skipping repeat {rep}", file=sys.stderr)
                continue
            if time.monotonic() > deadline and have >= 1:
                print(f"# budget exhausted; skipping {name} repeat {rep}",
                      file=sys.stderr)
                continue
            t0 = time.monotonic()
            child_wedged = False
            try:
                out = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--config", name],
                    capture_output=True, text=True, timeout=CHILD_TIMEOUT_S,
                    env=env,
                )
            except subprocess.TimeoutExpired:
                print(f"# {name} rep {rep}: child timed out", file=sys.stderr)
                child_wedged = True
            arm_spent[name] += time.monotonic() - t0
            if not child_wedged:
                sys.stderr.write(out.stderr)
                line = next(
                    (l for l in reversed(out.stdout.splitlines())
                     if l.startswith("{")), None,
                )
                if line is None:
                    print(f"# {name} rep {rep}: no JSON from child "
                          f"(rc={out.returncode})", file=sys.stderr)
                    child_wedged = True
                else:
                    rec = json.loads(line)
                    print(f"# {name} rep {rep}: {line} "
                          f"({time.monotonic() - t0:.0f}s wall)",
                          file=sys.stderr)
                    if rec.get("ok"):
                        samples[name].append(rec)
                    elif "WATCHDOG" in str(rec.get("note", "")):
                        child_wedged = True
            if child_wedged:
                # a wedge may mean the device went away mid-run; re-probe
                # before burning another child on it
                alive, note = probe_backend(env)
                if not alive:
                    skip_note = note
                    break
        if skip_note is not None:
            break

    configs_out = {}
    ratios = []
    headline_value = None
    gflops = None
    mfu_out = None
    for name in names:
        recs = samples[name]
        if not recs:
            configs_out[name] = {"ok": False, "runs": 0}
            if skip_note is not None:
                configs_out[name]["skipped"] = skip_note
            continue
        med_ratio = median_or_none([r["vs_baseline"] for r in recs])
        med_t = median_or_none([r["t_hit"] for r in recs])
        configs_out[name] = {
            "ok": True,
            "platform": recs[0].get("platform"),
            "device_kind": recs[0].get("device_kind"),
            "runs": len(recs),
            "t_hit_median_s": med_t,
            "vs_baseline_median": med_ratio,
            "t_hit_all": [r["t_hit"] for r in recs],
            "vs_baseline_all": [r["vs_baseline"] for r in recs],
            "updates_per_sec_median": median_or_none(
                [r["updates_per_sec"] for r in recs]
            ),
            "gflops_median": median_or_none([r["gflops"] for r in recs]),
            "kernel_gflops_median": median_or_none(
                [r["kernel_gflops"] for r in recs
                 if r.get("kernel_gflops") is not None]
            ),
            "kernel_ms_per_update_median": median_or_none(
                [r["kernel_ms_per_update"] for r in recs
                 if r.get("kernel_ms_per_update") is not None]
            ),
            "mfu_median": median_or_none(
                [r["mfu"] for r in recs if r.get("mfu") is not None]
            ),
            "fused_updates_per_sec_median": median_or_none([
                r["fused"]["updates_per_sec"] for r in recs
                if r.get("fused") and "updates_per_sec" in r["fused"]
            ]),
            "fused_vs_baseline_median": median_or_none([
                r["fused"]["vs_baseline"] for r in recs
                if r.get("fused")
                and r["fused"].get("vs_baseline") is not None
            ]),
        }
        traced = [r["trace"] for r in recs if r.get("trace")]
        if traced:
            # latest sample's full decomposition rides the artifact: the
            # BENCH trajectory gains per-stage p50/p95/p99 + staleness-ms
            configs_out[name]["trace"] = traced[-1]
        telem = [r["telemetry"] for r in recs if r.get("telemetry")]
        if telem:
            # latest sample's convergence summary + SLO verdicts: the
            # artifact records statistical efficiency, not just updates/s
            configs_out[name]["telemetry"] = telem[-1]
        ratios.append(med_ratio)
        if name == "epsilon":
            headline_value = med_t
        if name == "mnist8m":
            gflops = configs_out[name]["gflops_median"]
            mfu_out = configs_out[name]["mfu_median"]
    if headline_value is None:  # epsilon failed: fall back to any config
        for name in names:
            if configs_out[name].get("ok"):
                headline_value = configs_out[name]["t_hit_median_s"]
                break
    if gflops is None:
        for name in names:
            if configs_out[name].get("ok"):
                gflops = configs_out[name]["gflops_median"]
                mfu_out = configs_out[name]["mfu_median"]
                break
    ok_all = all(configs_out[n].get("ok") for n in names)
    # a failed config contributes ratio 0.0: vs_baseline is defined as
    # "EVERY dataset beats its reference estimate by at least this factor",
    # so a partial failure must not report the min over survivors
    for n in names:
        if not configs_out[n].get("ok"):
            ratios.append(0.0)
    if ok_all:
        unit = "s"
    elif skip_note is not None and not any(
        configs_out[n].get("ok") for n in names
    ):
        unit = "s (SKIPPED: backend unavailable)"
    else:
        unit = "s (SOME CONFIGS FAILED)"
    payload = {
        "metric": "asgd_time_to_target_3datasets",
        "value": headline_value if headline_value is not None else 0.0,
        "unit": unit,
        "vs_baseline": round(min(ratios), 2) if ratios else 0.0,
        "configs": configs_out,
        "gflops": gflops,
        "mfu": mfu_out,
    }
    if skip_note is not None:
        payload["note"] = skip_note
    if os.environ.get("BENCH_DCN", "1") != "0":
        # DCN data-plane bench (CPU loopback, device-independent): wire
        # bytes per update and pull/push payload shapes per pull mode
        payload["dcn"] = collect_dcn_block(env)
        if os.environ.get("BENCH_DCN_MESH", "1") != "0":
            # mesh gradient-plane arm (ISSUE 11): single-device vs
            # 8-forced-host-device worker step on the dense config; its
            # own child so the forced device count cannot perturb the
            # other arms' shard placement
            if not isinstance(payload["dcn"], dict):
                payload["dcn"] = {"error": str(payload["dcn"])}
            payload["dcn"]["mesh"] = collect_dcn_mesh_block(env)
    if os.environ.get("BENCH_SERVE", "1") != "0":
        # serving-tier bench (CPU loopback): QPS vs freshness lag per
        # replica count with training concurrently running, including the
        # SIGKILL-a-replica-mid-load failover arm
        payload["serve"] = collect_serve_block(env)
    if os.environ.get("BENCH_RELAY", "1") != "0":
        # relaycast wire bench (ISSUE 12, CPU loopback): PS subscribe
        # egress per distributed version -- direct (N x control) vs
        # relay tree raw vs compressed -- plus quantized-PUSH wire
        # bytes per update per codec
        payload["relay"] = collect_relay_block(env)
    if os.environ.get("BENCH_NATIVE", "1") != "0":
        # native data-plane bench (PR 19, CPU loopback): python vs
        # native per codec unit, DCN updates/s with the codecs in the
        # loop, shm-ring vs loopback transport throughput
        payload["native"] = collect_native_block(env)
    if trace_out:
        with open(trace_out, "w") as f:
            for name in names:
                for rep, rec in enumerate(samples[name]):
                    if rec.get("trace"):
                        f.write(json.dumps({
                            "config": name, "rep": rep,
                            "updates_per_sec": rec.get("updates_per_sec"),
                            "trace": rec["trace"],
                        }) + "\n")
        payload["trace_jsonl"] = trace_out
    emit(payload)
    if not ok_all:
        sys.exit(1)  # a config with no sample is a failed benchmark


#: wire benches: they measure the data plane, not the chip, so their
#: children run on the CPU backend by assignment (set before JAX loads)
CPU_MODES = {
    "--dcn-mesh": ("dcn_mesh", run_dcn_mesh_child),
    "--dcn": ("dcn", run_dcn_child),
    "--serve": ("serve", run_serve_child),
    "--relay": ("relay", run_relay_child),
    "--native": ("native", run_native_child),
}


def main() -> None:
    """Dispatch one mode.  Every mode that fails exits non-zero, after
    printing its parseable error line where it has one."""
    for flag, (key, child) in CPU_MODES.items():
        if flag not in sys.argv:
            continue
        os.environ["JAX_PLATFORMS"] = "cpu"
        from asyncframework_tpu.utils.devices import setup_compile_cache

        setup_compile_cache()
        try:
            child()
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            emit({key: {"error": f"{type(e).__name__}: {str(e)[:200]}"}})
            os._exit(1)
        os._exit(0)
    if "--probe" in sys.argv:
        # parent owns the timeout; nothing here may block interpreter exit
        try:
            run_probe()
        except (Exception, SystemExit) as e:
            emit({"probe": False,
                  "note": f"{type(e).__name__}: {str(e)[:200]}"})
            os._exit(1)
        os._exit(0)
    if "--config" in sys.argv:
        name = sys.argv[sys.argv.index("--config") + 1]
        arm_watchdog(name)
        try:
            run_child(name)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            emit({"config": name, "ok": False,
                  "note": f"FAILED: {type(e).__name__}: {str(e)[:200]}"})
            sys.exit(1)
    else:
        run_parent()


if __name__ == "__main__":
    main()
