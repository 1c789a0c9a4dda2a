"""One cell of ``BENCHMARK.json``, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips.  Set-up (process start to the
call of the solver's ``run()``): chip attach, data generated on the device
from ``--seed`` by the program's generator, the solver the CLI builds, and
a fenced warm-up on that same solver object, so that the one time-bounded
run that follows compiles nothing.  The run's update budget is out of reach
and ``run_timeout_s`` is the window; ``TrainResult.elapsed_s`` is taken
after the final model's read-back, so the rate is fenced.  After the window
the benchmark checks the run (``correct``) against its own reference and
prints the contract's object as the last stdout line; everything else goes
on earlier ``{"info": ...}`` lines, the last of which says how long the
whole process took (``wall_s``) beside the ``seconds + RUN_OVERHEAD_S`` the
harness's budget reckons a run at.

With ``--trace 1`` the program's span sampling is on in that run, and a
second, short run of the same solver follows under ``jax.profiler``: a run
of its own, so that the profiler is never on, started or stopped inside
the run that is checked.  The line then carries the cell's per-layer
metrics, not its end-to-end ones.
"""

import time

T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path[0] = ROOT  # run as a script: import the package, not siblings
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import plan as plan_mod, roofline, target  # noqa: E402

#: anything else is a failure, never a fallback (tests relax it themselves)
REQUIRED_PLATFORM = "tpu"
#: what a run leaves behind (listed in .gitignore): the profiler's files
OUT_DIR = os.path.join(ROOT, ".bench_out")
#: the profiled run's length, and what is cut from each end of it (the
#: pool's ramp-up, the drain) before the trace is reduced: 3 s stay
TRACE_RUN_S = 4.0
TRACE_EDGE_S = 0.5
#: what a full check allows a run beyond its window (the contract's
#: ``run_seconds`` + 60): set-up, the evaluation of the trajectory, the
#: reference's passes, a traced run's second run.  The budget test reads it;
#: a run that takes longer says so on stderr and is reported as any other.
RUN_OVERHEAD_S = 60
#: the program evaluates its trajectory as an (n, S) matrix-matrix product
#: at default precision, which on the v5e rounds the operands to bf16: its
#: last objective sat 4.3e-6 of ``f(0)`` above the reference's with bf16
#: shards and 6.2e-6 with f32 shards (PR 22, chip).  So the two must agree
#: within 1e-3 relative plus 2e-5 of ``f(0)``: 2% of a 0.001 target, three
#: times the rounding seen, and far under what a wrong model would show.
FINAL_REL, FINAL_ABS_OF_F0 = 1e-3, 2e-5
#: an ASAGA run's ``alpha_bar`` off the mean of the table it left
#: (``reference_saga.history_drift``), in units of ``max |X^T y / n|``.  Set
#: from two readings at 8,100,000 x 784 bf16 on the v5e: the largest the
#: program gave over 35 seeds, 6.25e-7 (PR 25 and PR 29: f32 sums of 1M
#: terms in another order), and the smallest of the control, the vector
#: that advances ``alpha_bar`` rounded to bf16 on every accept
#: (``check_saga.py --round-delta``, three seeds, PR 29): 3.91e-5.
#: A configuration whose own two readings lie elsewhere states its limit
#: as ``pins["history_drift_limit"]``; one over padded ELL asks for the
#: same gap in each column's own unit as well, with
#: ``pins["history_by_column_limit"]`` (``reference_saga.history_by_column``):
#: at criteo's shape one column fills 7.4% of the slots and sets the unit,
#: sound runs read 2.9e-6 to 3.3e-6 and the control 3.8e-6 to 2.2e-5, which
#: no limit parts, where by the column they read 7.4e-7 to 8.1e-7 and 3.8e-4
#: to 6.3e-4 (my chip runs, PR 45; PERF.md section 7).
DRIFT_LIMIT = 2e-6

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
HIT_EVENT = "/jax/compilation_cache/cache_hits"


def info(**kw) -> None:
    print(json.dumps({"info": kw}), flush=True)


def _json_number(x):
    """``x`` as strict JSON takes it: a NaN or an infinity as its name."""
    x = float(x)
    return x if math.isfinite(x) else repr(x)


def _devices():
    import jax

    return jax.devices()


class CompileLog:
    """Every executable JAX builds or loads in this process, with the time
    it started, from JAX's own monitoring hooks."""

    def __init__(self):
        import jax

        self.builds = []  # (start, seconds)
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, seconds, **_kw):
        if name == COMPILE_EVENT:
            self.builds.append((time.monotonic() - seconds, seconds))

    def _event(self, name, **_kw):
        if name == HIT_EVENT:
            self.hits += 1
        elif name == MISS_EVENT:
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for start, _s in self.builds if t0 <= start < t1)


def generator_call(data: dict, num_workers: int, devices, seed: int):
    """The program's generator for the configuration's ``kind`` and the
    arguments it is called with.  The configuration's ``generator`` mapping,
    where it has one, goes to it as keyword arguments, whatever they are:
    how the values are stored, how the columns are drawn, what the labels
    are.  A key the generator does not take is its ``TypeError``."""
    import jax.numpy as jnp

    extra = data.get("generator", {})
    if data["kind"] == "dense":
        from asyncframework_tpu.data.sharded import ShardedDataset

        return (
            ShardedDataset.generate_on_device,
            (data["n"], data["d"], num_workers, devices),
            dict(seed=seed, noise=data["noise"],
                 dtype=jnp.dtype(data["storage_dtype"]), **extra),
        )
    if data["kind"] == "sparse":
        from asyncframework_tpu.data.sparse import SparseShardedDataset

        return (
            SparseShardedDataset.generate_on_device,
            (data["n"], data["d"], data["nnz_per_row"], num_workers, devices),
            dict(seed=seed, noise=data["noise"], **extra),
        )
    raise ValueError(f"unknown dataset kind {data['kind']!r}")


def build_dataset(data: dict, num_workers: int, devices, seed: int):
    """The cell's dataset on the device, from the seed, by the program's
    own generators (what they produce is pinned after the window)."""
    import jax

    generate, args, kwargs = generator_call(data, num_workers, devices, seed)
    ds = generate(*args, **kwargs)
    jax.block_until_ready([
        (s.cols, s.vals, s.y) if data["kind"] == "sparse" else (s.X, s.y)
        for s in ds.shards.values()
    ])
    return ds


def describe_data(ds, data: dict) -> dict:
    shards = [ds.shard(w) for w in range(ds.num_workers)]
    sparse = data["kind"] == "sparse"
    lead = [s.vals if sparse else s.X for s in shards]
    out = {
        "kind": data["kind"], "n": ds.n, "d": ds.d,
        "shard_rows": [int(a.shape[0]) for a in lead],
        "dtype": str(lead[0].dtype),
        "itemsize": int(lead[0].dtype.itemsize),
        "shards_per_device": {},
    }
    if sparse:
        out["width"] = int(lead[0].shape[1])
        out["index_itemsize"] = int(shards[0].cols.dtype.itemsize)
    for a in lead:
        key = str(a.device)
        out["shards_per_device"][key] = out["shards_per_device"].get(key, 0) + 1
    return out


def rtt_ms(n: int = 20) -> float:
    """Median of ``n`` fenced tiny dispatches: a set-up diagnostic."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1.0)
    x = f(jnp.zeros((), jnp.float32)).block_until_ready()
    times = []
    for _ in range(n):
        t = time.monotonic()
        x = f(x).block_until_ready()
        times.append((time.monotonic() - t) * 1e3)
    return float(np.median(times))


def profiled_run(solver, cfg, run, trace_dir: str, rounds=None):
    """The device trace: a short run of its own under ``jax.profiler``.

    The profiler is opened before the call of ``run()`` and closed after it
    returns.  Neither call falls inside a run: either can hold every Python
    thread for a while, and the program's heartbeat monitor declares an
    idle executor lost after 2 s of silence, so a profiler opened in the
    middle of the checked run can make that run report ``workers_lost``
    (section 6 of PERF.md).  Span sampling is off here and one snapshot is
    kept, so that the evaluation after the run compiles one shape.  Returns
    the window to reduce, in the trace's own clock (seconds since the
    profiler's start), and what the run did and the two calls cost."""
    import jax

    solver.cfg = dataclasses.replace(
        cfg, run_timeout_s=TRACE_RUN_S, trace_sample=None,
        printer_freq=2**30,
        num_iterations=cfg.num_iterations if rounds is None else rounds,
    )
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # ten busy threads: too heavy
    opts.host_tracer_level = 2
    t0 = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t1 = time.monotonic()
    try:
        t_call = time.monotonic()
        res = run()
    finally:
        t2 = time.monotonic()
        jax.profiler.stop_trace()
        t3 = time.monotonic()
        solver.cfg = cfg
    # the trace's clock starts somewhere inside the call that opened it:
    # take the later bound for the window's start and the earlier for its end
    edge = min(TRACE_EDGE_S, res.elapsed_s / 4)
    window = (t_call - t0 + edge, t_call - t1 + res.elapsed_s - edge)
    ran = {"start_call_s": t1 - t0, "stop_call_s": t3 - t2,
           "called_at": t_call, "accepted": res.accepted,
           "elapsed_s": res.elapsed_s,
           "extras": {k: v for k, v in res.extras.items()
                      if isinstance(v, (bool, int, float, str))}}
    return window, ran


def history_compared(shards, res, n: int, d: int, pins: dict) -> dict:
    """The table an ASAGA run left against the mean it kept of it, ``{name:
    (value, limit)}``: what the final objective cannot see (a delta rounded
    to bf16 still crosses the target).  ``history_within`` always; where
    the configuration's pins state a limit for it, ``history_by_column``
    from the same pass."""
    from benchmark import reference_saga

    table = (shards, [res.extras["alpha"][w] for w in range(len(shards))],
             res.extras["alpha_bar"], n)
    drift_limit = pins.get("history_drift_limit", DRIFT_LIMIT)
    if "history_by_column_limit" not in pins:
        return {"history_within": (
            reference_saga.history_drift(*table, d=d), drift_limit)}
    got = reference_saga.history_by_column(*table, d=d)
    return {"history_within": (got["drift"], drift_limit),
            "history_by_column": (got["by_column"],
                                  pins["history_by_column_limit"])}


def verify(ds, data: dict, config: dict, plan: dict, res, f0: float,
           f_final: float, goal: float):
    """The run against the benchmark's own reference: the generator's pins,
    the final model's objective, an ASAGA run's history, and the engine's
    own guarantees.  Returns what was compared, ``{name: (value, limit)}``
    (the run is correct where every value is at or under its limit), the
    pins as measured, and the reference's final objective."""
    from benchmark import reference

    shards = [ds.shard(w) for w in range(ds.num_workers)]
    pins, f0_ref = reference.data_pins(shards, plan["loss"])
    f_final_ref = reference.objective(shards, res.final_w, plan["loss"])
    want = config["pins"]
    tol = want["tolerance"]
    lo, hi = want["label_second_moment_min"], want["label_second_moment_max"]
    shapes = (
        data["n"] == config["n"]
        and data["d"] == config["d"]
        and data["dtype"] == want["shard_dtype"]
        and sum(data["shard_rows"]) == data["n"]
        and data.get("width") == want.get("ell_width")
    )
    compared = {
        "shapes": (0 if shapes else 1, 0),
        "row_second_moment": (
            abs(pins["row_second_moment"] - want["row_second_moment"]),
            tol * want["row_second_moment"],
        ),
        "label_second_moment": (
            abs(pins["label_second_moment"] - (lo + hi) / 2), (hi - lo) / 2
        ),
        "objective_at_zero": (abs(f0 - f0_ref), tol * f0_ref),
        "final_objective_agrees": (
            abs(f_final - f_final_ref),
            FINAL_REL * f_final_ref + FINAL_ABS_OF_F0 * f0_ref,
        ),
        "final_under_target": (f_final_ref, goal),
        "staleness_bounded": (res.max_staleness, plan["taw"]),
        "no_worker_lost": (res.extras.get("workers_lost", 0), 0),
        "no_shard_moved": (res.extras.get("shards_moved", 0), 0),
    }
    if "nnz_per_row" in want:
        compared["nnz_per_row"] = (
            abs(pins.get("nnz_per_row", 0.0) - want["nnz_per_row"]),
            tol * want["nnz_per_row"],
        )
    if plan["solver"] == "asaga":
        compared.update(history_compared(shards, res, ds.n, ds.d, want))
    return compared, pins, f_final_ref


def run_cell(args, man: "manifest_mod.Manifest") -> int:
    cell = man.workload(args.workload)
    config = man.config(cell["config"])
    mix = man.traffic(cell["traffic"])
    plan = plan_mod.resolve(config, mix)
    trace_on = bool(args.trace)

    # the program's compile cache first: <checkout>/.jax_cache, or where
    # JAX_COMPILATION_CACHE_DIR says
    from asyncframework_tpu.utils import devices as prog_devices

    cache_dir = prog_devices.setup_compile_cache()
    compiles = CompileLog()
    devs = _devices()
    platform = devs[0].platform
    if platform != REQUIRED_PLATFORM or len(devs) != cell["chips"]:
        print(
            f"benchmark: cell {args.workload} needs {cell['chips']} "
            f"{REQUIRED_PLATFORM} device(s); JAX reports {len(devs)} of "
            f"platform {platform!r}", file=sys.stderr,
        )
        return 2
    spans = {"attach_s": time.monotonic() - T0}
    peak_table = (
        roofline.peaks(devs[0].device_kind) if platform == "tpu" else None
    )
    cache_before = prog_devices.cache_entries(cache_dir)
    info(workload=args.workload, seed=args.seed, seconds=args.seconds,
         trace=trace_on, plan=plan, cache_dir=cache_dir,
         cache_entries=cache_before, rtt_ms=rtt_ms(),
         attach_s=spans["attach_s"])

    # ------------------------------------------------------------- set-up
    t = time.monotonic()
    ds = build_dataset(config, plan["num_workers"], devs, args.seed)
    spans["data_gen_s"] = time.monotonic() - t
    data = describe_data(ds, config)

    from asyncframework_tpu import solvers
    from asyncframework_tpu.metrics import trace as prog_trace
    from asyncframework_tpu.solvers.base import SolverConfig

    t = time.monotonic()
    cfg = SolverConfig(**plan_mod.solver_config_kwargs(
        plan, args.seed, args.seconds, trace_on
    ))
    solver_cls = {"asgd": solvers.ASGD, "asaga": solvers.ASAGA}[plan["solver"]]
    solver = solver_cls(ds, None, cfg, devices=devs)
    sync = plan["mode"] == "sync"
    run = solver.run_sync if sync else solver.run
    # the warm-up is a short run of the SAME solver object (its jitted
    # steps are per object), fenced by the read-back of its final model
    warm_steps = 3 if sync else 2 * plan["num_workers"]
    solver.cfg = dataclasses.replace(cfg, num_iterations=warm_steps)
    warm = run()
    trace_rounds = None
    if sync:
        # run_sync has no deadline: size the round count from the window
        rate = warm_steps / warm.elapsed_s
        cfg = dataclasses.replace(
            cfg, num_iterations=max(1, int(args.seconds * rate))
        )
        trace_rounds = max(1, int(TRACE_RUN_S * rate))
    solver.cfg = cfg
    spans["warmup_s"] = time.monotonic() - t
    cache_after = prog_devices.cache_entries(cache_dir)
    cache = {"entries_before": cache_before, "entries_after": cache_after,
             "hits": compiles.hits, "misses": compiles.misses,
             "builds": len(compiles.builds)}
    prog_trace.reset_aggregator()
    spans["setup_s"] = time.monotonic() - T0

    # ----------------------------------------------------------- the window
    t_call = time.monotonic()
    res = run()
    t_post = time.monotonic()
    program_trace = prog_trace.aggregator().snapshot() if trace_on else None
    # the solver's own warm-up comes between the call and its clock: on a
    # warmed object it is milliseconds, so the window is taken to start at
    # the call (an executable built in the warm-up counts against the run)
    compiles_in_window = compiles.between(t_call, t_call + res.elapsed_s)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]

    # ------------------------------------------------- after the window
    objective = [float(f) for _t, f in res.trajectory]
    updates = target.snapshot_updates(
        len(objective), plan["printer_freq"], res.accepted,
        per_snapshot=plan["num_workers"] if sync else 1,
    )
    goal = plan["target_fraction"] * objective[0]
    hit = target.updates_to_target(updates, objective, goal)
    compared, pins, f_final_ref = verify(
        ds, data, config, plan, res, objective[0], objective[-1], goal
    )
    compared["target_crossed"] = (0 if hit is not None else 1, 0)
    compared["no_compile_in_window"] = (compiles_in_window, 0)
    # a NaN is at or under no limit
    checks = {name: bool(v <= lim) for name, (v, lim) in compared.items()}
    spans["post_s"] = time.monotonic() - t_post
    # between the run's fence (``elapsed_s`` ends at the final model's
    # read-back) and its return the program evaluates its trajectory
    spans["trajectory_eval_s"] = t_post - t_call - res.elapsed_s

    record = {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "plan": plan, "data": data,
        "spans": spans, "cache": cache, "peaks": peak_table,
        "result": {
            "accepted": res.accepted, "dropped": res.dropped,
            "rounds": res.rounds, "elapsed_s": res.elapsed_s,
            "max_staleness": res.max_staleness,
            "extras": {k: v for k, v in res.extras.items()
                       if isinstance(v, (bool, int, float, str))},
        },
        "target": {"f0": objective[0], "objective": goal,
                   "updates_to_target": hit,
                   "final_objective": objective[-1],
                   "final_objective_reference": f_final_ref},
        "program_trace": program_trace,
        "memory_peak_bytes": max(mem),
        "compiles_in_window": compiles_in_window,
    }
    info(spans=spans, cache=cache, data=data, result=record["result"],
         target=record["target"], pins=pins, checks=checks,
         snapshots=len(objective), memory_peak_by_chip=mem,
         run_wall_s=t_post - t_call,
         trajectory=list(zip(updates, objective)))

    # ------------------------------------- the device trace, a run of its own
    trace = None
    if trace_on:
        from benchmark import trace_reduce

        trace_dir = os.path.join(OUT_DIR, "trace-" + args.workload)
        t_prof = time.monotonic()
        window, ran = profiled_run(
            solver, cfg, run, trace_dir, rounds=trace_rounds
        )
        ran["compiles"] = compiles.between(
            ran["called_at"], ran["called_at"] + ran["elapsed_s"]
        )
        path = trace_reduce.newest_xplane(trace_dir)
        if path is not None:
            trace = trace_reduce.reduce_file(path, window=window)
            info(trace_file=os.path.relpath(path, ROOT),
                 trace_bytes=os.path.getsize(path),
                 chips=trace and trace["chips"],
                 modules=trace and trace["modules"])
        info(profiled_run=ran, window=window,
             profiled_s=time.monotonic() - t_prof)

    spans["wall_s"] = time.monotonic() - T0
    budget_s = args.seconds + RUN_OVERHEAD_S
    info(wall_s=spans["wall_s"], budget_s=budget_s,
         trajectory_eval_s=spans["trajectory_eval_s"])
    if spans["wall_s"] > budget_s:
        print(f"benchmark: {args.workload} seed {args.seed} took "
              f"{spans['wall_s']:.1f} s, over the {budget_s:.1f} s "
              f"(seconds + RUN_OVERHEAD_S) a full check reckons a run at",
              file=sys.stderr)
    failing = sorted(k for k, ok in checks.items() if not ok)
    if failing:
        # the reason goes where a reader of a refused run looks first
        print(f"benchmark: {args.workload} seed {args.seed} is not correct: "
              f"{failing}; result {record['result']}; target "
              f"{record['target']}; pins {pins}", file=sys.stderr)
    # every number compared beside its limit, in every run: the last lines
    # of stderr here, the last key of the result line below
    compared = {name: {"value": _json_number(v), "limit": _json_number(lim)}
                for name, (v, lim) in compared.items()}
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}"
              f"{'' if checks[name] else '  NOT WITHIN'}", file=sys.stderr)

    kind = "per_layer" if trace_on else "end_to_end"
    device = {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": int(max(mem)),
    }
    line = {
        "correct": all(checks.values()),
        "attempted": int(res.accepted + res.dropped),
        "failed": int(res.extras.get("workers_lost", 0)),
        "metrics": man.read_metrics(kind, args.workload, record, trace),
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None, manifest_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest_mod.Manifest(manifest_path or manifest_mod.MANIFEST)
    return run_cell(args, man)


if __name__ == "__main__":
    sys.exit(main())
