"""A sparse cell's step and blocked evaluation against the plain reference,
at the cell's size.

    python3 benchmark/check_sparse.py --workload <name> --seed <n> [--bf16-model]

The builder's tool beside ``benchmark/run.py``, as ``check_saga.py`` is: the
cell's dataset and solver are built exactly as ``run.py`` builds them, and
then, with no run and no timed window, two programs of the solver are held
to ``benchmark/reference.py`` (float32 ``jax.numpy`` at precision
"highest", no program code) on ONE whole shard:

- ``step``: the worker step's gradient at a seeded model against
  ``reference.full_gradient`` with the step's own sampled rows as weights
  (the Bernoulli mask the step draws from its key, drawn again here).
  Over the largest entry of the reference's gradient.  Limit
  ``STEP_LIMIT``.
- ``evaluation``: the trajectory evaluation of eight seeded models (the
  first ``w = 0``) against ``reference.objective`` of each.  Relative, the
  largest over the eight.  Limit ``EVAL_LIMIT``.

Each comes with the seconds one fenced call took (the median of three) and
what that is a slot.  The last stdout line is ``{"check_sparse": {...,
"correct": bool}}`` and the exit code is 0 only where ``correct``.
``--bf16-model`` is the negative control of both: the program is handed the
model rounded to bf16 (``lax.reduce_precision``, an op no compiler may
drop) while the reference keeps the float32 one, which has to come out as
NOT correct.  It is made here, not a switch of the program.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path[0] = ROOT  # run as a script: import the package, not siblings
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import plan as plan_mod, reference  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

#: the step's gradient off the reference's, over its largest entry, and
#: the evaluation's objective off the reference's, relative.  Each lies
#: between two readings at 2,865,039 x 40 on the v5e (PR 32, PERF.md
#: section 6).  The step: the sound program 1.32e-5, 1.44e-5 and 3.72e-5
#: over three seeds (float32 sums of 440,000 terms on the hottest column in
#: another order), ``--bf16-model`` 2.17e-4.  The evaluation: 2.14e-6 in
#: every seed (the float32 sum of 2.9M rows of ln 2 at ``w = 0``), the
#: control 5.46e-4.
STEP_LIMIT = 1e-4
EVAL_LIMIT = 3e-5


def _fenced_median(call, repeats: int = 3):
    """The result of ``call()`` and the median seconds of ``repeats``
    fenced calls after a first one that compiles."""
    import jax

    out = jax.block_until_ready(call())
    times = []
    for _ in range(repeats):
        t = time.monotonic()
        out = jax.block_until_ready(call())
        times.append(time.monotonic() - t)
    return out, float(np.median(times))


def step(solver, shard, d: int, loss: str, batch_rate: float, seed: int,
         rounded) -> dict:
    import jax
    import jax.numpy as jnp

    rows = int(shard.y.shape[0])
    w = jnp.asarray(np.random.default_rng(seed).standard_normal(d),
                    jnp.float32)
    key = jax.random.PRNGKey(seed % 1000)
    (g, _key), seconds = _fenced_median(
        lambda: solver._step(shard.cols, shard.vals, shard.y, rounded(w), key))
    # the rows the step sampled: its own draw, made again
    _next, sub = jax.random.split(key)
    mask = jax.random.bernoulli(sub, batch_rate, (rows,))
    want = reference.full_gradient(shard, w, d, loss,
                                   weights=mask.astype(jnp.float32))
    off = float(np.max(np.abs(np.asarray(g, np.float64) - want))
                / np.max(np.abs(want)))
    slots = solver._task_rows(rows) * int(shard.cols.shape[1])
    return {"rows": rows, "sampled": int(mask.sum()), "off": off,
            "limit": STEP_LIMIT, "within": off <= STEP_LIMIT,
            "seconds": seconds, "slot_ns": seconds / slots * 1e9}


def evaluation(solver, shard, d: int, loss: str, seed: int, rounded) -> dict:
    import jax.numpy as jnp

    ev = solver._eval
    rs = np.random.default_rng(seed + 1)
    W = np.stack([0.25 * j * rs.standard_normal(d)
                  for j in range(ev.snapshots_per_call)]).astype(np.float32)
    got, seconds = _fenced_median(
        lambda: ev(shard.cols, shard.vals, shard.y, rounded(jnp.asarray(W))))
    rows = int(shard.y.shape[0])
    got = np.asarray(got, np.float64) / rows
    want = np.array([reference.objective([shard], w, loss) for w in W])
    off = float(np.max(np.abs(got - want) / np.abs(want)))
    slots = ev.blocks(rows) * ev.block_rows(rows) * int(shard.cols.shape[1])
    return {"snapshots": len(W), "blocks": ev.blocks(rows), "off": off,
            "limit": EVAL_LIMIT, "within": off <= EVAL_LIMIT,
            "seconds": seconds, "slot_ns": seconds / slots * 1e9}


def main(argv=None, manifest_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--bf16-model", action="store_true")
    args = ap.parse_args(argv)
    man = manifest_mod.Manifest(manifest_path or manifest_mod.MANIFEST)
    cell = man.workload(args.workload)
    config = man.config(cell["config"])
    plan = plan_mod.resolve(config, man.traffic(cell["traffic"]))
    if plan["solver"] != "asgd" or config["kind"] != "sparse":
        raise ValueError(f"{args.workload}: no sparse ASGD cell")

    import jax

    from asyncframework_tpu import solvers
    from asyncframework_tpu.solvers.base import SolverConfig
    from asyncframework_tpu.utils import devices as prog_devices

    prog_devices.setup_compile_cache()
    devs = bench_run._devices()
    ds = bench_run.build_dataset(config, plan["num_workers"], devs, args.seed)
    cfg = SolverConfig(**plan_mod.solver_config_kwargs(
        plan, args.seed, 1.0, False
    ))
    solver = solvers.ASGD(ds, None, cfg, devices=devs)
    shard = ds.shard(args.seed % ds.num_workers)
    to_bf16 = jax.jit(lambda v: jax.lax.reduce_precision(v, 8, 7))
    rounded = to_bf16 if args.bf16_model else (lambda v: v)
    out = {"workload": args.workload, "seed": args.seed,
           "device": devs[0].device_kind, "bf16_model": args.bf16_model,
           "step": step(solver, shard, ds.d, plan["loss"],
                        plan["batch_rate"], args.seed, rounded),
           "evaluation": evaluation(solver, shard, ds.d, plan["loss"],
                                    args.seed, rounded)}
    out["correct"] = out["step"]["within"] and out["evaluation"]["within"]
    print(json.dumps({"check_sparse": out}), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
