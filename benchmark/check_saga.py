"""An ASAGA cell's history path against the plain reference, at the cell's size.

    python3 benchmark/check_saga.py --workload <name> --seed <n> --seconds <s> [--round-delta]

The builder's tool beside ``benchmark/run.py``: the cell's solver is built,
warmed and run for ``--seconds`` exactly as ``run.py`` does it, and then,
outside any timed window, the state the run left is held to
``reference_saga`` and ``reference`` (float32 ``jax.numpy`` at precision
"highest", no program code):

- ``history``: ``alpha_bar`` against the mean of the table the run left
  (``reference_saga.history_drift``, in units of ``max |X^T y / n|``; the
  program's own ``history_drift`` is reported beside it).  Limit
  ``run.DRIFT_LIMIT``, or the configuration's own
  (``pins["history_drift_limit"]``); where the configuration states
  ``pins["history_by_column_limit"]``, the same gap in each column's own
  unit beside it (``reference_saga.history_by_column``).  ``run.py:
  verify`` makes this comparison in every run of an ASAGA cell
  (``history_within``, ``history_by_column``); it is here for the control.
- ``objective``: the trajectory's last value against
  ``reference.objective`` of the final model, by ``run.py``'s own limits.
- ``task``: one step + table delta + commit on one whole shard, seeded
  ``w`` and history, a third of the slice moved on between dispatch and
  accept, against ``reference_saga.task``.  Limit ``TASK_LIMIT``, over the
  largest entry of each vector.  Only this file makes it.  The solver's
  programs are called with the shard's own operands in either storage; a
  padded-ELL step returns its sample packed (``diff_sel``, ``idx``,
  ``valid``), which is laid back over the shard's rows: the mask the
  reference is given, and ``diff`` compared on the sampled rows.

The last stdout line is ``{"check_saga": {..., "correct": bool}}`` and the
exit code is 0 only where ``correct``.  ``--round-delta`` is the negative
control of ``history``: the run is made with the vector that advances
``alpha_bar`` rounded to bf16 on EVERY accept (the ``delta`` operand of the
apply, whether it is the step's own ``g`` or the recomputed table delta;
``lax.reduce_precision``, an op no compiler may drop), which has to come
out as NOT correct.  It is a patch made here, not a switch of the program.
"""

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path[0] = ROOT  # run as a script: import the package, not siblings
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import plan as plan_mod, reference, reference_saga  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

#: one task's ``g``, ``delta``, ``diff`` and committed slice off the
#: reference's, over the largest entry: 1.2e-6 at most on a 1,012,500-row
#: dense shard (PR 25); a vector rounded to bf16 reads 1e-3.  Over padded
#: ELL the program's ``g`` is ONE scatter-add of the packed sample, a
#: float32 chain as long as the hottest column's share of it: 1.2e-6 to
#: 1.8e-5 over eight runs at criteo's shape (1.4M to 2.9M rows a shard,
#: PR 45), as ``check_sparse.STEP_LIMIT`` found the ASGD step's.
TASK_LIMIT = {"dense": 5e-6, "sparse": 1e-4}


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def history(shards, res, n: int, d: int, pins: dict) -> dict:
    """What ``run.py: verify`` compares of the table, by its own function:
    the drift, and the same gap column by column where the configuration
    states a limit for it."""
    got = bench_run.history_compared(shards, res, n, d, pins)
    out = {"program_history_drift": res.extras.get("history_drift")}
    out["drift"], out["limit"] = got["history_within"]
    if "history_by_column" in got:
        out["by_column"], out["by_column_limit"] = got["history_by_column"]
    out["within"] = all(value <= limit for value, limit in got.values())
    return out


def objective(shards, res, loss: str) -> dict:
    f0, f_final = res.trajectory[0][1], res.trajectory[-1][1]
    f_ref = reference.objective(shards, res.final_w, loss)
    off = abs(f_final - f_ref)
    return {"trajectory": f_final, "reference": f_ref, "off_over_f0": off / f0,
            "within": off <= (bench_run.FINAL_REL * f_ref
                              + bench_run.FINAL_ABS_OF_F0 * f0)}


def task(solver, shard, d: int, seed: int, limit: float) -> dict:
    import jax
    import jax.numpy as jnp

    from asyncframework_tpu.ops import steps

    rows = shard.size
    rs = np.random.default_rng(seed)
    w = jnp.asarray(0.05 * rs.standard_normal(d), jnp.float32)
    read = rs.standard_normal(rows)
    a_read = jnp.asarray(read, jnp.float32)
    a_cur = jnp.asarray(
        np.where(rs.random(rows) < 0.3, rs.standard_normal(rows), read),
        jnp.float32)
    g, *payload, _key = solver._step(
        *shard.operands, w, a_read, jax.random.PRNGKey(seed % 1000))
    # by the payload, as the solver's accept path takes it
    if solver._compacted:
        diff_sel, idx, valid, c_sel, v_sel = payload
        delta = solver._table_delta(c_sel, v_sel, diff_sel, a_cur, idx)
        committed = solver._commit(a_cur, diff_sel, idx, valid)
        # the packed sample back over the shard's rows: the mask the step
        # drew, and its candidate scalars where it drew (the reference's
        # ``diff`` holds every row's, and is compared where the mask is set)
        filled = np.asarray(valid) > 0
        sel = np.asarray(idx)[filled]
        mask = np.zeros(rows, np.float32)
        mask[sel] = 1.0
        diff_h = np.zeros(rows, np.float32)
        diff_h[sel] = np.asarray(diff_sel)[filled]
    else:
        diff, mask = payload
        delta = solver._table_delta(*shard.operands[:-1], diff, mask, a_cur)
        diff_h = np.asarray(diff)  # the commit donates ``diff``
        committed = steps.saga_commit_history(a_cur, diff, mask)
        mask = np.asarray(mask)
    ref = reference_saga.task(shard, w, a_read, a_cur, mask)
    if solver._compacted:
        ref["diff"] = np.asarray(ref["diff"]) * mask
    out = {"rows": rows, "sampled": int(mask.sum()),
           "g": _rel(g, ref["g"]), "delta": _rel(delta, ref["delta"]),
           "diff": _rel(diff_h, ref["diff"]),
           "committed": _rel(committed, ref["alpha"]), "limit": limit}
    out["within"] = max(out[k] for k in ("g", "delta", "diff", "committed")
                        ) <= limit
    return out


def compare(ds, solver, res, config: dict, loss: str, seed: int) -> dict:
    """The three comparisons on the state ``res`` left; ``correct`` is all
    of them."""
    shards = [ds.shard(w) for w in range(ds.num_workers)]
    out = {"history": history(shards, res, ds.n, ds.d, config["pins"]),
           "objective": objective(shards, res, loss),
           "task": task(solver, shards[seed % len(shards)], ds.d, seed,
                        TASK_LIMIT[config["kind"]])}
    out["correct"] = all(part["within"] for part in out.values())
    return out


def _round_what_advances_alpha_bar() -> None:
    """The control: every apply the solver builds from here on gets its
    ``delta`` rounded to bf16 first, on either side of the accept path."""
    import jax

    from asyncframework_tpu.ops import steps

    make_apply = steps.make_saga_apply
    to_bf16 = jax.jit(lambda v: jax.lax.reduce_precision(v, 8, 7))

    def make_rounding_apply(*args, **kwargs):
        apply = make_apply(*args, **kwargs)
        return lambda w, alpha_bar, g, delta: apply(
            w, alpha_bar, g, to_bf16(delta))

    steps.make_saga_apply = make_rounding_apply


def main(argv=None, manifest_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--round-delta", action="store_true")
    args = ap.parse_args(argv)
    man = manifest_mod.Manifest(manifest_path or manifest_mod.MANIFEST)
    cell = man.workload(args.workload)
    config = man.config(cell["config"])
    plan = plan_mod.resolve(config, man.traffic(cell["traffic"]))
    if plan["solver"] != "asaga":
        raise ValueError(f"{args.workload}: no ASAGA cell")

    from asyncframework_tpu.utils import devices as prog_devices

    prog_devices.setup_compile_cache()
    devs = bench_run._devices()
    if args.round_delta:
        _round_what_advances_alpha_bar()
    ds = bench_run.build_dataset(config, plan["num_workers"], devs, args.seed)

    from asyncframework_tpu import solvers
    from asyncframework_tpu.solvers.base import SolverConfig

    cfg = SolverConfig(**plan_mod.solver_config_kwargs(
        plan, args.seed, args.seconds, False
    ))
    solver = solvers.ASAGA(ds, None, cfg, devices=devs)
    solver.cfg = dataclasses.replace(
        cfg, num_iterations=2 * plan["num_workers"]
    )
    solver.run()  # the warm-up, as run.py makes it
    solver.cfg = cfg
    res = solver.run()
    out = {"workload": args.workload, "seed": args.seed,
           "device": devs[0].device_kind, "rounded_delta": args.round_delta,
           "accepted": res.accepted, "elapsed_s": res.elapsed_s,
           **compare(ds, solver, res, config, plan["loss"], args.seed)}
    print(json.dumps({"check_saga": out}), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
