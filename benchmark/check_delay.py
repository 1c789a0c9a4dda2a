"""A straggler cell's injected delays against the plain reference.

    python3 benchmark/check_delay.py --workload <name> --seed <n> --seconds <s> [--halve]

The builder's tool beside ``benchmark/run.py``: the cell's solver is built,
warmed and run for ``--seconds`` exactly as ``run.py`` does it, with a patch
made HERE (not a switch of the program) that keeps one log of the run in
call order: every task built with the delay it was given, ``("task", worker,
delay_ms)``; every result past the staleness filter, ``("merged", worker,
accepted)``; the calibration's end, ``("calibrated", scale_ms, at_update,
at_s)``.  Then, outside any timed window, the log is held to
``reference_delay`` (the reference's straggler model restated with no
program code) and the run's own account (``TrainResult.extras``) to the log:

- ``who``: no worker outside the reference's late ones was ever given a
  delay, each late worker was, and the program counts as many of them;
- ``schedule``: every sleep equals the reference's for that position, from
  the run's seed, the scale the program calibrated and the order in which
  the delayed tasks were built;
- ``calibration``: nobody slept before the calibration's end, which came
  after no fewer than ``100 x num_workers`` accepted updates, and the log's
  count of accepted results in front of it is
  ``delay_calibrated_at_update``;
- ``account``: ``delayed_tasks`` is the log's count, ``delay_sleep_s`` and
  ``delay_sleep_long_tail_s`` its sums (1e-6 relative), ``avg_delay_ms`` the
  scale to the bit, ``accepted_from_stragglers`` the log's accepted results
  of the late workers and ``accepted_after_calibration`` those behind the
  calibration's end.

The last stdout line is ``{"check_delay": {..., "correct": bool}}`` and the
exit code is 0 only where ``correct``.  ``--halve`` is the negative
control: the run is made with every sleep halved inside the patch (the task
sleeps, and the log holds, half of what the model drew), which has to come
out as NOT correct, by ``schedule`` and by ``account``.
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

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import plan as plan_mod, reference_delay  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

#: the sums of the account against the log's, relative
SUM_REL = 1e-6


def _log_the_runs(halve: bool) -> list:
    """The patch: every ``EngineRun`` built from here on gets a delay model
    that logs what it hands out (halved first, under ``halve``) and when it
    was calibrated, and every merged result is logged beside them.  Returns
    the list that gets one log a run."""
    from asyncframework_tpu.solvers import engine_loop, instrumentation

    runs = []

    class LoggedDelayModel(engine_loop.DelayModel):
        def __post_init__(self):
            super().__post_init__()
            self.log = []
            runs.append(self.log)

        def calibrate(self, avg_delay_ms, at_update=0, at_s=0.0):
            # in front of the model's own flag: a delay drawn behind the
            # flag is logged behind this entry
            self.log.append(("calibrated", avg_delay_ms, at_update, at_s))
            super().calibrate(avg_delay_ms, at_update=at_update, at_s=at_s)

        def delay_ms(self, worker_id):
            ms = super().delay_ms(worker_id)
            if halve:
                ms = ms / 2
            self.log.append(("task", worker_id, ms))
            return ms

    merged = instrumentation.RunInstruments.on_gradient_merged

    def logged_merge(self, res, accepted, *args, **kwargs):
        runs[-1].append(("merged", res.worker_id, bool(accepted)))
        return merged(self, res, accepted, *args, **kwargs)

    engine_loop.DelayModel = LoggedDelayModel
    instrumentation.RunInstruments.on_gradient_merged = logged_merge
    return runs


def compare(log, res, plan: dict, seed: int) -> dict:
    """The four comparisons on one run's log and result; ``correct`` is all
    of them."""
    nw, coeff = plan["num_workers"], plan["coeff"]
    extras = res.extras
    late = reference_delay.late_workers(nw, coeff)
    marks = [i for i, e in enumerate(log) if e[0] == "calibrated"]
    at = marks[0] if marks else len(log)
    scale = log[at][1] if marks else 0.0
    tasks = [(i, e[1], e[2]) for i, e in enumerate(log) if e[0] == "task"]
    order, slept = reference_delay.split([(w, ms) for _i, w, ms in tasks])
    by_worker = {}
    for e in log:
        if e[0] == "merged" and e[2]:
            by_worker[e[1]] = by_worker.get(e[1], 0) + 1
    before = sum(1 for e in log[:at] if e[0] == "merged" and e[2])

    who = {
        "late": {str(w): c for w, c in sorted(late.items())},
        "delayed_workers": sorted(set(order)),
        "straggler_workers": extras.get("straggler_workers"),
    }
    who["within"] = (set(order) == set(late) if marks else not order) and (
        extras.get("straggler_workers") == len(late))

    known = [w for w in order if w in late]
    want = reference_delay.sleeps(seed, scale, known, nw, coeff)
    off = [i for i, (a, b) in enumerate(zip(slept, want)) if a != b]
    schedule = {
        "scale_ms": scale, "delayed_tasks": len(slept),
        "differ": len(off) + abs(len(slept) - len(want)),
        "first_differs": None if not off else {
            "position": off[0], "worker": order[off[0]],
            "slept_ms": slept[off[0]], "reference_ms": want[off[0]]},
        # a scale under a third of a millisecond rounds a draw to nothing
        # and the positions could not be told: not this check's to pass
        "within": bool(marks) and not off and len(slept) == len(want)
        and len(slept) > 0 and 1.5 * scale >= 0.5,
    }

    early = [i for i, _w, ms in tasks if ms > 0 and i < at]
    calibration = {
        "calibrated": bool(marks), "at_update": log[at][2] if marks else None,
        "at_s": log[at][3] if marks else None,
        "accepted_before": before, "slept_before": len(early),
        "calibration_updates": 100 * nw,
    }
    calibration["within"] = (
        len(marks) == 1 and not early and before >= 100 * nw
        and before == log[at][2] == extras.get("delay_calibrated_at_update")
        and log[at][3] == extras.get("delay_calibrated_at_s"))

    tail = [ms for w, ms in zip(order, slept)
            if late.get(w) == reference_delay.LONG_TAIL]
    sums = {"delay_sleep_s": sum(slept) / 1e3,
            "delay_sleep_long_tail_s": sum(tail) / 1e3}
    counts = {
        "delayed_tasks": len(slept),
        "avg_delay_ms": scale,
        "accepted_from_stragglers": sum(by_worker.get(w, 0) for w in late),
        "accepted_after_calibration": res.accepted - before if marks else 0,
    }
    account = {"log": {**counts, **sums},
               "extras": {k: extras.get(k) for k in {**counts, **sums}}}
    account["within"] = all(
        extras.get(k) == v for k, v in counts.items()
    ) and all(
        extras.get(k) is not None
        and abs(extras[k] - v) <= SUM_REL * max(abs(v), 1e-12)
        for k, v in sums.items()
    ) and sum(by_worker.values()) == res.accepted

    out = {"who": who, "schedule": schedule, "calibration": calibration,
           "account": account}
    out["correct"] = all(part["within"] for part in out.values())
    return out


def main(argv=None, manifest_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--halve", action="store_true")
    args = ap.parse_args(argv)
    man = manifest_mod.Manifest(manifest_path or manifest_mod.MANIFEST)
    cell = man.workload(args.workload)
    config = man.config(cell["config"])
    plan = plan_mod.resolve(config, man.traffic(cell["traffic"]))
    if plan["coeff"] == 0 or plan["mode"] != "async":
        raise ValueError(f"{args.workload}: no asynchronous straggler cell")

    from asyncframework_tpu.utils import devices as prog_devices

    prog_devices.setup_compile_cache()
    devs = bench_run._devices()
    runs = _log_the_runs(args.halve)
    ds = bench_run.build_dataset(config, plan["num_workers"], devs, args.seed)

    from asyncframework_tpu import solvers
    from asyncframework_tpu.solvers.base import SolverConfig

    cfg = SolverConfig(**plan_mod.solver_config_kwargs(
        plan, args.seed, args.seconds, False
    ))
    solver_cls = {"asgd": solvers.ASGD, "asaga": solvers.ASAGA}[plan["solver"]]
    solver = solver_cls(ds, None, cfg, devices=devs)
    solver.cfg = dataclasses.replace(
        cfg, num_iterations=2 * plan["num_workers"]
    )
    solver.run()  # the warm-up, as run.py makes it
    solver.cfg = cfg
    res = solver.run()
    out = {"workload": args.workload, "seed": args.seed,
           "device": devs[0].device_kind, "halved": args.halve,
           "accepted": res.accepted, "elapsed_s": res.elapsed_s,
           "updates_per_s": res.accepted / res.elapsed_s,
           **compare(runs[-1], res, plan, args.seed)}
    print(json.dumps({"check_delay": out}), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
