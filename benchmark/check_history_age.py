"""A delayed ASAGA cell's account of its history's age against the plain
reference.

    python3 benchmark/check_history_age.py --workload <name> --seed <n> --seconds <s> [--drop-class]

The builder's tool beside ``benchmark/run.py`` and ``check_delay.py``: the
cell's solver is built, warmed and run for ``--seconds`` exactly as
``run.py`` does it (``check_delay.py: main``'s sequence), with ONE addition
made here: a listener on each run's event bus (``metrics/bus.py:
add_listener``) that keeps the worker id of every ``GradientMerged`` event
with ``accepted`` set, in order.  The program posts that event for every
result once somebody listens; the bus of an untraced run is not started,
so the listener is called on the updater's own thread, in the order the
updates were applied.  It is a run of its own: the timed cell has no
listener.

Then, outside any timed window, the accept order is replayed by
``reference_history_age`` (plain Python, no program code) with the late
set ``reference_delay`` gives and the calibration's end the run reports
(``extras["delay_calibrated_at_update"]``), and the run's four integers are
held to the replay EXACTLY:

- ``late``: ``history_age_late_sum`` and ``history_age_late_n``;
- ``healthy``: ``history_age_healthy_sum`` and ``history_age_healthy_n``;
- ``order``: the listener heard as many accepted results as the run
  accepted, the tail began inside the run, and both classes were counted.

``by_class`` is the distribution the sums cannot show (count, mean, median,
95th percentile, maximum, for ``healthy``, ``normal`` and ``long_tail``),
from the same replay.

The last stdout line is ``{"check_history_age": {..., "correct": bool}}``
and the exit code is 0 only where ``correct``.  ``--drop-class`` is the
negative control: the run is made with every late worker's age booked to
the healthy class inside a patch made here (not a switch of the program),
which has to come out as NOT correct, by ``late`` and by ``healthy``.
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
from benchmark import plan as plan_mod  # noqa: E402
from benchmark import reference_delay, reference_history_age  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

KEYS = {"late": ("history_age_late_sum", "history_age_late_n"),
        "healthy": ("history_age_healthy_sum", "history_age_healthy_n")}


class AcceptOrder:
    """The bus listener: the worker id of every accepted result."""

    def __init__(self):
        self.order = []

    def on_gradient_merged(self, event) -> None:
        if event.accepted:
            self.order.append(event.worker_id)

    def on_event(self, event) -> None:
        pass


def _hear_the_runs(drop_class: bool) -> list:
    """The patch: every ``EngineRun`` built from here on gets a listener on
    its bus (and, under ``drop_class``, a delay model that books every age
    to the healthy class).  Returns the list that gets one listener a
    run."""
    from asyncframework_tpu.solvers import engine_loop

    runs = []

    class HeardInstruments(engine_loop.RunInstruments):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            runs.append(AcceptOrder())
            self.bus.add_listener(runs[-1])

    engine_loop.RunInstruments = HeardInstruments
    if drop_class:
        class OneClass(engine_loop.DelayModel):
            def book_history_age(self, worker_id, age):
                healthy = next(w for w in range(self.num_workers)
                               if w not in self.stragglers)
                return super().book_history_age(healthy, age)

        engine_loop.DelayModel = OneClass
    return runs


def compare(order, res, plan: dict) -> dict:
    """The run's four integers against the replay of its accept order;
    ``correct`` is all three parts."""
    extras = res.extras
    classes = reference_delay.late_workers(plan["num_workers"], plan["coeff"])
    at = extras.get("delay_calibrated_at_update")
    want = reference_history_age.account(order, classes, at)
    out = {}
    for part, keys in KEYS.items():
        out[part] = {"reference": {k: want[k] for k in keys},
                     "extras": {k: extras.get(k) for k in keys}}
        out[part]["within"] = all(extras.get(k) == want[k] for k in keys)
    out["order"] = {
        "heard": len(order), "accepted": res.accepted,
        "calibrated_at_update": at,
        "within": len(order) == res.accepted and bool(at)
        and want["history_age_late_n"] > 0
        and want["history_age_healthy_n"] > 0,
    }
    correct = all(part["within"] for part in out.values())
    out["by_class"] = reference_history_age.by_class(order, classes, at)
    out["correct"] = correct
    return out


def main(argv=None, manifest_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drop-class", action="store_true")
    args = ap.parse_args(argv)
    man = manifest_mod.Manifest(manifest_path or manifest_mod.MANIFEST)
    cell = man.workload(args.workload)
    config = man.config(cell["config"])
    plan = plan_mod.resolve(config, man.traffic(cell["traffic"]))
    if (plan["coeff"] == 0 or plan["mode"] != "async"
            or plan["solver"] != "asaga"):
        raise ValueError(f"{args.workload}: no delayed asynchronous ASAGA "
                         f"cell")

    from asyncframework_tpu.utils import devices as prog_devices

    prog_devices.setup_compile_cache()
    devs = bench_run._devices()
    runs = _hear_the_runs(args.drop_class)
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
           "device": devs[0].device_kind, "dropped_class": args.drop_class,
           "accepted": res.accepted, "elapsed_s": res.elapsed_s,
           "updates_per_s": res.accepted / res.elapsed_s,
           **compare(runs[-1].order, res, plan)}
    print(json.dumps({"check_history_age": out}), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
