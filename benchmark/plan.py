"""The one general generator: a traffic mix's parameters -> a run plan.

This system's "traffic" is the stream of worker gradients reaching the
server, and what shapes it is how many logical workers there are, whether
they run behind a barrier, how late each is (``coeff``: the delay
intensity of the paper's straggler model, ``-1`` for its cloud long tail)
and the staleness bound ``taw`` the server filters with.  A mix is a JSON
file of those parameters; a key it leaves out keeps the configuration's
value.  ``per_config`` in a mix overrides keys for one configuration (a
mix that changes the rate changes the ``gamma`` that meets the target and
the ``printer_freq`` that keeps the snapshot count), so a new mix brings
them along as data.

What a configuration says about its DATA (kind, shape, storage, and the
``generator`` mapping that goes to the program's generator as it stands)
is ``run.py: build_dataset``'s and no mix overrides it.

The delay schedule itself is drawn inside the program (``DelayModel``,
seeded with the run's seed): a mix with ``coeff != 0`` therefore rests on
program code, which PERF.md lists under Open questions.
"""

from __future__ import annotations

from typing import Dict

#: a configuration's keys a traffic mix may override, with the type of each
RUN_KEYS = {
    "solver": str,          # asgd | asaga
    "loss": str,            # least_squares | logistic
    "mode": str,            # async | sync
    "num_workers": int,
    "batch_rate": float,
    "bucket_ratio": float,
    "taw": int,
    "coeff": float,
    "gamma": float,
    "printer_freq": int,
    "target_fraction": float,
    "heartbeat_timeout_ms": float,
}
#: share of updates whose spans the program records in a traced run
TRACE_SAMPLE = 0.125
#: ``heartbeat_timeout_ms``: how long an idle executor may stay silent
#: before the program's monitor declares it lost.  The program's own 2 s
#: cannot tell a lost executor from a host that held every Python thread
#: (the monitor's included) that long: on the chip's shared-core host that
#: happened inside runs (2.3 s and 3.5 s, PR 22) and cost a run eight
#: "lost" workers and its ``correct``.  The reference waits 120 s
#: (``spark.network.timeout``, ``HeartbeatReceiver``'s executor timeout).
#: A dead executor thread is still found at the next 0.25 s scan.
DEFAULTS = {"mode": "async", "coeff": 0.0, "taw": 2**31 - 1,
            "loss": "least_squares", "heartbeat_timeout_ms": 120_000.0}


def resolve(config: Dict[str, object], traffic: Dict[str, object]) -> Dict[str, object]:
    """The cell's run parameters: defaults, then the configuration's, then
    the mix's, then the mix's ``per_config`` entry for this configuration."""
    layers = [
        DEFAULTS,
        config,
        traffic,
        traffic.get("per_config", {}).get(config["name"], {}),
    ]
    out: Dict[str, object] = {}
    for layer in layers:
        for key, typ in RUN_KEYS.items():
            if key in layer:
                out[key] = typ(layer[key])
    missing = sorted(set(RUN_KEYS) - set(out))
    if missing:
        raise KeyError(
            f"cell {config['name']}.{traffic['name']} sets no {missing}"
        )
    if out["mode"] not in ("async", "sync"):
        raise ValueError(f"unknown mode {out['mode']!r}")
    if out["solver"] not in ("asgd", "asaga"):
        raise ValueError(f"unknown solver {out['solver']!r}")
    return out


def solver_config_kwargs(run_plan: Dict[str, object], seed: int,
                         seconds: float, trace: bool) -> Dict[str, object]:
    """``SolverConfig`` fields of the one time-bounded run: the update
    budget is out of reach and the submitter's deadline is the window."""
    return {
        "num_workers": run_plan["num_workers"],
        "num_iterations": 2**31 - 1,
        "gamma": run_plan["gamma"],
        "taw": run_plan["taw"],
        "batch_rate": run_plan["batch_rate"],
        "bucket_ratio": run_plan["bucket_ratio"],
        "printer_freq": run_plan["printer_freq"],
        "coeff": run_plan["coeff"],
        "seed": seed,
        "loss": run_plan["loss"],
        "heartbeat_timeout_ms": run_plan["heartbeat_timeout_ms"],
        "run_timeout_s": float(seconds),
        "trace_sample": TRACE_SAMPLE if trace else None,
    }
