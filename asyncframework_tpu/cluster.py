"""Local multi-process cluster launcher.

Parity: ``deploy/LocalSparkCluster.scala:36`` -- the reference's
single-machine REAL cluster (actual Master/Worker processes, actual RPC,
no fake backends), used both as a test rig and a demo.  The TPU-native
analog: N OS processes on one machine, each running the stock CLI
(``asyncframework_tpu.cli``) with the bring-up env vars set
(``ASYNCTPU_COORDINATOR`` / ``ASYNCTPU_NUM_PROCESSES`` /
``ASYNCTPU_PROCESS_ID``), so a recipe that works single-process works on
the cluster unchanged.  Two multi-process modes:

- ``sgd-mllib``: SPMD over a ``jax.distributed`` global mesh (collectives
  ride the loopback DCN);
- ``asgd`` / ``asaga``: the DCN parameter server (``parallel/ps_dcn.py``)
  -- process 0 runs the PS (the driver IS the server, across the process
  boundary), the rest push tau-stamped gradients to it over TCP.

Device ownership (``utils/devices.py``): this launcher never imports JAX,
and decides before any child does which device each child owns.  With
``--chips 0`` (the default, the test rig) every process runs on the CPU
backend with ``--devices-per-process`` virtual devices.  With ``--chips K``
the worker processes get one TPU chip each, the server one if a chip is
left and otherwise the CPU backend by assignment; more worker processes
than chips is refused.  The table is printed, and every child stamps its
assignment into its role record.

CLI: ``bin/async-cluster <N> [--chips K] [--devices-per-process D] --
<cli args...>`` e.g. ``bin/async-cluster 2 --chips 1 -- asgd synthetic x
2000 400000 8 400 100.0 2147483647 0.1 0.7 50 0 42``
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
from typing import List, Optional, Tuple

from asyncframework_tpu.utils import devices as _devices


def _free_port() -> int:
    from asyncframework_tpu.net.frame import free_port

    return free_port()


def launch_local_cluster(
    num_processes: int,
    cli_args: List[str],
    devices_per_process: int = 2,
    timeout_s: float = 300.0,
    chips: int = 0,
) -> Tuple[int, List[str]]:
    """Spawn ``num_processes`` CLI processes that form one app.

    Returns ``(worst_returncode, [process-0 stdout lines])``.  Process 0's
    output is the run's output (the PS summary, or the SPMD result every
    process computes identically); the last stdout line of every other
    process -- its role record -- is echoed to stderr.

    ``chips``: how many of this host's TPU chips to hand out (see the
    module docstring); raises ``ValueError`` when the roles cannot be
    placed on them.
    """
    if num_processes < 1:
        raise ValueError("num_processes must be >= 1")
    roles = _devices.process_roles(cli_args[0] if cli_args else "",
                                   num_processes)
    assigned = _devices.assign_devices(roles, chips)
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    procs = []
    for pid in range(num_processes):
        print(f"async-cluster: process {pid} ({roles[pid]}) -> "
              f"{assigned[pid]}", file=sys.stderr)
        env = _devices.child_env(os.environ, assigned[pid],
                                 cpu_devices=devices_per_process)
        env["ASYNCTPU_COORDINATOR"] = coord
        env["ASYNCTPU_NUM_PROCESSES"] = str(num_processes)
        env["ASYNCTPU_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "asyncframework_tpu.cli", *cli_args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        ))
    # drain every process CONCURRENTLY: a sequential communicate() would
    # let a later process block on its full 64KB stdout pipe while we wait
    # on an earlier one stuck in the distributed barrier behind it
    results: List[Optional[Tuple[str, str]]] = [None] * num_processes

    def drain(pid: int, p) -> None:
        try:
            results[pid] = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            p.kill()
            results[pid] = p.communicate()

    threads = [
        threading.Thread(target=drain, args=(pid, p),
                         name=f"cluster-drain-{pid}", daemon=True)
        for pid, p in enumerate(procs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    outs: List[str] = []
    worst = 0
    for pid, p in enumerate(procs):
        out, err = results[pid] if results[pid] is not None else ("", "")
        if p.returncode:
            worst = p.returncode
            print(f"--- process {pid} rc={p.returncode} stderr tail ---",
                  file=sys.stderr)
            print("\n".join(err.splitlines()[-15:]), file=sys.stderr)
        if pid == 0:
            outs = out.splitlines()
        elif out.strip():
            print(f"async-cluster: process {pid} record: "
                  f"{out.strip().splitlines()[-1]}", file=sys.stderr)
    return worst, outs


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(argv if argv is not None else sys.argv[1:])
    usage = ("usage: async-cluster <num_processes> [--chips K] "
             "[--devices-per-process D] -- <cli args...>")
    if not argv or not argv[0].isdigit():
        print(usage, file=sys.stderr)
        return 2
    n = int(argv.pop(0))
    opts = {"--chips": 0, "--devices-per-process": 2}
    while argv and argv[0] in opts:
        flag = argv.pop(0)
        if not argv or not argv[0].isdigit():
            print(f"{flag} needs an integer\n{usage}", file=sys.stderr)
            return 2
        opts[flag] = int(argv.pop(0))
    if argv and argv[0] == "--":
        argv.pop(0)
    try:
        rc, out = launch_local_cluster(
            n, argv, devices_per_process=opts["--devices-per-process"],
            chips=opts["--chips"],
        )
    except ValueError as e:
        print(f"async-cluster: {e}", file=sys.stderr)
        return 2
    for line in out:
        print(line)
    return rc


if __name__ == "__main__":
    sys.exit(main())
