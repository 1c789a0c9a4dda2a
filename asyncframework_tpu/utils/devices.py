"""Which device a process owns, and where it keeps compiled code.

A TPU chip belongs to one process at a time: a process that has
initialised a JAX backend holds every chip it can see, and a child that
needs one then fails or hangs.  So device ownership is decided by the
LAUNCHER, before the child imports JAX, and travels in the child's
environment; nothing here falls back from one platform to another.

The module imports JAX only inside the functions that run in the process
that owns the device, so launchers that must stay off JAX
(``cluster.py``, ``deploy/worker.py``, ``chip_smoke.py``'s parent) can use
the rest.

How a process is confined to one chip was established on the four-chip
v5e host with libtpu 0.0.34 (CHANGES.md, PR 21): ``TPU_VISIBLE_CHIPS=<i>``
with ``TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1`` and ``TPU_PROCESS_BOUNDS=1,1,1``
gives four concurrent processes one device each (every one reports it as
local device 0).
"""

from __future__ import annotations

import importlib
import os
import re
import threading
from typing import Dict, List, Optional, Sequence

#: JAX reads this itself; where it is set the program sets no directory
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the launcher's decision, for the child's role record: "tpu:<i>" | "cpu"
ASSIGNED_ENV = "ASYNCTPU_DEVICE"

CPU = "cpu"

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


# ------------------------------------------------------------ compile cache
def compile_cache_dir() -> str:
    """The one persistent compile-cache directory: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache`` (the path is part of
    the cache key, so it is built from no temporary name, pid or clock)."""
    return os.environ.get(CACHE_ENV) or os.path.join(_REPO, ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compile cache at :func:`compile_cache_dir`.

    Called once by every entry point that will touch JAX, before its first
    compile (JAX latches "no cache" at the first compile of a process).
    With the variable set JAX has already read it and no directory is set
    here.  The minimum compile time is dropped to zero either way: most of
    this program's executables compile in 0.1-3 s, under JAX's 1 s default.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _preload_kernels()
    return path


def _preload_kernels() -> None:
    """Start importing ``ops/pallas_kernels.py`` on a background thread,
    in a process that may hold a TPU.

    The dense worker step is a Pallas kernel there, and importing
    ``jax.experimental.pallas`` costs 1.3-1.9 s on the chip's host (most of
    it the GPU back end's modules, which JAX imports with it).  Every entry
    point calls :func:`setup_compile_cache` just before it attaches the
    chip, which takes 8-12 s in PJRT with the interpreter lock released:
    the import finishes inside that wait, and the program's own import
    after it drops from 1.41 s to 0.19 s (v5e, PERF.md section 6, PR 26).
    A process held to the CPU (``JAX_PLATFORMS=cpu``: every test, every role
    without a chip) never runs the kernel and imports nothing here.
    """
    if "tpu" not in os.environ.get("JAX_PLATFORMS", "tpu"):
        return
    from asyncframework_tpu.utils.threads import guarded

    threading.Thread(
        target=guarded(importlib.import_module, "preload-kernels"),
        args=("asyncframework_tpu.ops.pallas_kernels",),
        name="preload-kernels", daemon=True,
    ).start()


def step_store_dir() -> Optional[str]:
    """Where the padded-ELL worker steps' executables are stored by a key
    computed without tracing (``ops/program_store.py``): ``step_programs/``
    under the directory JAX's persistent compile cache uses in THIS
    process, so it persists exactly as the cache does (and is cleared
    with it: ``rm -rf <cache dir>/step_programs``); ``None`` in a process
    that has no such directory, which then has no store.
    :func:`cache_entries` keeps counting the cache's own files."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    return os.path.join(path, "step_programs") if path else None


def cache_entries(path: Optional[str] = None) -> int:
    """Number of cached executables under ``path`` (0 for a missing dir)."""
    path = path or compile_cache_dir()
    try:
        return sum(1 for f in os.listdir(path) if f.endswith("-cache"))
    except OSError:
        return 0


# -------------------------------------------------------------- device stamp
def device_stamp() -> Dict[str, object]:
    """What JAX reports in this process, plus what the launcher assigned:
    stamped into every summary and role record so a run can always be told
    apart from one on another platform."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
        "assigned": os.environ.get(ASSIGNED_ENV),
    }


# ------------------------------------------------------ launcher-side choice
def host_chip_count() -> int:
    """TPU chips on this host, counted from the device nodes without
    touching JAX (``/dev/vfio/<n>`` on v5e hosts, ``/dev/accel<n>`` on
    older ones); 0 when there are none."""
    n = 0
    for d, pat in (("/dev/vfio", r"\d+"), ("/dev", r"accel\d+")):
        try:
            n += sum(1 for f in os.listdir(d) if re.fullmatch(pat, f))
        except OSError:
            pass
    return n


def check_chips(chips: int) -> None:
    """Refuse a launcher asked to hand out more chips than the host has."""
    have = host_chip_count()
    if chips > have:
        raise ValueError(
            f"asked to hand out {chips} TPU chip(s) but this host has "
            f"{have}"
        )


def process_roles(driver: str, num_processes: int) -> List[str]:
    """Role of each process of one app, by process id: under a DCN driver
    (asgd/asaga) with more than one process, process 0 is the parameter
    server (``cli.run_async_cluster``); everything else is a worker."""
    from asyncframework_tpu.cli import DRIVER_ALIASES

    dcn = DRIVER_ALIASES.get(driver.lower()) in ("asgd", "asaga")
    if dcn and num_processes > 1:
        return ["server"] + ["worker"] * (num_processes - 1)
    return ["worker"] * num_processes


def assign_devices(roles: Sequence[str], chips: int) -> List[str]:
    """One assignment per role, ``"tpu:<i>"`` or ``"cpu"``.

    ``chips == 0`` is the CPU rig: everything on the CPU backend.
    Otherwise the ``"worker"`` roles get a chip each first (they hold the
    data and do the arithmetic) and it is an error to have more of them
    than chips; any other role (server, shard, replica) gets a chip if one
    is left and else runs on the CPU backend -- by assignment, which the
    launcher prints, not by fallback.
    """
    if chips <= 0:
        return [CPU] * len(roles)
    check_chips(chips)
    workers = [i for i, r in enumerate(roles) if r == "worker"]
    if len(workers) > chips:
        raise ValueError(
            f"{len(workers)} worker processes need a chip each but only "
            f"{chips} chip(s) were offered; one worker process can drive "
            f"several chips instead (--devices / async.mesh.devices)"
        )
    out = [CPU] * len(roles)
    free = iter(range(chips))
    for i in workers:
        out[i] = f"tpu:{next(free)}"
    for i, r in enumerate(roles):
        if r != "worker":
            chip = next(free, None)
            if chip is not None:
                out[i] = f"tpu:{chip}"
    return out


def child_env(env: Dict[str, str], assigned: str,
              cpu_devices: int = 1) -> Dict[str, str]:
    """``env`` plus what makes a child own exactly ``assigned``, and the
    resolved compile-cache directory.  ``cpu_devices`` is the virtual
    device count of a CPU-assigned child (kept if XLA_FLAGS already
    names one)."""
    env = dict(env)
    env[ASSIGNED_ENV] = assigned
    env[CACHE_ENV] = compile_cache_dir()
    if assigned == CPU:
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cpu_devices}"
            ).strip()
        return env
    chip = assigned.split(":", 1)[1]
    env["JAX_PLATFORMS"] = "tpu,cpu"
    env["TPU_VISIBLE_CHIPS"] = chip
    env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


class ChipPool:
    """The chips a long-lived launcher (``deploy/worker.py``) may hand to
    the processes it starts: :meth:`take` a free one, :meth:`release` it
    when the process is gone."""

    def __init__(self, chips: int):
        if chips > 0:
            check_chips(chips)
        self._free = list(range(max(chips, 0)))
        self._lock = threading.Lock()

    def take(self) -> Optional[int]:
        with self._lock:
            return self._free.pop(0) if self._free else None

    def release(self, chip: int) -> None:
        with self._lock:
            self._free.append(chip)
            self._free.sort()
