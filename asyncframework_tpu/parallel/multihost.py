"""Multi-host process-group bring-up over DCN.

Parity: the reference's cluster bring-up is standalone Master/Worker
registration over its Netty RPC (``deploy/master/Master.scala:41``,
``deploy/worker/Worker.scala:43``, executor registration in
``CoarseGrainedSchedulerBackend``).  The TPU-native equivalent is
``jax.distributed``: one coordinator, N host processes, after which
``jax.devices()`` spans every host and the SAME mesh/pjit code rides ICI
within a slice and DCN across slices -- there is no separate "cluster mode"
code path, which is the point of the SPMD design.

This module is a thin, honest wrapper: env-driven initialization, a host
barrier built from a device collective, and helpers to build global meshes.
Single-process usage is a no-op (``ensure_initialized`` returns False), so
every call site works unchanged on one host.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import numpy as np

_initialized = False


def is_initialized() -> bool:
    """True when ``jax.distributed`` was initialized, by us or by a
    launcher calling ``jax.distributed.initialize()`` directly."""
    return _initialized or jax.distributed.is_initialized()


def ensure_initialized(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    auto: bool = False,
) -> bool:
    """Initialize ``jax.distributed`` when multi-host args/env are present.

    Resolution order: explicit args > ``ASYNCTPU_COORDINATOR`` /
    ``ASYNCTPU_NUM_PROCESSES`` / ``ASYNCTPU_PROCESS_ID`` env vars.  With
    neither, this is a single-process no-op unless ``auto=True``, which
    hands off to ``jax.distributed.initialize()``'s own cloud environment
    detection (an explicit opt-in: auto-detection can block waiting for a
    coordinator on non-cluster machines).  Returns True when running
    multi-process, False for single-process.  Idempotent, including when a
    launcher already called ``jax.distributed.initialize()`` itself.
    """
    global _initialized
    if is_initialized():
        _initialized = True
        return jax.process_count() > 1
    coordinator_address = coordinator_address or os.environ.get(
        "ASYNCTPU_COORDINATOR"
    )
    env_np = os.environ.get("ASYNCTPU_NUM_PROCESSES")
    env_pid = os.environ.get("ASYNCTPU_PROCESS_ID")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None
    )
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    if coordinator_address is None and num_processes is None and not auto:
        return False  # single-process: nothing to do
    if num_processes is not None and num_processes <= 1:
        # an explicit 1-process "cluster" (e.g. a master-scheduled
        # single-executor placement) is just a single process: spinning up
        # the distributed service would bind the coordinator port and buy
        # nothing
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    return jax.process_count() > 1


def process_info() -> Tuple[int, int]:
    """(process_id, process_count) -- (0, 1) when single-process."""
    return jax.process_index(), jax.process_count()


def sync_hosts(name: str = "barrier") -> None:
    """Block until every host reaches this point.

    Built from a tiny all-reduce over all devices (a psum is a barrier:
    no host can observe its result before every host contributed), which is
    how SPMD programs fence hosts without a separate RPC service.
    """
    device_count = jax.device_count()
    x = jax.numpy.ones((jax.local_device_count(),))
    total = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(x)
    got = int(np.asarray(total)[0])
    if got != device_count:
        raise RuntimeError(
            f"{name}: barrier saw {got} devices, expected {device_count}"
        )


def global_mesh(axis_names=("dp",), axis_sizes=None):
    """A mesh over every device of every host (ICI within a slice, DCN
    across); defaults to one data-parallel axis over all devices."""
    from asyncframework_tpu.parallel.mesh import make_mesh

    return make_mesh(
        n_devices=jax.device_count(),
        axis_names=tuple(axis_names),
        axis_sizes=axis_sizes,
        devices=jax.devices(),
    )
