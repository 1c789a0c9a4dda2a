"""Device mesh and sharding helpers.

The reference's "cluster" is Master/Workers/Executors over TCP
(``deploy/master/Master.scala``, ``scheduler/cluster/...``); the TPU-native
cluster is a :class:`jax.sharding.Mesh` over ICI (one slice) or ICI+DCN
(multi-slice / multi-host via ``jax.distributed``).  Data parallelism shards
the batch dimension over the ``dp`` axis; an optional ``md`` (model-dim) axis
shards the feature dimension of very wide models (rcv1 is 47k dims -- fits
replicated, but the axis is wired through so the same code scales).
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


def resolve_shard_map():
    """The one shard_map entry point for the whole repo: ``jax.shard_map``
    (keyword ``mesh``/``in_specs``/``out_specs``, ``check_vma=``).  Every
    call site routes through here so the next move of that API is absorbed
    in one place."""
    return jax.shard_map


def make_mesh(
    n_devices: Optional[int] = None,
    axis_names: Tuple[str, ...] = ("dp",),
    axis_sizes: Optional[Tuple[int, ...]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    clamp: bool = False,
) -> Mesh:
    """Create a mesh over the first ``n_devices`` (default: all).

    For multi-host deployments callers run ``jax.distributed.initialize()``
    first; ``jax.devices()`` then spans hosts and the same mesh code rides
    ICI within a slice and DCN across slices.

    ``clamp=True``: an ``n_devices`` beyond what the rig actually has is
    CLAMPED to the available device count (logged) instead of raising --
    the conf-driven path (``async.mesh.devices`` on a worker daemon) must
    degrade on a smaller rig, never crash the process.  The default stays
    strict: a programmatic caller asking for devices that are not there is
    a bug worth a traceback.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            if not clamp:
                raise ValueError(
                    f"requested a {n_devices}-device mesh but only "
                    f"{len(devs)} devices are available"
                )
            logger.warning(
                "make_mesh: requested %d devices but only %d available; "
                "clamping", n_devices, len(devs),
            )
            n_devices = len(devs)
        devs = devs[:n_devices]
    if axis_sizes is None:
        axis_sizes = (len(devs),) + (1,) * (len(axis_names) - 1)
    arr = np.array(devs).reshape(axis_sizes)
    mesh = Mesh(arr, axis_names)
    if clamp:
        logger.info("make_mesh: using mesh %s over %d %s device(s)",
                    dict(zip(axis_names, axis_sizes)), len(devs),
                    devs[0].platform if devs else "?")
    return mesh


def batch_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Sharding for an array whose leading dim is the batch dim."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _put_sharded(a, sh: NamedSharding):
    """Multi-host-aware placement: single-process uses device_put; with
    ``jax.distributed`` active, every process holds the same global host
    array and contributes only its addressable shards (the SPMD-driver
    convention -- ``device_put`` would reject non-addressable devices)."""
    if jax.process_count() > 1:
        return jax.make_array_from_callback(
            np.shape(a), sh, lambda idx: np.asarray(a)[idx]
        )
    return jax.device_put(a, sh)


def shard_batch(mesh: Mesh, *arrays, axis: str = "dp"):
    """Place host arrays onto the mesh sharded on their leading dim."""
    sh = batch_sharding(mesh, axis)
    out = tuple(_put_sharded(a, sh) for a in arrays)
    return out if len(out) > 1 else out[0]


def pad_and_shard_2d(
    mesh: Mesh,
    X,
    y,
    w0,
    dp_axis: str = "dp",
    md_axis: str = "md",
):
    """2-D layout: rows pad+shard over ``dp_axis`` AND features over
    ``md_axis`` (``w`` sharded over the feature axis, never whole on one
    chip).  Returns ``(Xs, ys, valid, w_dev, d)`` with ``d`` the original
    feature count (padded feature columns are zero and slice off the
    results).  Placement goes through :func:`_put_sharded`, so the same
    code runs single-process and under ``jax.distributed``.
    """
    n, d = X.shape
    n_dp = mesh.shape[dp_axis]
    n_md = mesh.shape[md_axis]
    pad_n = (-n) % n_dp
    pad_d = (-d) % n_md
    Xp = np.pad(np.asarray(X, np.float32), ((0, pad_n), (0, pad_d)))
    yp = np.pad(np.asarray(y, np.float32), (0, pad_n))
    valid = np.pad(np.ones(n, np.float32), (0, pad_n))
    Xs = _put_sharded(Xp, NamedSharding(mesh, P(dp_axis, md_axis)))
    ys = _put_sharded(yp, NamedSharding(mesh, P(dp_axis)))
    vs = _put_sharded(valid, NamedSharding(mesh, P(dp_axis)))
    w_dev = _put_sharded(
        np.pad(np.asarray(w0, np.float32), (0, pad_d)),
        NamedSharding(mesh, P(md_axis)),
    )
    return Xs, ys, vs, w_dev, d


def pad_and_shard(mesh: Mesh, *arrays, axis: str = "dp"):
    """Pad rows to a multiple of the mesh size (static shapes for XLA) and
    shard on the batch axis.

    Returns ``(*sharded_arrays, valid_sharded, n)`` where ``valid`` is a
    float mask that is 0 on padding rows and ``n`` the original row count.
    All arrays are padded along axis 0 with zeros.
    """
    n_dev = mesh.devices.size
    n = arrays[0].shape[0]
    pad = (-n) % n_dev
    valid = np.ones(n, np.float32)
    if pad:
        arrays = tuple(
            np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            for a in arrays
        )
        valid = np.concatenate([valid, np.zeros(pad, np.float32)])
    sharded = shard_batch(mesh, *arrays, valid, axis=axis)
    return (*sharded, n)
