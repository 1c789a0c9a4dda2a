"""A jitted step that is built once a MACHINE and loaded by shape after.

JAX's persistent compile cache saves XLA's compile and nothing in front of
it: a jitted function is traced and lowered once a PROCESS for every shape
it is called with, because the cache's key is computed from the lowered
module.  For a padded-ELL worker step that is 0.5 to 1.2 s of Python a
shape (0.64 s of it the sorted-segment kernel, traced and lowered by
Mosaic), paid with the device idle, and a solver over shards of unequal
width has eight shapes (PERF.md section 6, PR 54 and PR 57).

:class:`LoadedByShape` stands where such a step is called.  It keeps a
table of executables by the SHAPE of a call (every operand's shape, dtype
and sharding).  On a miss it computes a key WITHOUT tracing
(:meth:`LoadedByShape.key`), and loads the executable stored under it
(``jax.experimental.serialize_executable``) in
``utils/devices.step_store_dir()``, a sub-directory of the compile cache's
directory; if there is none, or loading raises, it lowers and compiles the
jitted function for the call's operands (through the compile cache, as
``jit`` would), serializes the result and writes it atomically (deflated:
webspam's steps are 6 to 9 MB each as they come, a quarter of it on the
disk, for 20 ms a load).  After that a call of the shape goes to the loaded
``jax.stages.Compiled``: the XLA module keeps its name (``jit_step``), so a
device trace reads it as it reads the traced one.  On the v5e (PERF.md
section 6, PR 57; the widest of webspam's steps): a load 0.10 s where
tracing, lowering and the compile cache's hit take 0.77, the loaded
step's ``g`` the traced one's to the bit, a call no dearer than ``jit``'s.

What a key cannot see is a program changed behind it, so the key holds a
digest of the SOURCE the step is traced from (:func:`source_digest`): an
edit there is a miss, and the first step stored under the new source takes
its factory's entries under any other out of the store, so the store holds
one source's programs and does not grow with every edit.  What it cannot
see at all is a module attribute
replaced at run time: ``tests/conftest.py`` keeps the tests off the store
(``step_store_dir`` gives ``None``), and the store's own tests opt in.

A call whose operands are not all COMMITTED device arrays (a host array,
an array that follows the default device, a tracer) goes to the jitted
function as it always did: only committed operands say where the program
runs without asking ``jit``.  A process with no compile cache directory
has no store; on a backend that cannot serialize an executable, or not
whole (:func:`serializes_whole`: the CPU's), the step is the ``jit`` it
was.
"""

from __future__ import annotations

import contextlib
import hashlib
import logging
import os
import pickle
import tempfile
import threading
import zlib
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
from jax.experimental import serialize_executable

from asyncframework_tpu.utils import devices as _devices

log = logging.getLogger(__name__)

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def source_files() -> Tuple[str, ...]:
    """The files a padded-ELL step is traced from: every module of
    ``ops/`` and ``data/sparse.py`` (which decides how a shard is stored
    and what width it is read at)."""
    ops = os.path.join(_PACKAGE, "ops")
    return (*sorted(os.path.join(ops, f) for f in os.listdir(ops)
                    if f.endswith(".py")),
            os.path.join(_PACKAGE, "data", "sparse.py"))


def source_digest() -> str:
    """SHA-256 over :func:`source_files`, each by its base name and its
    CONTENT: where the checkout lies and when it was written do not reach
    it, one changed byte does.  Read on every miss (a third of a megabyte,
    a millisecond), so nothing is remembered that a test would have to
    forget."""
    h = hashlib.sha256()
    for path in source_files():
        with open(path, "rb") as f:
            body = f.read()
        h.update(f"{os.path.basename(path)}:{len(body)}:".encode())
        h.update(body)
    return h.hexdigest()


def serializes_whole(device) -> bool:
    """Whether an executable of ``device``'s backend comes back from
    ``serialize`` and ``deserialize_and_load`` as it went.  XLA:CPU's does
    not (jaxlib 0.9.0): one that the process LOADED from the persistent
    compile cache serializes without its kernels (37 KB where the freshly
    compiled one is 47), nothing raises, and the loaded copy fails at its
    first execution (``NOT_FOUND: Function ... not found``), in the same
    process or the next.  A step on the CPU compiles in well under a
    second and stays on ``jit``."""
    return device.platform != "cpu"


def _committed(a) -> bool:
    return (isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
            and a.committed)


class LoadedByShape:
    """``jitted`` as the engine calls it: the executable of a call's shape
    from the table, from the store, or built and stored (the module's
    docstring).  ``name`` and ``static`` are the factory's name and the
    arguments it closed the step over: with the operands they decide the
    executable.  ``lower`` is the jitted function's; :meth:`counts` is
    what this object loaded, built and failed to load over its life."""

    def __init__(self, jitted: Callable, name: str,
                 static: Mapping[str, object]):
        self._jitted = jitted
        self._name = name
        self._static = tuple(sorted(
            (k, tuple(sorted(v.items())) if isinstance(v, Mapping) else v)
            for k, v in static.items()))
        self._programs: Dict[tuple, Callable] = {}
        self._lock = threading.Lock()
        self._counts = {"loaded": 0, "built": 0, "failed": 0}
        self._unstorable = False
        self.lower = jitted.lower

    def __call__(self, *args):
        if not all(map(_committed, args)):
            return self._jitted(*args)
        shape = tuple((a.shape, a.dtype, a.sharding) for a in args)
        program = self._programs.get(shape)
        if program is None:
            program = self._program(shape, args)
        return program(*args)

    def counts(self) -> Dict[str, int]:
        """``{"loaded", "built", "failed"}``: a load that raised counts
        ``failed`` and then ``built``."""
        with self._lock:
            return dict(self._counts)

    def key(self, args) -> str:
        """The name a call with ``args`` is stored under:
        ``<factory>-<the source's digest, 16 digits>-<SHA-256>`` of what
        decides its executable, and nothing that needs a trace: the
        factory's name and static arguments, each operand's shape, dtype
        and format (its layout and its sharding, the device in it), the
        versions of ``jax`` and ``jaxlib``, the backend's and the
        device's, the flags the compiler reads from the environment, the
        configuration that changes what is traced, and the source's
        digest."""
        import jaxlib

        dev = next(iter(args[0].devices()))
        source = source_digest()
        parts = (
            self._name, self._static,
            tuple((a.shape, str(a.dtype), str(a.format)) for a in args),
            jax.__version__, jaxlib.__version__,
            dev.client.platform_version, dev.device_kind,
            os.environ.get("XLA_FLAGS", ""),
            os.environ.get("LIBTPU_INIT_ARGS", ""),
            jax.config.jax_enable_x64,
            jax.config.jax_default_matmul_precision,
            jax.config.jax_default_prng_impl,
            jax.config.jax_threefry_partitionable,
            source,
        )
        return "-".join((self._name, source[:16],
                         hashlib.sha256(repr(parts).encode()).hexdigest()))

    # -------------------------------------------------------------- a miss
    def _program(self, shape, args) -> Callable:
        with self._lock:
            program = self._programs.get(shape)
            if program is None:
                root = _devices.step_store_dir()
                if (root is None or self._unstorable or not
                        serializes_whole(next(iter(args[0].devices())))):
                    program = self._jitted
                else:
                    path = os.path.join(root, self.key(args))
                    program = self._load(path, args)
                    if program is None:
                        program = self._jitted.lower(*args).compile()
                        self._counts["built"] += 1
                        self._store(path, program)
                self._programs[shape] = program
            return program

    def _load(self, path: str, args) -> Optional[Callable]:
        try:
            with open(path, "rb") as f:
                stored = f.read()
        except FileNotFoundError:
            return None
        try:
            # (bytes this program wrote, under a key that holds its source)
            blob, in_tree, out_tree = pickle.loads(zlib.decompress(stored))
            devs = sorted(args[0].devices(), key=lambda d: d.id)
            program = serialize_executable.deserialize_and_load(
                blob, in_tree, out_tree, backend=devs[0].client,
                execution_devices=devs)
        except Exception:  # a file cut short, another runtime's bytes
            log.warning("stored step %s did not load: building it", path,
                        exc_info=True)
            self._counts["failed"] += 1
            return None
        self._counts["loaded"] += 1
        return program

    def _store(self, path: str, program) -> None:
        try:
            payload = zlib.compress(
                pickle.dumps(serialize_executable.serialize(program)), 1)
            root, entry = os.path.split(path)
            os.makedirs(root, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=root, prefix=".writing-")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(payload)
                os.replace(tmp, path)
            except BaseException:
                os.unlink(tmp)
                raise
            # this factory's entries under another source are stale now
            mine, current = self._name + "-", entry[:entry.rindex("-") + 1]
            for other in os.listdir(root):
                if other.startswith(mine) and not other.startswith(current):
                    with contextlib.suppress(FileNotFoundError):
                        os.unlink(os.path.join(root, other))
        except Exception:  # this backend serializes none, or no room
            log.warning("step %s is not stored: the next ones stay on jit",
                        path, exc_info=True)
            self._unstorable = True
