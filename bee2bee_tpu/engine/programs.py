"""Compiled programs kept on disk, loaded at boot without a trace.

jax's persistent compilation cache saves the XLA compile of a program, not
what comes before it: a jit root's first call of a shape still traces the
model in Python and lowers it, 1.1-1.6 s a prefill program of the served
models on a warm cache (PERF.md, PR 42) — nothing, beside a compile of
tens of seconds inside the first request that needs the shape, and too much
for a boot that must call EVERY prefill program an admission burst can ask
for (scheduler.warm_prefill). So the programs warmed at boot are kept whole:
lowered and compiled once (``jit.lower(...).compile()``), serialized beside
the compile cache (jax.experimental.serialize_executable, what the cache
itself stores; under ``programs/`` of the directory jax is configured with,
and nowhere if it has none: such a process compiles and keeps them in
memory), and loaded by the next boot in tens of milliseconds.

A stored program is only ever found under a key that holds everything its
text depends on: this package's source, the jax / jaxlib / runtime versions,
the compiler's environment flags, the caller's ``salt`` (model, engine
configuration and mesh) and the call's own signature (every argument's shape, dtype
and sharding). A file that cannot be loaded is compiled again and replaced.
"""

from __future__ import annotations

import functools
import hashlib
import logging
import os
import pickle
from pathlib import Path

import jax

logger = logging.getLogger("bee2bee_tpu.programs")

_ENV_FLAGS = ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "JAX_DEFAULT_MATMUL_PRECISION")


@functools.cache
def _build_digest() -> str:
    """What every program of this process depends on beside its own call."""
    import jaxlib

    h = hashlib.sha256()
    package = Path(__file__).resolve().parent.parent
    for path in sorted(package.rglob("*.py")):
        h.update(str(path.relative_to(package)).encode())
        h.update(path.read_bytes())
    backend = jax.devices()[0].client
    for part in (jax.__version__, jaxlib.__version__, backend.platform,
                 backend.platform_version,
                 *(f"{k}={os.environ.get(k, '')}" for k in _ENV_FLAGS)):
        h.update(str(part).encode())
    return h.hexdigest()


def _signature(tree) -> str:
    """Shape, dtype and placement of every leaf (None leaves and the
    tree's structure included): what selects a compiled variant."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: x is None)
    return str(treedef) + ";".join(
        "None" if a is None else
        f"{getattr(a, 'shape', ())}{getattr(a, 'dtype', type(a).__name__)}"
        f"{getattr(a, 'sharding', '')}"
        for a in leaves
    )


def _store_base() -> str | None:
    """jax's compile cache directory as this process has it NOW, or None
    where programs are not kept: a process without a compile cache, and one
    that lets XLA keep every kernel cache of its own there
    (``jax_persistent_cache_enable_xla_caches="all"``, the test suite's): a
    program compiled against those does not hold all of its code, and a
    loaded one then fails at its first call ("Function ... not found",
    XLA:CPU)."""
    if jax.config.jax_persistent_cache_enable_xla_caches == "all":
        return None
    return jax.config.jax_compilation_cache_dir


class StoredPrograms:
    """A jit root some of whose programs are loaded executables.

    ``warm(*args, **kwargs)`` makes the program of exactly that call
    resident — loaded from disk, else lowered, compiled and stored — under
    ``key_fn(*args, **kwargs)``; a call whose key is resident runs the
    loaded program, every other call the jit root itself (``fn``), which
    compiles on first use as ever. ``fn`` may be a wrapper of the jitted
    function (the retrace sentinel's); ``__wrapped__`` is the jitted one."""

    def __init__(self, name: str, fn, key_fn, salt: str, devices: list):
        self.name, self.fn, self.key_fn = name, fn, key_fn
        self.__wrapped__ = fn if hasattr(fn, "lower") else fn.__wrapped__
        self._salt = salt
        self._devices = devices  # the programs' own: a process may see more
        self._resident: dict = {}

    def __call__(self, *args, **kwargs):
        prog = self._resident.get(self.key_fn(*args, **kwargs)) if self._resident else None
        return (prog or self.fn)(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.__wrapped__.lower(*args, **kwargs)

    def warm(self, *args, **kwargs) -> bool:
        """-> whether the program came from disk. Nothing runs: ``args``
        may be arrays or ``jax.ShapeDtypeStruct``s of the served call's."""
        key = self.key_fn(*args, **kwargs)
        if key in self._resident:
            return True
        cache = _store_base()
        digest = hashlib.sha256("\x00".join(
            (_build_digest(), self._salt, self.name, _signature((args, kwargs)))
        ).encode()).hexdigest()
        path = Path(cache or "") / "programs" / f"{self.name}-{digest[:40]}.bin"
        try:
            from jax.experimental.serialize_executable import deserialize_and_load

            if cache:
                self._resident[key] = deserialize_and_load(
                    *pickle.loads(path.read_bytes()), execution_devices=self._devices)
                return True
        except FileNotFoundError:
            pass
        except Exception:  # noqa: BLE001 — an unreadable file is a miss
            logger.warning("stored program %s unreadable: compiled again", path.name,
                           exc_info=True)
        compiled = self.__wrapped__.lower(*args, **kwargs).compile()
        self._resident[key] = compiled
        if not cache:
            return False
        try:
            from jax.experimental.serialize_executable import serialize

            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(f".{os.getpid()}.tmp")
            tmp.write_bytes(pickle.dumps(serialize(compiled)))
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 — the program serves; the next boot compiles again
            logger.warning("program %s not stored", path.name, exc_info=True)
        return False
