"""Compiled programs kept on disk, so that a booting process loads them
instead of tracing and lowering them again.

jax's persistent compile cache is keyed by a hash of the lowered module,
so every process traces and lowers a program before it can look it up:
~12 s of host work for each pad shape of the Pallas wave entry on a v5e,
against ~0.1 s for the load itself (CHANGES.md).  This store is keyed by
what decides the compiled program instead: the arguments' shapes and
dtypes, the entry's own options, the jax, jaxlib and backend versions,
the device kind and count, ``XLA_FLAGS`` and ``LIBTPU_INIT_ARGS``, and a
digest of the source of this package, which holds every traced line.  A
hit is one ``deserialize_and_load``: no trace, no lower, no backend
compile.

The store is a subdirectory of jax's compile-cache directory, which
``tpu/__init__.py`` alone decides, so removing that directory clears
both.  An entry is written to a temporary file beside it and renamed
into place, so a reader sees a whole entry or none; one that cannot be
read or loaded is a miss, rebuilt and overwritten, never a failed boot.

A miss builds as a ``jax.jit`` call would, so the program it writes may
be one jax loaded from its own cache.  The TPU serializes such a program
again whole (seen on a v5e); XLA:CPU does not (the copy it writes fails
at its first call), which is one reason the verifier engages the store
on the CPU only where it is handed a directory.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import tempfile
import time
from functools import lru_cache

import jax
import jaxlib
from jax.experimental import serialize_executable

log = logging.getLogger(__name__)

#: the store's directory inside jax's compile-cache directory
SUBDIR = "executables"


def default_dir() -> str | None:
    """The store beside the compile cache, or None where that cache is
    off or not a local directory."""
    root = jax.config.jax_compilation_cache_dir
    if not root or "://" in root:
        return None
    return os.path.join(root, SUBDIR)


@lru_cache(maxsize=1)
def source_digest() -> str:
    """sha256 over the name and bytes of every module of this package."""
    here = os.path.dirname(os.path.abspath(__file__))
    digest = hashlib.sha256()
    for name in sorted(os.listdir(here)):
        if name.endswith(".py"):
            with open(os.path.join(here, name), "rb") as f:
                digest.update(name.encode() + b"\0" + f.read() + b"\0")
    return digest.hexdigest()


def key(entry: str, args, **options) -> dict:
    """The store's key of the program ``entry`` compiles for ``args``
    under its ``options``: the pytree structure of ``args`` and each
    leaf's shape and dtype, then what the process brings to it."""
    leaves, tree = jax.tree.flatten(args)
    device = jax.devices()[0]
    return {
        "entry": entry,
        **options,
        "args": [str(tree)] + [[list(x.shape), str(x.dtype)] for x in leaves],
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform_version": device.client.platform_version,
        "device_kind": device.device_kind,
        "device_count": jax.device_count(),
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "x64": bool(jax.config.jax_enable_x64),
        "source": source_digest(),
    }


class ExecutableStore:
    """Serialized ``jax.stages.Compiled`` programs under ``root``, one
    file a key.  A key is a JSON-able dict; the file is named by its
    hash and holds the key itself, which a load compares."""

    def __init__(self, root: str):
        self.root = root

    def path(self, key: dict) -> str:
        text = json.dumps(key, sort_keys=True)
        return os.path.join(
            self.root, hashlib.sha256(text.encode()).hexdigest()[:40] + ".exe"
        )

    def load(self, key: dict):
        """The stored program for ``key``, or None: no file, another
        key's file, or one that does not read or load."""
        try:
            with open(self.path(key), "rb") as f:
                stored, device_ids, blob, in_tree, out_tree = pickle.load(f)
            if stored != key:
                return None
            devices = {d.id: d for d in jax.devices()}
            return serialize_executable.deserialize_and_load(
                blob,
                in_tree,
                out_tree,
                execution_devices=[devices[i] for i in device_ids],
            )
        except FileNotFoundError:
            return None
        except Exception as e:  # a torn, foreign or stale entry: a miss
            log.warning("Executable store: %s unusable (%r)", self.path(key), e)
            return None

    def save(self, key: dict, compiled) -> None:
        """Write ``compiled`` under ``key``: a temporary file in the same
        directory, then one rename over whatever was there."""
        sharding = jax.tree.leaves(compiled.input_shardings)[0]
        device_ids = sorted(d.id for d in sharding.device_set)
        payload = pickle.dumps(
            (key, device_ids, *serialize_executable.serialize(compiled))
        )
        os.makedirs(self.root, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(payload)
            os.replace(tmp, self.path(key))
        except BaseException:
            os.unlink(tmp)
            raise

    def get(self, key: dict, build):
        """``(program, report)``: the stored program for ``key``, else
        ``build()``'s, written for the next process.  ``report`` says
        which (``exe``: ``loaded`` or ``built``) and the milliseconds the
        load, or the serialization and write, took (``exe_ms``)."""
        t0 = time.perf_counter()
        program = self.load(key)
        if program is not None:
            return program, _report("loaded", t0)
        program = build()
        t0 = time.perf_counter()
        try:
            self.save(key, program)
        except (
            OSError, ValueError, NotImplementedError, pickle.PicklingError
        ) as e:
            # a read-only or full disk costs the next boot its trace and
            # lower, as before the store; it never costs this one a wave
            log.warning("Executable store: not written (%r)", e)
        return program, _report("built", t0)


def _report(exe: str, t0: float) -> dict:
    return {"exe": exe, "exe_ms": round((time.perf_counter() - t0) * 1e3, 1)}
