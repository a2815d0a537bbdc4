"""Device modules (JAX/XLA/Pallas kernels).

Importing this package applies the repo's ONE compile-cache rule; every
module that compiles imports it (or a submodule) before its first
compilation:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax has already read it into
  ``jax_compilation_cache_dir`` — nothing is set in code;
- unset: ``<checkout>/.jax_cache`` (in ``.gitignore``).  A fixed path
  inside the checkout: the directory is part of what makes an entry
  findable again, so a path from ``~``, a pid or a temp name never hits.

jax initialises the cache at the first compilation and ignores a later
update without a word — hence "before the first compilation".

The rule has a second half.  A Pallas kernel reaches XLA as a custom call
whose body is the serialized Mosaic module, locations included, and jax
strips locations from the module it hashes but not from that body.  With
full tracebacks in locations (jax's default) the body holds the Python
call stack that traced the kernel, so the same kernel warmed from node
boot and from ``chip_smoke.py`` is two cache keys and two compiles (seen
on the v5e: three misses and a 65 s node warm-up straight after another
process had compiled the same three shapes; 42 s and three hits with
this set).  Locations are therefore cut to the innermost frame, which is
the kernel's own source line whoever calls it.
"""

import os as _os
import threading as _threading
import time as _time

import jax as _jax
import jax.monitoring as _monitoring

if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(
                _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
            ),
            ".jax_cache",
        ),
    )
_jax.config.update("jax_include_full_tracebacks_in_locations", False)


def device_info() -> dict:
    """The device as jax reports it — printed beside every on-device
    number, so a CPU run can never pass for a chip run."""
    devices = _jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu(what: str) -> dict:
    """``device_info()`` of a TPU backend, or raise: a path that means
    the chip (``node run --verifier tpu``, ``chip_smoke.py``) must not
    carry on with the XLA kernel on the CPU."""
    backend = _jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{what} needs a TPU, but jax's default backend is '{backend}'"
        )
    return device_info()


class FirstCallTimer:
    """Where a first call's seconds went, from jax's own monitoring
    events: tracing to a jaxpr and lowering to a module are host work
    every process pays; only the backend compile is what the persistent
    cache replaces with a load.  Use as a context manager around the
    calls, ``take()`` after each.  It counts the events of the thread
    that entered it, so programs warmed in threads of their own are
    timed each by its own."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_or_load_s",
    }
    _COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __enter__(self) -> "FirstCallTimer":
        self._thread = _threading.get_ident()
        self._acc: dict = {}
        self._t0 = _time.perf_counter()
        _monitoring.register_event_duration_secs_listener(self._on_duration)
        _monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        _monitoring.unregister_event_duration_listener(self._on_duration)
        _monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if _threading.get_ident() != self._thread:
            return
        key = self._DURATIONS.get(event)
        if key == "trace_s":
            # jits nest and the outer trace's time holds the inner's
            self._acc[key] = max(self._acc.get(key, 0.0), duration)
        elif key:
            self._acc[key] = self._acc.get(key, 0.0) + duration

    def _on_event(self, event: str, **_kw) -> None:
        if _threading.get_ident() != self._thread:
            return
        key = self._COUNTS.get(event)
        if key:
            self._acc[key] = self._acc.get(key, 0) + 1

    def take(self) -> dict:
        """Wall seconds and event totals since the last ``take()``."""
        now = _time.perf_counter()
        acc, self._acc = self._acc, {}
        out = {"first_call_s": round(now - self._t0, 2)}
        self._t0 = now
        out.update(
            (k, round(acc.get(k, 0.0), 2)) for k in self._DURATIONS.values()
        )
        out.update((k, acc.get(k, 0)) for k in self._COUNTS.values())
        return out
