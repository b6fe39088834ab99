"""Entry-point setup: forced host devices, the device check, the compile
cache — jax-free at import on purpose.

The elastic runner, the multi-device examples and the runner benchmark run
one worker per device. On the CPU that needs more than one device, and jax
pins the device count at first backend init, so the host-device flag has to
be in ``XLA_FLAGS`` *before any jax import*: entry points call
:func:`ensure_host_devices` as their very first statement. The flag touches
only the CPU backend; on a TPU host the chips are the workers, and
:func:`require_devices` fails early when there are too few of them.

``tests/conftest.py::run_with_devices`` does the same thing for test
subprocesses; this is the library-side equivalent for examples/benchmarks.
"""

from __future__ import annotations

import os
import sys

_FLAG = "--xla_force_host_platform_device_count"

#: The persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed directory at the checkout root. The path is part of the cache
#: key, so it must never depend on a temp name, a pid or the time.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache")


def ensure_host_devices(n: int) -> int:
    """Request ``n`` forced host devices; returns the count actually in force.

    - If jax is already imported, the device count is frozen: return the
      existing count (callers decide whether that is enough).
    - If ``XLA_FLAGS`` already forces a count, keep it (the user or a parent
      process chose it deliberately).
    - Otherwise append the force flag for ``n`` devices.
    """
    if "jax" in sys.modules:
        import jax  # already initialized; count is whatever it is

        return jax.local_device_count()
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags:
        for tok in flags.split():
            if tok.startswith(_FLAG + "="):
                try:
                    return int(tok.split("=", 1)[1])
                except ValueError:  # malformed; leave it to jax to complain
                    return n
        return n
    os.environ["XLA_FLAGS"] = (flags + f" {_FLAG}={n}").strip()
    return n


def require_devices(n_workers: int) -> None:
    """Fail with both counts unless jax sees one device per worker.

    On a TPU host the chips are the devices (the host-device flag does not
    apply there), so a four-worker entry point needs four chips."""
    import jax

    devices = jax.devices()
    if len(devices) < n_workers:
        raise SystemExit(
            f"{n_workers} workers need {n_workers} devices (one per "
            f"worker); found {len(devices)} {devices[0].platform} "
            f"device(s)")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here. Otherwise the cache goes to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
