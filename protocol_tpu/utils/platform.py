"""Process-level JAX placement: which platform a process may claim and
where its compiled executables are kept.

A chip belongs to one process at a time, so the two decisions below are
made once, at the top of a process entry point, before any jax op runs:

  - :func:`force_host_cpu` pins a process to the host-CPU platform —
    tests, the multi-chip dry run on a virtual mesh, and every
    control-plane process that must not claim the chip the scheduler
    pod owns.
  - :func:`place_compile_cache` gives the persistent compilation cache
    a directory that does not move between runs (the path is part of
    the cache key's lookup, so a tempdir never hits).

Nothing here falls back: a process that wants the accelerator simply
does not call :func:`force_host_cpu`, and reports what it got with
:func:`device_summary`.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def force_host_cpu(n_devices: int = 8) -> None:
    """Pin JAX to the host-CPU platform with >= ``n_devices`` devices.

    Must run before the CPU backend is first initialized (before any jax
    op runs on CPU in this process). Raises with a diagnosis if the
    requested device count cannot be satisfied.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(_FLAG) + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + f" {_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(m.group(0), f"{_FLAG}={n_devices}")

    import jax

    jax.config.update("jax_platforms", "cpu")
    got = len(jax.devices("cpu"))
    if got < n_devices:
        raise RuntimeError(
            f"need {n_devices} host devices, got {got}: the CPU backend was "
            "already initialized before force_host_cpu() — call it before "
            "any jax op in this process"
        )


def place_compile_cache() -> str:
    """Give the persistent compilation cache a fixed home; returns the
    directory in use. Called first thing by every process entry that can
    hold the chip.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and this
    function sets nothing. Unset: ``<checkout>/.jax_cache`` — the same
    path in every process and every run from this checkout, so a second
    run finds what the first one compiled."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> dict:
    """What JAX actually gave this process: ``platform``, ``device_kind``
    and ``device_count`` of the default backend. Every start-up line and
    every report that carries a timing names these, so a run that came
    up on the CPU says so."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
