"""Process-level JAX platform selection.

One rule, no probe: ``REPORTER_TPU_PLATFORM`` (or the ``prefer``
argument of :func:`ensure_backend`) says which platform a process runs
on.

- ``cpu`` pins the CPU backend, optionally as a virtual multi-device
  mesh (``REPORTER_TPU_VIRTUAL_DEVICES``). Tests and CI use it; child
  processes inherit it through the exported env var.
- ``tpu`` initialises the chip in-process and raises unless JAX came up
  on a TPU — a missing chip is an error, never a silent CPU run.
- unset takes JAX's own default platform and logs which one it is.

A chip belongs to one process at a time, so nothing here starts a child
that touches JAX: the process that calls ensure_backend is the one that
holds the device.

Reference analog: the reference binds to its native matcher at process
start (reporter_service.py:284 ``valhalla.Configure``) and simply dies
if the library is missing — ``tpu`` is that contract for the chip.
"""
from __future__ import annotations

import logging
import os
import re

log = logging.getLogger(__name__)

ENV_PLATFORM = "REPORTER_TPU_PLATFORM"          # cpu | tpu | unset
ENV_VIRTUAL_DEVICES = "REPORTER_TPU_VIRTUAL_DEVICES"
_DEVICE_COUNT_FLAG = "xla_force_host_platform_device_count"

#: the persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed directory inside the checkout (gitignored), so every process
#: of one tree — and a copied tree — keys the same path
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE = os.path.join(REPO_ROOT, ".jax_cache")

_decided: str | None = None  # this process's platform decision, once made


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _backends_initialized():
    from jax._src import xla_bridge
    return bool(getattr(xla_bridge, "_backends", None))


def enable_compile_cache() -> str:
    """Point JAX at its persistent compilation cache; returns the
    directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    is configured here. Unset: :data:`DEFAULT_COMPILE_CACHE`, with every
    compile that took meaningful time cached (the default 1 s floor
    skips exactly the small shapes a micro-batching service churns
    through). Accelerator runs only: XLA:CPU persists machine code whose
    feature lists are host-specific, and CPU compiles are sub-second.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    os.makedirs(DEFAULT_COMPILE_CACHE, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return DEFAULT_COMPILE_CACHE


def force_virtual_cpu(n_devices: int | None = None) -> None:
    """Pin this process to the CPU backend, optionally as a virtual
    ``n_devices``-device mesh. Must run before the first jax backend
    resolution; safe to call repeatedly. Sets both the env var (for
    child processes) and the live config (jax may already be imported).
    """
    global _decided
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        want = f"--{_DEVICE_COUNT_FLAG}={n_devices}"
        if _DEVICE_COUNT_FLAG in flags:
            # a stale/smaller pre-set count would silently under-provision
            # the mesh — override it with the requested count
            flags = re.sub(rf"--{_DEVICE_COUNT_FLAG}=\d+", want, flags)
        else:
            flags = (flags + " " + want).strip()
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    if _backends_initialized():
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "force_virtual_cpu called after a non-CPU backend was "
                f"already initialised ({jax.default_backend()}); call it "
                "before any jax.devices()/jit use in the process")
        if n_devices is not None and len(jax.devices()) < n_devices:
            raise RuntimeError(
                f"CPU backend already initialised with {len(jax.devices())} "
                f"devices; {n_devices} requested — the device-count flag "
                "only takes effect before the first backend init")
    _decided = "cpu"


def ensure_backend(prefer: str | None = None,
                   n_virtual_devices: int | None = None) -> str:
    """Decide and pin this process's JAX platform; returns its name.

    Authority: the ``prefer`` argument, then ``REPORTER_TPU_PLATFORM``.
    ``cpu`` forces the CPU (exported so child processes inherit it);
    ``tpu`` initialises in-process and raises RuntimeError unless the
    platform is ``tpu``; unset takes JAX's default and logs it once.
    """
    global _decided
    if _decided is not None:
        return _decided

    choice = (prefer or os.environ.get(ENV_PLATFORM) or "").strip().lower()
    if choice == "cpu":
        if n_virtual_devices is None:
            env_n = os.environ.get(ENV_VIRTUAL_DEVICES)
            n_virtual_devices = int(env_n) if env_n else None
        force_virtual_cpu(n_virtual_devices)
        os.environ[ENV_PLATFORM] = "cpu"
        return "cpu"
    if choice not in ("", "tpu"):
        raise ValueError(f"unknown {ENV_PLATFORM} value {choice!r} "
                         "(want cpu, tpu or unset)")

    import jax
    if choice == "tpu" and not _backends_initialized():
        # without this JAX would fall back to the CPU when the chip
        # fails to initialise; pinned, the init error surfaces instead
        jax.config.update("jax_platforms", "tpu")
    dev = jax.devices()[0]
    if choice == "tpu" and dev.platform != "tpu":
        raise RuntimeError(f"{ENV_PLATFORM}=tpu but JAX came up on "
                           f"{dev.platform!r}: no TPU in this process")
    if dev.platform != "cpu":
        enable_compile_cache()
    log.info("JAX platform %s (%s), %d device(s)", dev.platform,
             dev.device_kind, len(jax.devices()))
    _decided = dev.platform
    return _decided
