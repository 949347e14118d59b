"""Backend selection helpers.

JAX picks the accelerator when one is attached and honours
``JAX_PLATFORMS`` otherwise.  These helpers place the persistent compile
cache and force the host backend (with N virtual devices) through
jax.config, for tests/smoke runs on machines without a chip.
"""

from __future__ import annotations

import hashlib
import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """Where the persistent XLA compilation cache lives.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used byte for byte and
    nothing else is ever configured.  Unset, the cache is a fixed path
    inside the checkout (the path is part of what makes a later process
    hit), under a subdirectory keyed by ``XLA_FLAGS`` — the one piece of
    the XLA environment that changes generated code but escapes jax's
    cache key (``--xla_force_host_platform_device_count`` is excluded
    from it): an executable the test env compiled under 8 virtual CPU
    devices, replayed in a 1-device tool process, is not even run-to-run
    deterministic (seen once: it flipped a K=1 bitwise check of the step
    pipeline on identical inputs).  Derived from the environment
    string alone — no backend is initialised to answer.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    flags = os.environ.get("XLA_FLAGS", "")
    return os.path.join(
        _REPO_ROOT, ".jax_cache", hashlib.sha1(flags.encode()).hexdigest()[:8]
    )


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache — first compiles of the big train
    graphs take minutes; every later process reuses them.  Must not touch
    the backend: tools call this before ``jax.distributed.initialize``
    and before :func:`force_cpu`."""
    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cli_bootstrap() -> None:
    """Shared entry-point preamble for every tool main(): persistent
    compile cache + INFO logging (force=True — jax/absl pre-install a
    root handler at WARNING that would swallow the logs)."""
    import logging

    enable_compile_cache()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
        force=True,
    )


def use_pallas() -> bool:
    """Pallas kernels on the TPU, jnp references elsewhere.
    Override with MX_RCNN_TPU_PALLAS=0/1."""
    env = os.environ.get("MX_RCNN_TPU_PALLAS")
    if env is not None:
        return env == "1"
    import jax

    return jax.devices()[0].platform == "tpu"


def set_cpu_platform(n_devices: int = 1) -> None:
    """Point JAX at the host backend with ``n_devices`` virtual devices
    WITHOUT touching the backend (no device probe) — the half of
    :func:`force_cpu` that may safely run before
    ``jax.distributed.initialize`` (which itself must precede the first
    backend initialization)."""
    import jax

    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        )
    jax.config.update("jax_platforms", "cpu")


def force_cpu(n_devices: int = 1) -> None:
    """Switch JAX to the host CPU backend with ``n_devices`` virtual
    devices.  Must run before the first backend initialization in this
    process (XLA parses XLA_FLAGS exactly once, at first client init)."""
    import jax
    from jax._src import xla_bridge as xb

    set_cpu_platform(n_devices)
    if xb.backends_are_initialized():
        from jax.extend.backend import clear_backends

        clear_backends()
    got = len(jax.devices())
    if got < n_devices:
        raise RuntimeError(
            f"need {n_devices} host devices, got {got} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            f"before any jax use"
        )
