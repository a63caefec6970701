"""What the running process can observe about its accelerator.

Options that the platform decides are worked out here, once, instead of
being defaulted at every call site:

* ``default_interpret`` — Pallas kernels run compiled on a TPU and in
  interpret mode everywhere else;
* ``default_backend`` — the serving engine's ``stripe_jit`` backend:
  Pallas kernels on a TPU, plain XLA (``jnp``) elsewhere;
* ``default_hw_name`` / ``hw_name_for_device`` — the ``HardwareConfig``
  registry name of the chip JAX reports (an unknown chip is an error,
  not a guess);
* ``enable_compilation_cache`` — JAX's persistent compilation cache at a
  fixed place, for entry points (never on library import);
* ``pin_worker_to_cpu`` — the initializer of helper process pools, so a
  worker can never take the chip from the process that drives it.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

from .hwconfig import DEVICE_KINDS


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret mode unless the default backend is a TPU."""
    return not on_tpu()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def default_backend() -> str:
    """``stripe_jit`` backend for served programs on this platform."""
    return "pallas" if on_tpu() else "jnp"


def hw_name_for_device(device=None) -> str:
    """Registry name of the hardware config for ``device`` (default: the
    first device JAX reports)."""
    kind = (device if device is not None else jax.devices()[0]).device_kind
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise KeyError(
            f"no hardware config for device kind {kind!r}; known kinds: "
            f"{sorted(DEVICE_KINDS)}") from None


def default_hw_name() -> str:
    """The attached chip's config on a TPU; ``tpu_v5e`` (the config the
    CPU-side compiles model) elsewhere."""
    return hw_name_for_device() if on_tpu() else "tpu_v5e"


def enable_compilation_cache(checkout: os.PathLike) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    it itself, and no other is set here); otherwise the cache lives at
    ``<checkout>/.jax_cache``.  The path is part of every entry's key, so
    it is fixed, never temporary or per-process.  Every compile is kept,
    however quick: a cold start pays for many small kernels."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(checkout).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def pin_worker_to_cpu() -> None:
    """Process-pool initializer: a worker computes on the host only.  Only
    one process may hold a chip, and it is the one that started the pool."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
