"""Global runtime flags (kernel routing, interpret mode) and the
persistent compilation cache that entry points turn on.

Environment overrides (read once at import) let CI exercise the Pallas
kernels without code changes:

* ``REPRO_USE_PALLAS=1``       — route hot attention paths via Pallas even
  off-TPU (paired with interpret mode this is the ``pallas-interpret`` CI
  job that runs the kernel parity suites on every PR).
* ``REPRO_PALLAS_INTERPRET=0`` — force compiled Pallas (TPU only).
"""
from __future__ import annotations

import dataclasses
import os
import pathlib


def _env_bool(name: str, default: bool) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() not in ("0", "false", "no", "")


@dataclasses.dataclass
class Flags:
    use_pallas: bool = False          # route hot attention paths via Pallas
    pallas_interpret: bool = True     # CPU container: interpret=True


flags = Flags(
    use_pallas=_env_bool("REPRO_USE_PALLAS", False),
    pallas_interpret=_env_bool("REPRO_PALLAS_INTERPRET", True),
)


# <checkout>/.jax_cache (git ignores it): a fixed path, since a cache
# whose directory moves between runs is never found again
DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[2] / \
    ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set; otherwise the cache is the fixed
    ``<checkout>/.jax_cache``.  Called by entry points (``main``s), never
    on import or from tests."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
