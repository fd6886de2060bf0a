"""Where JAX's persistent compilation cache lives — the one place that decides.

The cache key includes the directory, so a directory that moves never hits.
Rule (ISSUE 21):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself; nothing
  is configured in code, whatever the config or the caller would prefer.
- unset: ``override`` (``TpuConfig.compilation_cache_dir``) if given, else
  one fixed directory inside the checkout, ``.bench_cache/xla``
  (git-ignored). Never a path made from a temporary name, a pid or the time.

An entry is keyed WITH the program's metadata (``op_name``, source
positions). JAX's default leaves it out, and an executable taken from the
cache then carries the metadata of whoever compiled it first: a tree
without the device scopes (telemetry/device_scopes.py), where two trees share
a directory. The scope table a recording session writes reads that metadata.

``load()``/``compile()``, ``benchmark/harness/system.py`` and ``chip_smoke.py`` all call
:func:`configure_compile_cache`; it is the only ``set_cache_dir`` call site.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed fallback: ``<checkout>/.bench_cache/xla``
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".bench_cache",
    "xla",
)


def configure_compile_cache(override: Optional[str] = None) -> str:
    """Point the persistent compilation cache at its directory and return
    that directory. Idempotent; errors (an unwritable directory, a cache
    already initialised elsewhere) propagate — a run that believes it
    caches and does not is a wrong measurement of compile time."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = override or DEFAULT_CACHE_DIR
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.set_cache_dir(path)
    return path
