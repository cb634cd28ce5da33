"""Where JAX's persistent compilation cache lives — the ONE resolver.

The directory is part of the cache key, so it must not move between
processes or runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself, and no code here sets another), else
``<checkout>/.jax_cache`` next to the package.  ``chip_smoke.py`` and
the benchmark's harness (``benchmark/harness.py``) call :func:`enable`;
side files that must be shared between a run's processes follow
:func:`resolve_dir`.

The checkout's own directory must not be part of a key either.  JAX
hashes a lowered module with its debug locations stripped, so an XLA-only
program's key holds no path; but a Pallas TPU kernel travels inside a
custom call as serialized Mosaic bytecode that was written WITH its
locations, out of reach of that strip pass, and each location names the
source file by its absolute path.  :func:`enable` therefore has JAX name
every file under the checkout relative to it
(``jax_hlo_source_file_canonicalization_regex``, which Pallas' lowering
applies to its locations too): the same tree, unpacked anywhere, then
finds what it compiled.
"""

from __future__ import annotations

import os
import re

__all__ = ["resolve_dir", "enable"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def resolve_dir() -> str:
    """The cache directory this process uses (pure: touches no state)."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on at :func:`resolve_dir`, name source
    files relative to the checkout (module docstring) and return the
    directory.  Call before the first compile (it initialises the
    backend).  With the environment variable set the directory is
    already JAX's own; no code sets another.

    On the CPU backend the directory is resolved but persistence stays
    OFF: XLA:CPU (jaxlib 0.9.0) cannot re-serialise an executable it
    loaded from the cache — a bundle exported in a warm process fails
    when it executes, ``NOT_FOUND: Function ... not found`` — and CPU
    runs are tests and dry runs whose compiles take seconds."""
    import jax
    path = resolve_dir()
    # before the CPU's early return: it only names files, and a program
    # lowered here for the TPU then hashes as it does on the chip.  A
    # value the user has set stays
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(_CHECKOUT + os.sep))
    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return path
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only compiles past JAX's 1 s default: a
    # compile that straddles the threshold would be written by one run
    # and not the next, and "a warm run writes nothing" would be noise
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
