"""The persistent cache's key may not hold the directory a checkout stands
in (runtime/compile_cache.py).  An XLA-only program's key never did: JAX
hashes the module with its locations stripped.  A Pallas TPU kernel
travels inside a custom call as serialized Mosaic bytecode that keeps its
locations, absolute paths and all, so a program that holds one — the
KMeans fit since the fused Lloyd step — compiled anew in every copy of the
tree until ``enable()`` named source files relative to the checkout.

Each case copies the package into two directories, lowers the fused step
for the TPU in a subprocess from each (lowering needs no chip) and hashes
the lowered text, once before ``enable()`` and once after."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LOWER = r"""
import hashlib, json, sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from dislib_tpu.ops import pallas_kernels as pk
from dislib_tpu.runtime import compile_cache
pk._interpret = lambda: False                  # lower for Mosaic, as the chip does
assert compile_cache._CHECKOUT == sys.argv[1], compile_cache._CHECKOUT


def key():
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    step = jax.jit(lambda x, sq, w, c: pk.kmeans_step(x, sq, w, c, 512, 256))
    low = step.trace(S(2048, 100), S(1, 2048), S(1, 2048), S(10, 100)) \
        .lower(lowering_platforms=("tpu",))
    text = low.as_text()
    assert "tpu_custom_call" in text
    return hashlib.sha256(text.encode()).hexdigest()


before = key()
compile_cache.enable()
print(json.dumps({"before": before, "after": key(),
                  "regex": jax.config.jax_hlo_source_file_canonicalization_regex}))
"""


@pytest.fixture(scope="module")
def keys(tmp_path_factory):
    """``{directory name: {"before", "after", "regex"}}`` from two copies
    of the package."""
    out = {}
    for name in ("first", "second/deeper"):
        root = str(tmp_path_factory.mktemp("checkout") / name)
        shutil.copytree(os.path.join(REPO, "dislib_tpu"),
                        os.path.join(root, "dislib_tpu"),
                        ignore=shutil.ignore_patterns("__pycache__", "*.so"))
        env = {k: v for k, v in os.environ.items()
               if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
        env["JAX_PLATFORMS"] = "cpu"
        got = subprocess.run([sys.executable, "-c", _LOWER, root], env=env,
                             cwd=root, capture_output=True, text=True,
                             timeout=300)
        assert got.returncode == 0, got.stderr[-3000:]
        out[name] = json.loads(got.stdout.strip().splitlines()[-1])
    return out


def test_enable_names_files_relative_to_the_checkout(keys):
    for row in keys.values():
        assert row["regex"].startswith("^") and "dislib_tpu" not in row["regex"]


def test_fused_step_hashes_alike_from_two_directories(keys):
    first, second = keys.values()
    assert first["after"] == second["after"]


def test_without_the_regex_the_directory_is_in_the_key(keys):
    """So the case above cannot pass by accident: the same two lowerings
    differ while the option is unset."""
    first, second = keys.values()
    assert first["before"] != second["before"]
    assert first["before"] != first["after"]


def test_a_value_the_user_set_is_left_alone():
    import jax
    from dislib_tpu.runtime import compile_cache
    name = "jax_hlo_source_file_canonicalization_regex"
    old = getattr(jax.config, name)
    try:
        jax.config.update(name, "^/somewhere/else/")
        compile_cache.enable()
        assert getattr(jax.config, name) == "^/somewhere/else/"
    finally:
        jax.config.update(name, old)
