"""Test rig: 8 virtual CPU devices — the analog of the reference's
"COMPSs workers as local processes" CI trick (SURVEY.md §5).

The suite runs on the CPU platform with 8 virtual devices so every sharding /
collective path executes for real.  Set ``DSLIB_TEST_TPU=1`` to run the same
tests unmodified on the real TPU backend instead (SURVEY §5 implication (c)).

XLA_FLAGS must be set before the first backend initialisation; the platform
override must happen before any jax computation (this file is imported by
pytest ahead of all test modules).
"""

import os

_ON_TPU = os.environ.get("DSLIB_TEST_TPU") == "1"

if not _ON_TPU:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_mesh():
    """Each test starts from the default (n_devices, 1) mesh unless it sets
    its own — and leaves it that way: a module-scoped fixture of the NEXT
    file runs before that file's first per-test reset, so a test that
    shrinks the mesh (the elastic tiers) must not hand its mesh on."""
    import dislib_tpu as ds
    ds.init()
    yield
    ds.init()


# Every jitted executable holds LLVM JIT code pages, and one long pytest
# process compiles ~thousands of programs; on this rig the suite's memory
# MAP count reaches the kernel's vm.max_map_count ceiling (default 65530)
# around the late test files, at which point an mmap failure inside a
# compile SEGFAULTS the whole run (observed 2026-08-04 at test_trees,
# reproducible at the PR-4 HEAD — an environment regression, not a code
# one).  Relief valve: when the process's map count crosses the
# threshold, drop jax's executable caches — the affected late files
# recompile their own programs (they share little with earlier files),
# which costs seconds, not the suite.
_MAP_RELIEF_THRESHOLD = int(os.environ.get("DSLIB_TEST_MAP_RELIEF", "45000"))


@pytest.fixture(autouse=True, scope="module")
def _jit_map_pressure_relief():
    try:
        n_maps = sum(1 for _ in open("/proc/self/maps"))
    except OSError:          # non-Linux: no ceiling to manage
        n_maps = 0
    if _MAP_RELIEF_THRESHOLD and n_maps > _MAP_RELIEF_THRESHOLD:
        import warnings
        warnings.warn(
            f"conftest: {n_maps} memory maps — clearing jax caches to stay "
            "under vm.max_map_count (see conftest note)", ResourceWarning)
        jax.clear_caches()
    yield


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def skip_unless_devices(n):
    """Skip on rigs with fewer than n devices — the single-chip TPU suite
    run can't host the multi-device mesh-shape tests."""
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (single-chip TPU suite run)")
