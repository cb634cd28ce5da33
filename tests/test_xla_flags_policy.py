"""XLA_FLAGS hygiene lint + the collective-timeout injection's contract
(XLA fatally aborts on unknown flags, so flag names live in one module).

Policy, enforced by scanning the repo's Python sources:

1. the XLA:CPU collective-timeout flag NAMES may be spelled only in
   ``dislib_tpu/runtime/xla_flags.py`` (the one injection site) —
   nowhere else;
2. ``os.environ["XLA_FLAGS"]`` mutation is allowed only in that module
   plus a short allowlist of test/example bootstrap sites, and those
   sites may set only the universally-supported device-count flag.
"""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the one module allowed to spell the timeout flag names
GUARDED_SITE = "dislib_tpu/runtime/xla_flags.py"

# bootstrap sites that may mutate XLA_FLAGS directly — each must touch
# ONLY the device-count flag (asserted below); everything else routes
# through runtime.xla_flags
MUTATION_ALLOWLIST = {
    GUARDED_SITE,
    "tests/conftest.py",
    "tests/mp_worker.py",
    "examples/multihost_launch.py",
    # round-19 two-process dryrun worker: each rank bootstraps 2 virtual
    # CPU devices pre-import (the mp_worker precedent); device-count
    # flag only
    "tools/mh_dryrun.py",
}

_MUTATION = re.compile(
    r"""(environ\s*\[\s*['"]XLA_FLAGS['"]\s*\]\s*=
         |environ\.setdefault\(\s*['"]XLA_FLAGS
         |putenv\(\s*['"]XLA_FLAGS)""", re.VERBOSE)
_TIMEOUT_FLAG = re.compile(r"xla_cpu_collective_call")


def _py_files():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                full = os.path.join(root, f)
                yield os.path.relpath(full, REPO).replace(os.sep, "/"), full


def test_timeout_flag_names_confined_to_guarded_site():
    offenders = []
    for rel, full in _py_files():
        if rel in (GUARDED_SITE, "tests/test_xla_flags_policy.py"):
            continue
        with open(full, encoding="utf-8", errors="replace") as f:
            if _TIMEOUT_FLAG.search(f.read()):
                offenders.append(rel)
    assert not offenders, (
        "the XLA:CPU collective-timeout flags may only be injected by "
        f"{GUARDED_SITE}; found the names in: {offenders}")


def test_xla_flags_mutation_only_at_allowed_sites():
    offenders, allowlisted = [], []
    for rel, full in _py_files():
        if rel == "tests/test_xla_flags_policy.py":
            continue  # this file quotes the forbidden pattern in asserts
        with open(full, encoding="utf-8", errors="replace") as f:
            src = f.read()
        if not _MUTATION.search(src):
            continue
        if rel not in MUTATION_ALLOWLIST:
            offenders.append(rel)
        elif rel != GUARDED_SITE:
            allowlisted.append((rel, src))
    assert not offenders, (
        "XLA_FLAGS mutation outside the allowed sites — route it through "
        f"dislib_tpu.runtime.xla_flags instead: {offenders}")
    for rel, src in allowlisted:
        # bootstrap sites may only set the device-count flag
        flags = set(re.findall(r"--(xla_\w+)", src))
        assert flags <= {"xla_force_host_platform_device_count"}, (
            f"{rel} sets XLA flags other than the device-count bootstrap "
            f"flag ({flags}) — use dislib_tpu.runtime.xla_flags")


class TestInjection:
    def test_inject_is_idempotent(self, monkeypatch):
        from dislib_tpu.runtime import xla_flags as xf
        monkeypatch.setenv("XLA_FLAGS", "")
        xf.inject_cpu_collective_timeouts()
        flags = os.environ["XLA_FLAGS"]
        assert "terminate_timeout_seconds=600" in flags
        assert "warn_stuck_timeout_seconds=60" in flags
        xf.inject_cpu_collective_timeouts()
        assert os.environ["XLA_FLAGS"] == flags

    def test_user_value_wins(self, monkeypatch):
        from dislib_tpu.runtime import xla_flags as xf
        monkeypatch.setenv(
            "XLA_FLAGS",
            "--xla_cpu_collective_call_terminate_timeout_seconds=99")
        xf.inject_cpu_collective_timeouts()
        assert "terminate_timeout_seconds=99" in os.environ["XLA_FLAGS"]
        assert "terminate_timeout_seconds=600" not in os.environ["XLA_FLAGS"]

    def test_device_count_helper(self, monkeypatch):
        from dislib_tpu.runtime import xla_flags as xf
        monkeypatch.setenv("XLA_FLAGS", "")
        xf.force_host_platform_device_count(6)
        assert os.environ["XLA_FLAGS"] == \
            "--xla_force_host_platform_device_count=6"
        xf.force_host_platform_device_count(8)   # existing value wins
        assert "=6" in os.environ["XLA_FLAGS"]
