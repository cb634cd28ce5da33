"""The ONE place allowed to mutate ``XLA_FLAGS`` (lint-enforced by
``tests/test_xla_flags_policy.py``; a handful of test/example rigs may set
the device-count flag, nothing else).

XLA treats unknown flags as FATAL at first backend init, so every
injection lives here and nowhere else is allowed to spell the flag names.

The timeout flags: XLA:CPU aborts the process when a collective
participant waits >40 s, and on a thread-starved CI rig (8 virtual
devices on one core) a long compile can legitimately stall a participant
that long.
"""

from __future__ import annotations

import os

# (flag, default) pairs injected by inject_cpu_collective_timeouts()
_TIMEOUT_FLAGS = (
    ("xla_cpu_collective_call_terminate_timeout_seconds", 600),
    ("xla_cpu_collective_call_warn_stuck_timeout_seconds", 60),
)


def _append_flag(name: str, value) -> None:
    """Append ``--name=value`` to XLA_FLAGS unless the name is already
    present (a user-provided value always wins)."""
    cur = os.environ.get("XLA_FLAGS", "")
    if name in cur:
        return
    os.environ["XLA_FLAGS"] = (cur + f" --{name}={value}").strip()


def inject_cpu_collective_timeouts() -> None:
    """Raise the XLA:CPU collective-rendezvous abort threshold (warn log
    stays early).  Must run before the backend initialises."""
    for name, default in _TIMEOUT_FLAGS:
        _append_flag(name, default)


def force_host_platform_device_count(n: int) -> None:
    """Request ``n`` virtual CPU devices (the multi-chip CI rig).  Must run
    before the backend initialises; a pre-existing user value wins."""
    _append_flag("xla_force_host_platform_device_count", int(n))
