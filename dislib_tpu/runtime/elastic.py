"""Elastic-resume helpers: restore a snapshot onto a DIFFERENT mesh.

Snapshots keep recovery state as host-side logical arrays (the host/device
split of arXiv:2112.09017): small replicated results (centers, mixture
parameters, SV sets) are mesh-independent as stored, and the only
mesh-dependent artifact is the pad width of row-padded state (ds-arrays
pad every dimension to the mesh quantum).  Resharding on restore
(arXiv:2112.01075 discipline) therefore reduces to :func:`repad_rows` —
crop the writing mesh's pad rows (zero by the pad-and-mask invariant) and
zero-fill to the restoring mesh's quantum — after which the normal
``device_put`` of the fit path lays the state out for the new topology.
An 8-device snapshot restores onto a 4-device or 2-D mesh this way.

:func:`fetch` is the host↔device transfer boundary with the
transient-failure :class:`~dislib_tpu.runtime.retry.Retry` policy applied
— the read every snapshot goes through.  ``fetch(x, blocking=False)``
returns an :class:`AsyncFetch` handle instead: the device→host copy is
enqueued immediately (before any later dispatch), but the blocking
resolution happens at ``result()`` — on the snapshot worker thread for
``FitCheckpoint.save_async``, so the copy and the file write overlap the
next chunk's compute instead of stalling the fit loop (round-7 perf PR).
"""

from __future__ import annotations

import numpy as np

__all__ = ["repad_rows", "fetch", "AsyncFetch"]


def repad_rows(a, logical: int, target: int, axis: int = 0):
    """Re-pad state along ``axis`` for the restoring mesh: keep the
    first ``logical`` (real) slices, zero-fill out to ``target`` (the
    restoring mesh's padded extent).  Exact because pad slices carry
    zeros under the pad-and-mask invariant.  Raises when the state holds
    fewer than ``logical`` slices (foreign/stale snapshot).

    Two routes (round-11 rechunk PR): a ``jax.Array`` input — state
    already ON DEVICE at an elastic mesh change — re-pads in one jitted
    kernel (``ops/rechunk.repad_axis``) and STAYS on device, no host
    round trip; anything else takes the original host-NumPy path, kept
    as the snapshot-restore fallback (checkpoint state arrives as host
    ndarrays by design)."""
    if not isinstance(a, np.ndarray):
        import jax
        if isinstance(a, jax.Array):
            if a.shape[axis] < logical:
                raise ValueError(
                    f"snapshot state has {a.shape[axis]} rows along axis "
                    f"{axis} but the logical state needs {logical} — stale "
                    "or foreign snapshot")
            if target < logical:
                raise ValueError(
                    f"target padded extent {target} is smaller than the "
                    f"logical extent {logical}")
            from dislib_tpu.ops.rechunk import repad_axis
            return repad_axis(a, int(logical), int(target), axis)
    a = np.asarray(a)
    if a.shape[axis] < logical:
        raise ValueError(
            f"snapshot state has {a.shape[axis]} rows along axis {axis} but "
            f"the logical state needs {logical} — stale or foreign snapshot")
    if target < logical:
        raise ValueError(
            f"target padded extent {target} is smaller than the logical "
            f"extent {logical}")
    sl = [slice(None)] * a.ndim
    sl[axis] = slice(0, logical)
    a = a[tuple(sl)]
    if target == logical:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, target - logical)
    return np.pad(a, pad)


class AsyncFetch:
    """Deferred device→host read started by ``fetch(x, blocking=False)``.

    The copy is enqueued at construction (``copy_to_host_async``) so it
    runs concurrently with whatever the caller dispatches next;
    :meth:`result` blocks until the bytes are on host (retried under the
    default transient policy) and caches the ndarray.

    NOT safe for buffers a later kernel call DONATES: donation
    invalidates the device buffer at dispatch time, before an un-resolved
    copy may have landed.  Estimators whose snapshot state is also a
    donated loop carry (ALS factors, GMM parameters, forest node arrays)
    fetch those blocking and overlap only the file write.
    """

    def __init__(self, x):
        self._x = x
        self._value = None
        self._resolved = False
        if hasattr(x, "copy_to_host_async"):    # a host value has none
            x.copy_to_host_async()

    def result(self) -> np.ndarray:
        if not self._resolved:
            import jax

            from dislib_tpu.runtime.retry import Retry
            from dislib_tpu.utils.profiling import host_read
            try:
                with host_read():
                    self._value = Retry.from_env().call(
                        lambda: np.asarray(jax.device_get(self._x)))
            except RuntimeError as e:
                if "deleted" in str(e) or "donated" in str(e):
                    raise RuntimeError(
                        "async fetch source buffer was donated before the "
                        "copy resolved — snapshot donated loop carries with "
                        "fetch(x, blocking=True) (see the user guide's "
                        "'Dispatch, fusion & donation' section)") from e
                raise
            self._resolved = True
            self._x = None
        return self._value


def fetch(x, blocking: bool = True):
    """Device→host read (``jax.device_get`` → ndarray) with transient
    failures retried under the env-tunable default policy — the snapshot
    write path's half of the host↔device boundary.

    A ds-array input is a force point: its deferred op chain runs as one
    program before the copy.  ``blocking=False`` returns an
    :class:`AsyncFetch` whose copy overlaps later host work;
    ``FitCheckpoint.save`` resolves such handles at write time."""
    if hasattr(x, "_data"):             # ds-array → padded device backing
        x = x._data
    if not blocking:
        return AsyncFetch(x)
    import jax

    from dislib_tpu.runtime.retry import Retry
    from dislib_tpu.utils.profiling import host_read
    with host_read():
        return Retry.from_env().call(lambda: np.asarray(jax.device_get(x)))
