"""Operations and bytes the algorithm needs, from the configuration's
shapes — the same whatever implements the step — and the roofline
arithmetic over the table of published peaks.

A share is ``100 * least_time / measured_time``: it cannot pass 100 while
the counted work is what the algorithm needs and the measured time covers
all of it.  Nothing here clamps.
"""

from __future__ import annotations


def kmeans_iter_flops(cfg) -> float:
    """One Lloyd iteration over n rows, d features, k centres: the
    distance cross-term x.c (2ndk) and the centre sums onehot^T x
    (2ndk).  Norms, argmin and the divide are O(nd + nk) and left out."""
    return 4.0 * cfg["rows"] * cfg["features"] * cfg["clusters"]


def kmeans_iter_bytes(cfg) -> float:
    """The least traffic of one Lloyd iteration: one read of X.  Centres,
    labels and sums are k/n of it."""
    return float(cfg["rows"]) * cfg["features"] * cfg["dtype_bytes"]


def matmul_dims(cfg) -> tuple[int, int, int]:
    """``(m, k, n)`` of C = A @ B with A (m, k) and B (k, n); a square
    product states its ``order`` alone."""
    order = cfg.get("order")
    return tuple(int(cfg.get(key, order)) for key in ("m", "k", "n"))


def matmul_flops(cfg) -> float:
    """C = A @ B with A (m, k) and B (k, n): 2mkn."""
    m, k, n = matmul_dims(cfg)
    return 2.0 * m * k * n


def matmul_bytes(cfg) -> float:
    """The least traffic of the product: read A and B, write C, once."""
    m, k, n = matmul_dims(cfg)
    return float(m * k + k * n + m * n) * cfg["dtype_bytes"]


FUNCTIONS = {f.__name__: f for f in (kmeans_iter_flops, kmeans_iter_bytes,
                                     matmul_flops, matmul_bytes)}


def work(name, cfg) -> float:
    """The counted work named ``name`` for configuration ``cfg``."""
    if name not in FUNCTIONS:
        raise KeyError(f"no work function {name!r}; counts.py has "
                       f"{sorted(FUNCTIONS)}")
    return FUNCTIONS[name](cfg)


def device_peaks(peaks, device_kind) -> dict:
    """The row of the peaks table for ``device_kind``; a device that is
    not in the table is an error, not a default."""
    try:
        return peaks["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(peaks['devices'])}): add its published "
                       "peaks with their source") from None


def least_seconds(flops, nbytes, row, chips=1):
    """``(seconds, bound)``: the least time ``chips`` chips could take for
    this work, and which of the two peaks sets it."""
    t_flops = flops / (chips * row["flops_per_s"])
    t_bytes = nbytes / (chips * row["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def share_pct(least_s, measured_s):
    """Share of the peak: least time over measured time, in percent; None
    when nothing was measured."""
    if not measured_s or measured_s <= 0:
        return None
    return 100.0 * least_s / measured_s
