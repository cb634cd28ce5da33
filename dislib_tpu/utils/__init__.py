"""dislib_tpu.utils — shuffle/split, model saving, checkpointing, profiling.

`shuffle`/`train_test_split`/`save_model`/`load_model` resolve lazily
(PEP 562): their home modules import `dislib_tpu.data.array`, while
`data/array.py` itself imports `dislib_tpu.utils.profiling` for the
dispatch counters — an eager import here would close that cycle mid-way
through the array module's initialisation.
"""

from dislib_tpu.utils.checkpoint import FitCheckpoint
from dislib_tpu.utils.profiling import (
    annotate, counters, dispatch_count, memory_stats, op_graph,
    profiled_jit, reset_counters, span, span_totals, trace, trace_count,
)

_LAZY_ATTRS = {
    "shuffle": "dislib_tpu.utils.base",
    "train_test_split": "dislib_tpu.utils.base",
    "save_model": "dislib_tpu.utils.saving",
    "load_model": "dislib_tpu.utils.saving",
}


def __getattr__(name):
    mod = _LAZY_ATTRS.get(name)
    if mod is None:
        raise AttributeError(
            f"module 'dislib_tpu.utils' has no attribute {name!r}")
    import importlib
    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value
    return value


__all__ = ["shuffle", "train_test_split", "save_model", "load_model",
           "FitCheckpoint",
           "trace", "annotate", "span", "span_totals", "op_graph",
           "memory_stats",
           "profiled_jit", "dispatch_count", "trace_count", "counters",
           "reset_counters"]
