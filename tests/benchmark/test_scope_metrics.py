"""The reader of device time by the program's own scopes
(``benchmark/readers/scope_time.py``), on hand-made traces with a planted
catalogue and hand-worked answers, and the six metrics that use it, end to
end in a traced rehearsal of their two cells."""

import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402
from benchmark import reduce_trace as rt  # noqa: E402
from benchmark.readers import scope_time  # noqa: E402

MS = 1_000_000          # ns

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)

# the one list of the scope metrics is the scratch manifest's
_spec = importlib.util.spec_from_file_location(
    "scope_manifest", os.path.join(ROOT, "tools", "scope_manifest.py"))
tool = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tool)

# name -> (cell, layer) of the six whose files are in benchmark/metrics/
SCOPE_METRICS = {name: (cell, layer)
                 for name, _, layer, cell, params in tool.ENTRIES
                 if params is None}

# two programs: a randomized SVD and something else that shares two names
# with it, one placed alike and one placed differently
ROWS = [
    {"program": "random_svd", "scopes": {
        "fusion.1": "dslib.rsvd.sketch/dslib.pdot",
        "fusion.2": "dslib.rsvd.sketch/dslib.tsqr.gram/dslib.pdot",
        "fusion.3": "dslib.rsvd.power/dslib.pdot",
        "custom-call.7": "dslib.rsvd.small_svd",
        "copy.4": "",
        "fusion.9": "dslib.rsvd.lift/dslib.pdot",
        "add.5": "dslib.rsvd.lift"}},
    {"program": "other", "scopes": {
        "fusion.9": "dslib.other.phase",         # collides
        "add.5": "dslib.rsvd.lift",              # the same chain: no collision
        "fusion.77": "dslib.other.phase"}}]


@pytest.fixture
def planted(monkeypatch):
    from dislib_tpu.utils import profiling
    monkeypatch.setattr(profiling, "program_scopes", lambda: ROWS,
                        raising=False)


def _ctx(ops, calls=2, units=4, async_ops=None):
    tr = rt.Trace(t0=0, t1=100 * MS, ops=ops, async_ops=async_ops or {})
    return SimpleNamespace(trace_data=tr, calls=calls, units=units)


def _ms(scope, per="call", **extra):
    return {"scope": scope, "per": per, "stat": "ms", **extra}


ONE_CHIP = {0: [("fusion.1", 0, 10 * MS), ("fusion.2", 10 * MS, 4 * MS),
                ("fusion.3", 20 * MS, 6 * MS), ("fusion.3", 30 * MS, 6 * MS),
                ("custom-call.7", 40 * MS, 2 * MS), ("copy.4", 50 * MS, 1 * MS),
                ("fusion.9", 60 * MS, 8 * MS), ("add.5", 70 * MS, 3 * MS),
                ("mystery.1", 80 * MS, 5 * MS)]}


@pytest.mark.parametrize("params,ms", [
    # searched in the whole chain: the GEMMs' innermost scope is dslib.pdot
    (_ms(r"dslib\.rsvd\.sketch"), (10 + 4) / 2),
    (_ms(r"dslib\.tsqr\.gram"), 4 / 2),
    (_ms(r"dslib\.rsvd\.(sketch|power|project)"), (10 + 4 + 6 + 6) / 2),
    # `not` takes the orthonormalisation out of the products with A
    (_ms(r"dslib\.rsvd\.(sketch|power|project)",
         **{"not": r"dslib\.tsqr\."}), (10 + 6 + 6) / 2),
    (_ms(r"dslib\.rsvd\.power", per="unit"), (6 + 6) / 4),
    (_ms(r"dslib\.rsvd\.small_svd$"), 2 / 2),
    # fusion.9 collides and counts toward no scope; add.5 does not
    (_ms(r"dslib\.rsvd\.lift"), 3 / 2),
    (_ms(r"dslib\.pdot$"), (10 + 4 + 6 + 6) / 2)])
def test_time_by_scope_per_call_and_per_unit(planted, params, ms):
    assert scope_time.read(_ctx(ONE_CHIP), params) == pytest.approx(ms)


def test_unscoped_share_holds_the_empty_the_unknown_and_the_colliding(planted):
    # copy.4 (empty chain) 1, mystery.1 (no row knows it) 5, fusion.9
    # (two rows, two chains) 8, of 45 ms busy
    got = scope_time.read(_ctx(ONE_CHIP), {"stat": "unscoped_pct"})
    assert got == pytest.approx(100.0 * (1 + 5 + 8) / 45)
    # a program whose every op is scoped reads 0.0, not nothing
    only = {0: [("fusion.1", 0, 10 * MS)]}
    assert scope_time.read(_ctx(only), {"stat": "unscoped_pct"}) == 0.0


def test_two_chips_are_averaged_and_an_op_is_cut_at_the_windows_edge(planted):
    ops = {0: [("fusion.1", -5 * MS, 10 * MS),       # 5 ms inside
               ("fusion.1", 95 * MS, 10 * MS),       # 5 ms inside
               ("fusion.1", 200 * MS, 10 * MS),      # outside
               ("copy.4", 20 * MS, 10 * MS)],
           1: [("fusion.1", 10 * MS, 30 * MS)]}
    got = scope_time.read(_ctx(ops), _ms(r"dslib\.rsvd\.sketch"))
    assert got == pytest.approx((10 + 30) / 2 / 2)
    # unscoped: 10 ms of chip 0 and nothing of chip 1, over the mean busy
    # time (20 and 30 ms)
    share = scope_time.read(_ctx(ops), {"stat": "unscoped_pct"})
    assert share == pytest.approx(100.0 * (10 / 2) / 25)


def test_the_async_line_is_in_neither_part_of_the_share(planted):
    # both stats read the op line alone: an async op counts toward no
    # scope, and is neither unscoped time nor busy time
    ops = {0: [("fusion.1", 0, 10 * MS), ("copy.4", 10 * MS, 10 * MS)]}
    asyn = {0: [("fusion.3", 50 * MS, 20 * MS), ("mystery.1", 80 * MS, MS)]}
    ctx = _ctx(ops, async_ops=asyn)
    assert scope_time.read(ctx, _ms(r"dslib\.rsvd\.power")) is None
    assert scope_time.read(ctx, {"stat": "unscoped_pct"}) \
        == pytest.approx(100.0 * 10 / 20)


@pytest.mark.parametrize("ctx,params", [
    (_ctx(ONE_CHIP), _ms(r"dslib\.kmeans\.step")),           # no op matches
    (_ctx({}), _ms(r"dslib\.rsvd\.sketch")),                 # no device ops
    (_ctx(ONE_CHIP, calls=0, units=0), _ms(r"dslib\.rsvd\.sketch")),
    (SimpleNamespace(trace_data=None, calls=2, units=4),
     {"stat": "unscoped_pct"})])                             # not traced
def test_nothing_to_read_is_nothing_and_never_zero(planted, ctx, params):
    assert scope_time.read(ctx, params) is None


def test_a_wrong_stat_or_per_is_an_error_not_a_number(planted):
    with pytest.raises(ValueError):
        scope_time.read(_ctx(ONE_CHIP), _ms(r"dslib", stat="seconds"))
    with pytest.raises(KeyError):
        scope_time.read(_ctx(ONE_CHIP), _ms(r"dslib", per="fit"))


def test_the_catalogue_is_merged_from_the_real_program():
    """Not planted: a program run here, read through the real accessor."""
    import jax
    import jax.numpy as jnp
    from dislib_tpu.utils import profiling

    @profiling.profiled_jit(name="scope_metrics_probe")
    def f(x):
        with jax.named_scope("dslib.probe.phase"):
            return jnp.sin(x) * 2

    profiling.clear_programs()
    f(jnp.ones((8, 128)))
    chains = scope_time.scope_chains()
    profiling.clear_programs()
    assert "dslib.probe.phase" in chains.values()


# -- the metrics ------------------------------------------------------------------
#
# Whether BENCHMARK.json lists them is not this file's to say (PERF.md
# section 7): the metric files are in the tree, and where an entry is not,
# the scratch manifest appends it to a copy.

def _root_that_lists(names, tmp_path):
    have = {m["name"] for m in BENCH["per_layer"]}
    if set(names) <= have:
        return ROOT
    dst = str(tmp_path / "scope")
    assert tool.build(dst) == []
    return dst


def test_the_six_metrics_are_files_and_entries_only(tmp_path):
    assert manifest.problems(ROOT) == []
    man = manifest.Manifest(_root_that_lists(SCOPE_METRICS, tmp_path))
    assert len(SCOPE_METRICS) == 6
    for name, (cell, layer) in SCOPE_METRICS.items():
        spec = man.metric_file(name)
        assert set(spec) == {"reader", "params", "what"}
        assert spec["reader"] == "scope_time"
        entry = next(m for m in man.per_layer_of(cell) if m["name"] == name)
        assert entry["workloads"] == [cell] and entry["layer"] == layer
        assert entry["source"] == "device_trace"
    # the cells whose metric sets are pinned read none of them
    for cell in ("matmul_1chip_steady", "gmm_fit_sustained",
                 "rsvd_fit_sustained"):
        assert not set(SCOPE_METRICS) & {
            m["name"] for m in man.per_layer_of(cell)}


def test_the_scratch_manifest_lists_every_entry_once_and_in_every_cell(
        tmp_path):
    dst = str(tmp_path / "scope")
    assert tool.build(dst) == []
    with open(os.path.join(dst, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    # what the tree lists stays as it is, and the copy adds only what the
    # tree lacks: built again over itself it adds nothing
    assert bench["per_layer"][:len(BENCH["per_layer"])] == BENCH["per_layer"]
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    assert {e[0] for e in tool.ENTRIES} <= set(names)
    assert tool.missing(bench) == []
    assert {cell for _, _, _, cell, _ in tool.ENTRIES} \
        == {w["name"] for w in BENCH["workloads"]}
    for name, *_ in tool.ENTRIES:
        with open(os.path.join(dst, "benchmark", "metrics", name + ".json"),
                  encoding="utf-8") as f:
            assert json.load(f)["reader"] == "scope_time"
    # an earlier copy is replaced
    assert tool.build(dst) == []


def test_the_scratch_manifest_empties_only_what_it_wrote(tmp_path):
    mine = tmp_path / "checkout"
    (mine / "dislib_tpu").mkdir(parents=True)
    (mine / "dislib_tpu" / "kept.py").write_text("kept")
    with pytest.raises(FileExistsError):
        tool.build(str(mine))
    assert (mine / "dislib_tpu" / "kept.py").read_text() == "kept"
    afile = tmp_path / "a_file"
    afile.write_text("kept")
    with pytest.raises(FileExistsError):
        tool.build(str(afile))
    assert afile.read_text() == "kept"
    (tmp_path / "empty").mkdir()
    assert tool.build(str(tmp_path / "empty")) == []


@pytest.mark.parametrize("cell", ["kmeans_fit_sustained",
                                  "matmul_summa_2x2"])
def test_traced_rehearsal_prints_every_scope_metric_of_the_cell(
        cell, tmp_path):
    mine = [name for name, (c, _) in SCOPE_METRICS.items() if c == cell]
    assert len(mine) == 3
    root = _root_that_lists(mine, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", cell, "--seed", "3800000011", "--seconds", "0.5",
        "--trace", "1", "--rehearsal"]
    done = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    earlier, last = (json.loads(line) for line in
                     done.stdout.strip().splitlines()[-2:])
    assert earlier["silent_metrics"] == []
    for name in mine:
        got = last["metrics"][name]
        assert got["value"] is not None and got["value"] >= 0
    times = [last["metrics"][n]["value"] for n in mine if "_ms_" in n]
    assert all(t > 0 for t in times)
    share = next(last["metrics"][n]["value"] for n in mine if "_pct" in n)
    assert 0 <= share < 100
