"""The reader of device time by the program's own scopes
(``benchmark/readers/scope_time.py``), on hand-made traces with a planted
catalogue and hand-worked answers, and the fifteen metrics that use it,
appended to BENCHMARK.json as ``append_only.py`` has it and read end to
end in a traced rehearsal of their four cells."""

import importlib.util
import json
import os
import re
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

import append_only  # noqa: E402

MS = 1_000_000          # ns

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)

# the one list of the scope metrics is the scratch manifest's
_spec = importlib.util.spec_from_file_location(
    "scope_manifest", os.path.join(ROOT, "tools", "scope_manifest.py"))
tool = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tool)

# name -> (unit, layer, cell, params) of those BENCHMARK.json lists: every
# entry but the mixture's five, whose traced run loses the end of its
# capture (PERF.md section 7); params None where the file came first
SCOPE_METRICS = {name: (unit, layer, cell, params)
                 for name, unit, layer, cell, params in tool.ENTRIES
                 if cell != "gmm_fit_sustained"}
# the six whose files came before their entries
SIX = {name for name, (*_, params) in SCOPE_METRICS.items() if params is None}

# Their place in BENCHMARK.json (append_only.py): every entry that stood
# before them, as it stood, and the fifteen, appended in ENTRIES' order
BEFORE = {
    "configs": (
        ("kmeans_12Mx100_k10", "70eaba93929e"),
        ("matmul_f32_24k", "ab4813e974d3"),
        ("matmul_f32_40k_2x2", "b55a242c266b"),
        ("gmm_24Mx50_k16", "789a7144f48b"),
        ("rsvd_1p5Mx1024_r256", "fc98f459f79c"),
    ),
    "workloads": (
        ("kmeans_fit_sustained", "bc9155cd6ac0"),
        ("matmul_1chip_steady", "b14535d739cb"),
        ("matmul_summa_2x2", "a6fbf2a77e66"),
        ("gmm_fit_sustained", "3271a4473f1b"),
        ("rsvd_fit_sustained", "abd320849f2a"),
    ),
    "end_to_end": (
        ("setup_s", "f4713141c801"),
        ("fit_iters_per_s", "2737eaa986f7"),
        ("matmul_tflops_per_chip", "d5b2d1f5e097"),
    ),
    "per_layer": (
        ("fit.step_mfu_pct", "739d158d9d3a"),
        ("kmeans_step_roofline", "e0495547e4b2"),
        ("fitloop.dispatches_per_iter", "ee9429174b47"),
        ("device.fit_idle_pct", "fbc59fce91c9"),
        ("matmul.step_mfu_pct", "7dfb3d504d4d"),
        ("pdot_roofline", "066684bb1e61"),
        ("array.dispatches_per_product", "50fe29f03dd9"),
        ("summa.collective_exposed_pct", "d2c2e8ecfa02"),
        ("device.matmul_idle_pct", "914b7632fef5"),
        ("kmeans.host_self_ms_per_fit", "e2c25ec5d9e5"),
        ("fitloop.host_self_ms_per_fit", "23b54ae412f5"),
        ("fitloop.host_reads_per_fit", "8c22bcb43f4a"),
        ("fitloop.sync_idle_ms_per_fit", "a3f36ba9188b"),
        ("array.host_self_ms_per_product", "8cab036c97de"),
        ("array.dispatch_idle_ms_per_product", "5c3691d6d400"),
        ("device.wait_idle_ms_per_product", "e09de185545c"),
        ("gmm_step_roofline", "3da3d3f9d86b"),
        ("gm.host_self_ms_per_fit", "a25c217c9005"),
        ("gm.host_reads_per_fit", "8179503905f2"),
        ("gm.sync_idle_ms_per_fit", "ed3ecaa28225"),
        ("rsvd_step_roofline", "736de08c69c3"),
        ("rsvd.host_self_ms_per_call", "062732921155"),
        ("rsvd.host_reads_per_call", "113a676c451a"),
        ("rsvd.sync_idle_ms_per_call", "bf2ac03f168f"),
    ),
}
OWN = {
    "per_layer": (
        ("kmeans.step_device_ms_per_iter", "98907e3279ef"),
        ("kmeans.norms_device_ms_per_fit", "a44ba3c211f7"),
        ("fit.unscoped_device_pct", "5874ad6a3ca2"),
        ("summa.fetch_device_ms_per_product", "d994c8a78f58"),
        ("summa.gemm_device_ms_per_product", "a22c74241124"),
        ("matmul.unscoped_device_pct", "47694a8f881d"),
        ("tsqr.gram_device_ms_per_call", "6f0b0114c31c"),
        ("tsqr.apply_device_ms_per_call", "74fb559e8cbd"),
        ("tsqr.chol_device_ms_per_call", "2d65742f0118"),
        ("rsvd.products_device_ms_per_call", "08efe448f36e"),
        ("rsvd.lift_device_ms_per_call", "f6f3b9c3a6d3"),
        ("rsvd.small_svd_device_ms_per_call", "654c9477503a"),
        ("rsvd.unscoped_device_pct", "9ee1e5e07fb6"),
        ("pdot.device_ms_per_product", "0fe8d9edc339"),
        ("matmul_1chip.unscoped_device_pct", "5707bdd32c09"),
    ),
}

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

def test_the_six_metrics_are_files_and_entries_only():
    assert manifest.problems(ROOT) == []
    man = manifest.Manifest(ROOT)
    assert len(SIX) == 6
    for name in SIX:
        _, layer, cell, _ = SCOPE_METRICS[name]
        spec = man.metric_file(name)
        assert set(spec) == {"reader", "params", "what"}
        assert spec["reader"] == "scope_time"
        entry = next(m for m in man.per_layer_of(cell) if m["name"] == name)
        assert entry["workloads"] == [cell] and entry["layer"] == layer
        assert entry["source"] == "device_trace"


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS))
def test_a_listed_scope_metric_is_its_file_and_its_entry(name):
    """Each entry as ``tool.missing()`` writes it, and each file with the
    reader, the params of ``ENTRIES`` (where the file did not come first)
    and a ``what`` that says which scope it reads."""
    unit, layer, cell, params = SCOPE_METRICS[name]
    man = manifest.Manifest(ROOT)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    rate = next(m["name"] for m in man.end_to_end_of(cell)
                if m["name"] != "setup_s")
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": rate, "workloads": [cell]}
    spec = man.metric_file(name)
    assert set(spec) == {"reader", "params", "what"}
    assert spec["reader"] == "scope_time"
    if params is not None:
        assert spec["params"] == params
    assert len(spec["what"]) > 80
    if spec["params"]["stat"] == "ms":
        assert unit == "ms" and "_ms_per_" in name
        # the pattern's literal head is named in words
        head = re.match(r"[\w.]+", spec["params"]["scope"].replace("\\", ""))
        assert head.group().rstrip(".") in spec["what"]
    else:
        assert unit == "%" and name.endswith("unscoped_device_pct")


def test_the_fifteen_came_by_appending_only():
    assert append_only.problems(BENCH, BEFORE, OWN) == []
    assert [name for name, _ in OWN["per_layer"]] == [
        name for name, *_ in tool.ENTRIES if name in SCOPE_METRICS]
    # the scratch manifest has only the mixture's five left to add
    assert {m["name"] for m in tool.missing(BENCH)} \
        == set(e[0] for e in tool.ENTRIES) - set(SCOPE_METRICS)
    assert len(SCOPE_METRICS) == 15 and len(tool.missing(BENCH)) == 5


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
        <= {w["name"] for w in BENCH["workloads"]}
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


@pytest.mark.parametrize("cell", ["kmeans_fit_sustained", "matmul_summa_2x2",
                                  "rsvd_fit_sustained",
                                  "matmul_1chip_steady"])
def test_traced_rehearsal_prints_every_scope_metric_of_the_cell(cell):
    mine = [name for name, (_, _, c, _) in SCOPE_METRICS.items() if c == cell]
    assert len(mine) == {"kmeans_fit_sustained": 3, "matmul_summa_2x2": 3,
                         "rsvd_fit_sustained": 7,
                         "matmul_1chip_steady": 2}[cell]
    # DSLIB_TSQR_CHOLQR=1 takes the chip's route: on the CPU the randomized
    # SVD's orthonormalisation is the Householder tree, which opens neither
    # dslib.tsqr.gram nor dslib.tsqr.chol
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               DSLIB_TSQR_CHOLQR="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", cell, "--seed", "3800000011", "--seconds", "0.5",
        "--trace", "1", "--rehearsal"]
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
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
