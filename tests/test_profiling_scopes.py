"""The catalogue of compiled programs and ``profiling.program_scopes()``
(utils/profiling.py): which ``dslib.`` scopes each compiled instruction
lies under, kept by the signature of the call that compiled it.

What the catalogue holds is checked directly; what ``program_scopes()``
gives is checked on small programs written here, on the library's four
scoped programs at tiny sizes, and against a real profiler capture on the
CPU: every device-op event of a library program is a key of its row, which
is what lets a reader join a capture to the scopes by name."""

import glob
import importlib
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import dislib_tpu as ds
from dislib_tpu.ops import base as _ops
from dislib_tpu.parallel import mesh as _mesh
from dislib_tpu.utils import profiling


@pytest.fixture(autouse=True)
def _empty_catalogue():
    profiling.clear_programs()
    profiling.reset_counters()
    yield
    profiling.clear_programs()


def _scoped(label):
    """A fresh profiled program with nested scopes, an unscoped tail and a
    loop, under its own label (so that its traces are its own)."""

    @profiling.profiled_jit(name=label)
    def f(x, y):
        with jax.named_scope("dslib.a.outer"):
            with jax.named_scope("dslib.a.sq"):
                s = jnp.sum(x * x, axis=1)
            with jax.named_scope("dslib.pdot"):
                z = x @ y

        def body(_, c):
            with jax.named_scope("dslib.a.loop"):
                return c * 1.01 + z

        return lax.fori_loop(0, 5, body, z) + s[:, None]

    return f


def _signatures(label):
    return [(tree, sig) for (lab, tree, sig) in profiling._PROGRAMS
            if lab == label]


# -- what dispatch records ------------------------------------------------------

def test_one_row_a_compilation_none_a_further_dispatch_one_a_new_shape():
    f = _scoped("cat_rows")
    x, y = jnp.ones((16, 8)), jnp.ones((8, 4))
    for _ in range(3):
        f(x, y)
    assert len(_signatures("cat_rows")) == 1
    assert profiling.counters()["dispatch_by"]["cat_rows"] == 3
    f(jnp.ones((32, 8)), y)
    assert len(_signatures("cat_rows")) == 2
    assert [r["program"] for r in profiling.program_scopes("cat_rows")] \
        == ["cat_rows"] * 2
    assert profiling.program_scopes("no_such_label") == []


def test_a_dispatch_that_compiled_nothing_runs_the_compare_alone(monkeypatch):
    f = _scoped("cat_compare")
    x, y = jnp.ones((16, 8)), jnp.ones((8, 4))
    calls = []
    real = profiling._remember_program
    monkeypatch.setattr(profiling, "_remember_program",
                        lambda *a: (calls.append(a[0]), real(*a)))
    f(x, y)
    assert calls == ["cat_compare"]
    for _ in range(5):
        f(x, y)
    assert calls == ["cat_compare"]


def test_a_call_under_an_outer_trace_records_nothing():
    f = _scoped("cat_inner")
    outer = profiling.profiled_jit(lambda x, y: f(x, y) * 2, name="cat_outer")
    outer(jnp.ones((16, 8)), jnp.ones((8, 4)))
    assert profiling.counters()["trace_by"]["cat_inner"] == 1
    assert _signatures("cat_inner") == []
    assert len(_signatures("cat_outer")) == 1
    # the inner program's ops are the outer program's
    (row,) = profiling.program_scopes("cat_outer")
    assert "dslib.a.outer/dslib.a.sq" in row["scopes"].values()


def test_reset_counters_keeps_the_catalogue_and_clear_programs_empties_it():
    f = _scoped("cat_reset")
    f(jnp.ones((16, 8)), jnp.ones((8, 4)))
    profiling.reset_counters()
    assert len(_signatures("cat_reset")) == 1
    assert len(profiling.program_scopes("cat_reset")) == 1
    profiling.clear_programs()
    assert profiling.program_scopes() == []


def test_the_catalogue_is_bounded_and_the_oldest_signature_goes_first(
        monkeypatch):
    monkeypatch.setattr(profiling, "_MAX_PROGRAMS", 2)
    f = _scoped("cat_bound")
    for rows in (8, 16, 24):
        f(jnp.ones((rows, 8)), jnp.ones((8, 4)))
    assert [sig[0].shape for _, sig in _signatures("cat_bound")] \
        == [(16, 8), (24, 8)]


def test_the_catalogue_holds_shapes_and_statics_and_no_buffer():
    @profiling.profiled_jit(name="cat_static", static_argnames=("k",))
    def g(x, k, scale):
        return x[:k] * scale

    g(jnp.ones((16, 8)), k=4, scale=2.0)
    g(np.ones((32, 8), np.float32), k=4, scale=2.0)
    leaves = [leaf for _, sig in _signatures("cat_static") for leaf in sig]
    assert not any(isinstance(a, (jax.Array, np.ndarray)) for a in leaves)
    assert sum(isinstance(a, jax.ShapeDtypeStruct) for a in leaves) == 2
    assert leaves.count(4) == 2 and leaves.count(2.0) == 2
    assert len(profiling.program_scopes("cat_static")) == 2


def test_a_donated_argument_leaves_its_signature():
    @profiling.profiled_jit(name="cat_donate", donate_argnames=("x",))
    def g(x, y):
        return x + y

    x = jax.device_put(jnp.ones((8, 128)), jax.devices()[0])
    g(x, jnp.ones((8, 128)))
    (row,) = profiling.program_scopes("cat_donate")
    assert row["argument_bytes"] == 2 * 8 * 128 * 4


def test_the_shardings_of_a_2x2_mesh_survive_in_the_signature():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for the 2x2 mesh")
    ds.init((2, 2), devices=jax.devices()[:4])
    mesh = _mesh.get_mesh()
    rows, cols = mesh.axis_names
    x = jax.device_put(jnp.ones((16, 8)), NamedSharding(mesh, P(rows, cols)))
    y = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P(cols, None)))
    f = _scoped("cat_mesh")
    f(x, y)
    ((_, sig),) = _signatures("cat_mesh")
    assert [a.sharding for a in sig] == [x.sharding, y.sharding]
    # and a leaf nobody placed carries none: it lowers as it was called
    f(x, jnp.ones((8, 4)))
    assert [a.sharding for _, sig in _signatures("cat_mesh")
            for a in sig][2:] == [x.sharding, None]
    assert len(profiling.program_scopes("cat_mesh")) == 2


# -- what program_scopes() gives --------------------------------------------------

def test_program_scopes_dispatches_nothing_and_compiles_once():
    f = _scoped("cat_once")
    f(jnp.ones((16, 8)), jnp.ones((8, 4)))
    before = profiling.counters()
    first = profiling.program_scopes("cat_once")
    for entry in profiling._PROGRAMS.values():
        entry[0] = None         # a second lowering would have nothing to call
    second = profiling.program_scopes("cat_once")
    after = profiling.counters()
    assert after["dispatches"] == before["dispatches"]
    assert after["transfers"] == before["transfers"]
    # the lowering is AOT access: it may count a trace, once
    assert after["trace_by"]["cat_once"] - before["trace_by"]["cat_once"] \
        in (0, 1)
    assert second[0] is first[0]                       # the row is kept
    assert set(first[0]) == {"program", "scopes", "temp_bytes",
                             "argument_bytes", "output_bytes"}
    assert first[0]["argument_bytes"] == (16 * 8 + 8 * 4) * 4
    assert first[0]["output_bytes"] == 16 * 4 * 4
    assert first[0]["temp_bytes"] >= 0


def test_the_chain_is_outer_to_inner_a_loop_body_is_mapped_the_rest_empty():
    f = _scoped("cat_chain")
    f(jnp.ones((256, 128)), jnp.ones((128, 64)))
    (row,) = profiling.program_scopes("cat_chain")
    chains = set(row["scopes"].values())
    assert {"dslib.a.outer/dslib.a.sq", "dslib.a.outer/dslib.pdot",
            "dslib.a.loop", ""} <= chains
    assert "dslib.a.sq/dslib.a.outer" not in chains
    by_chain = {}
    for inst, chain in row["scopes"].items():
        by_chain.setdefault(chain, []).append(inst)
    assert any(i.startswith("dot") for i in
               by_chain["dslib.a.outer/dslib.pdot"])
    # the loop itself and its counter lie under no scope
    assert any(i.startswith("while") for i in by_chain[""])


TEXT = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/jit(main)/dslib.x.outer/jit(g)/dslib.x.inner/mul" source_file="a.py" source_line=5}
}

fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  param_0.1 = f32[8]{0} parameter(0)
  ROOT add.3 = f32[8]{0} add(param_0.1, param_0.1), metadata={op_name="jit(f)/jit(main)/add"}
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8,2] {
  %buffer.1 = f32[8,2]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %param_0.2 = f32[8]{0} parameter(0)
  %bitcast.8 = f32[8,1]{1,0} bitcast(%param_0.2), metadata={op_name="jit(f)/dslib.x.pack/concatenate"}
  ROOT %dynamic-update-slice.2 = f32[8,2]{1,0} dynamic-update-slice(%buffer.1, %bitcast.8)
}

%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  %sine.1 = f32[8]{0} sine(%param_0.3), metadata={op_name="jit(f)/dslib.x.pack/sin"}
  %cosine.1 = f32[8]{0} cosine(%param_0.3), metadata={op_name="jit(f)/dslib.x.other/cos"}
  ROOT %add.9 = f32[8]{0} add(%sine.1, %cosine.1)
}

ENTRY %main.5 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation
  fusion.1 = f32[8]{0} fusion(%fusion), kind=kLoop, calls=fused_computation.1
  %fusion.2 = f32[8,2]{1,0} fusion(%fusion), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.3
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.1)
  ROOT %custom-call.7 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/dslib.x.step/pallas_call"}
}
"""


@pytest.mark.parametrize("inst,chain", [
    ("multiply.1", "dslib.x.outer/dslib.x.inner"),
    ("fusion", "dslib.x.outer/dslib.x.inner"),   # no metadata: its root's
    ("fusion.1", ""),                            # its root has no scope
    # its root has no metadata: what all it holds agree on, or nothing
    ("fusion.2", "dslib.x.pack"), ("fusion.3", ""),
    ("add.3", ""), ("Arg_0.1", ""), ("copy-start.2", ""),
    ("custom-call.7", "dslib.x.step")])
def test_the_text_is_read_with_and_without_percent_signs(inst, chain):
    assert profiling._scopes_of(TEXT)[inst] == chain


def test_a_signature_lowers_to_the_text_its_arguments_lower_to():
    """The abstract signature stands for the call: same module, so the same
    key in the persistent cache."""
    f = _scoped("cat_same")
    args = (jax.device_put(jnp.ones((16, 8)), jax.devices()[0]),
            jnp.asarray(np.ones((8, 4), np.float32)))
    f(*args)
    ((tree, sig),) = _signatures("cat_same")
    abstract, kwargs = jax.tree_util.tree_unflatten(tree, sig)
    assert f.lower(*abstract, **kwargs).as_text() == f.lower(*args).as_text()


def test_an_executable_compiled_under_other_scope_names_is_called_out(
        monkeypatch):
    """The persistent cache's key holds no scope name: what it hands back
    may name the scopes of an older tree (as the text is made to here)."""
    f = _scoped("cat_stale")
    f(jnp.ones((16, 8)), jnp.ones((8, 4)))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        profiling.program_scopes("cat_stale")       # this tree's: silent
    profiling.clear_programs()
    f(jnp.ones((32, 8)), jnp.ones((8, 4)))
    real = profiling._scopes_of
    monkeypatch.setattr(profiling, "_scopes_of", lambda text: real(
        text.replace("dslib.a.loop", "dslib.a.old_loop")))
    with pytest.warns(UserWarning, match=r"dslib\.a\.old_loop"):
        (row,) = profiling.program_scopes("cat_stale")
    assert "dslib.a.old_loop" in row["scopes"].values()


# -- the library's programs -------------------------------------------------------

def _kmeans():
    from dislib_tpu.cluster import KMeans
    x = ds.random_array((400, 6), random_state=0).force()
    start = np.random.RandomState(0).rand(3, 6).astype(np.float32)
    return lambda: KMeans(n_clusters=3, init=start, max_iter=5,
                          tol=0.0).fit(x)


def _mixture():
    from dislib_tpu.cluster import GaussianMixture
    x = ds.random_array((400, 6), random_state=0).force()
    return lambda: GaussianMixture(n_components=3, max_iter=3,
                                   random_state=0).fit(x)


def _rsvd():
    a = ds.random_array((4096, 64), random_state=1).force()

    def call():
        u, _, _ = ds.random_svd(a, iters=2, nsv=6, oversample=2,
                                random_state=0)
        u.block_until_ready()
    return call


def _summa():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for the 2x2 mesh")
    ds.init((2, 2), devices=jax.devices()[:4])
    a = ds.random_array((256, 256), random_state=3).force()
    b = ds.random_array((256, 256), random_state=4).force()
    return lambda: ds.matmul(a, b, algorithm="summa").block_until_ready()


# label -> (the call, the chains its row has to hold, where its jit lives)
PROGRAMS = {
    "kmeans_fit": (_kmeans, "dislib_tpu.cluster.kmeans._kmeans_fit", [
        r"^dslib\.kmeans\.norms$", r"^dslib\.kmeans\.(assign|step)",
        r"^dslib\.kmeans\.update$"]),
    "gm_fit": (_mixture, "dislib_tpu.cluster.gm._gm_fit", [
        r"^dslib\.gm\.pass$", r"^dslib\.gm\.pass/dslib\.gm\.e_step",
        r"^dslib\.gm\.pass/dslib\.gm\.m_step", r"^dslib\.gm\.chol$",
        r"^dslib\.gm\.close$",
        r"^dslib\.gm\.pass/dslib\.gm\.[em]_step/dslib\.pdot$"]),
    "random_svd": (
        _rsvd, "dislib_tpu.decomposition.randomsvd._random_svd_fused", [
        r"^dslib\.rsvd\.sketch/dslib\.tsqr\.gram",
        r"^dslib\.rsvd\.sketch/dslib\.tsqr\.chol",
        r"^dslib\.rsvd\.sketch/dslib\.tsqr\.apply/dslib\.pdot$",
        r"^dslib\.rsvd\.power/dslib\.tsqr\.gram",
        r"^dslib\.rsvd\.power/dslib\.tsqr\.chol",
        r"^dslib\.rsvd\.power/dslib\.tsqr\.apply/dslib\.pdot$",
        r"^dslib\.rsvd\.power/dslib\.pdot$", r"^dslib\.rsvd\.project",
        r"^dslib\.rsvd\.small_svd$", r"^dslib\.rsvd\.lift/dslib\.pdot$"]),
    "summa_matmul": (_summa, "dislib_tpu.ops.summa.summa_matmul", [
        r"^dslib\.summa\.fetch_a$", r"^dslib\.summa\.fetch_b$",
        r"^dslib\.summa\.gemm/dslib\.pdot$"]),
}


@pytest.fixture
def cholqr(monkeypatch):
    """CholeskyQR2 over several blocks, the TPU's route, on the CPU."""
    monkeypatch.setenv("DSLIB_TSQR_CHOLQR", "1")
    monkeypatch.setattr(_ops, "_EM_TILE_BYTES", 512 * 4 * 4 * 8)


def _device_events(logdir):
    """``{hlo module: {op names}}`` of the capture's device-op events."""
    from jax.profiler import ProfileData
    path = glob.glob(str(logdir) + "/plugins/profile/*/*.xplane.pb")[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" in stats:
                    out.setdefault(stats["hlo_module"], set()).add(ev.name)
    return out


@pytest.mark.parametrize("label", list(PROGRAMS))
def test_a_library_program_maps_its_scopes_and_a_capture_finds_them(
        label, cholqr, tmp_path):
    make, home, wanted = PROGRAMS[label]
    call = make()
    call()                                  # compiles, and is recorded
    (row,) = profiling.program_scopes(label)
    chains = set(row["scopes"].values())
    for pattern in wanted:
        assert any(re.search(pattern, c) for c in chains), \
            (pattern, sorted(chains))
    # soundness: in a real capture of the call, every device-op event of
    # the library's program is a key of its row (the names of the text
    # ARE the capture's)
    where, attr = home.rsplit(".", 1)
    module = "jit_" + getattr(importlib.import_module(where),
                              attr).__wrapped__.__name__
    with profiling.trace(str(tmp_path)):
        call()
    events = _device_events(tmp_path)
    assert events.get(module), sorted(events)
    assert events[module] <= set(row["scopes"])
    scoped = {n for n in events[module] if row["scopes"][n]}
    assert scoped, "no scoped op ran in the capture"
