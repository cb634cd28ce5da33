"""The span primitive and the library's own spans and scopes
(utils/profiling.py; PERF.md section 3 holds the vocabulary).

The tally is checked directly; names, nesting and stats are read from a
real profiler capture on the CPU, the store the benchmark's readers use."""

import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.cluster import KMeans
from dislib_tpu.utils import profiling
from dislib_tpu.utils.profiling import span

FIT_VOCABULARY = {
    "dslib.kmeans.fit", "dslib.kmeans.init_centers", "dslib.fitloop.run",
    "dslib.fitloop.chunk", "dslib.fitloop.wait", "dslib.fitloop.commit",
    "dslib.host_read"}
PRODUCT_VOCABULARY = {"dslib.matmul", "dslib.array.force",
                      "dslib.array.wait"}


# -- the primitive ------------------------------------------------------------

def test_span_tallies_count_total_max_and_reset_clears():
    profiling.reset_counters()
    for _ in range(3):
        with span("dslib.test.phase", it=1):
            pass
    row = profiling.span_totals()["dslib.test.phase"]
    assert row["count"] == 3
    assert 0 <= row["max_s"] <= row["total_s"] <= 3 * row["max_s"]
    assert profiling.counters()["spans"]["dslib.test.phase"]["count"] == 3
    profiling.reset_counters()
    assert profiling.span_totals() == {}


def _nested():
    with span("dslib.test.outer"):
        with span("dslib.test.inner"):
            pass
        with span("dslib.test.inner"):
            pass


def _raising():
    with pytest.raises(KeyError):
        with span("dslib.test.outer"):
            with span("dslib.test.inner"):
                raise KeyError("inside")
    with span("dslib.test.inner"):      # the primitive still works after
        pass


@pytest.mark.parametrize("body", [_nested, _raising])
def test_nesting_and_an_exception_leave_the_tally_right(body):
    profiling.reset_counters()
    body()
    rows = profiling.span_totals()
    assert rows["dslib.test.outer"]["count"] == 1
    assert rows["dslib.test.inner"]["count"] == 2
    assert rows["dslib.test.outer"]["total_s"] >= 0


def test_new_call_draws_a_fresh_id_each_time():
    first, second = profiling.new_call(), profiling.new_call()
    assert isinstance(first, int) and second > first


def test_annotate_is_span_and_scope():
    profiling.reset_counters()

    def f(a):
        with profiling.annotate("dslib.test.both"):
            return a @ a

    text = profiling.op_graph(f, jnp.ones((8, 8)))
    assert "dslib.test.both" in text
    assert profiling.span_totals()["dslib.test.both"]["count"] == 1


# -- the library's spans, from one capture -------------------------------------

def _host_events(logdir):
    """``[(name, start, end, stats)]`` of each thread's line of the host
    plane (two threads may share a line's name)."""
    from jax.profiler import ProfileData
    path = glob.glob(str(logdir) + "/plugins/profile/*/*.xplane.pb")[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            rows = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)) for ev in line.events
                    if ev.name.startswith(("dslib.", "test."))]
            if rows:
                out.append(rows)
    return out


def _operands(n, seed):
    a = ds.random_array((n, n), random_state=seed).force()
    b = ds.random_array((n, n), random_state=seed + 1).force()
    return a, b


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One capture of: a fit with an explicit start (the benchmark's
    call), the same fit with the chunk watchdog on (its read runs on
    another thread), a product on one device, a product on a 2x2 mesh.
    Each path is run once before the capture so that nothing compiles in
    it, and its counters are read right after it."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for the 2x2 mesh")
    from dislib_tpu.runtime.health import HealthPolicy
    rng = np.random.RandomState(0)
    start = rng.rand(3, 6).astype(np.float32)
    counts = {}

    def fit(x, **kw):
        return KMeans(n_clusters=3, init=start, max_iter=5, tol=0.0) \
            .fit(x, **kw)

    def product(a, b):
        return ds.matmul(a, b).block_until_ready()

    def measured(path, fn, *args, **kw):
        fn(*args, **kw)                 # compiles
        profiling.reset_counters()
        with span("test." + path):
            fn(*args, **kw)
        counts[path] = profiling.counters()

    logdir = tmp_path_factory.mktemp("spans")
    with profiling.trace(str(logdir)):
        ds.init()
        x = ds.random_array((400, 6), random_state=0).force()
        measured("fit", fit, x)
        measured("fit_watchdog", fit, x, health=HealthPolicy(deadline_s=60))
        ds.init((1, 1), devices=jax.devices()[:1])
        measured("product", product, *_operands(256, 1))
        ds.init((2, 2), devices=jax.devices()[:4])
        measured("product_summa", product, *_operands(256, 3))
    ds.init()
    lines = _host_events(logdir)
    main = next(rows for rows in lines
                if any(r[0] == "test.fit" for r in rows))
    others = [r for rows in lines if rows is not main for r in rows]

    def within(path):
        _, s, e, _ = next(r for r in main if r[0] == "test." + path)
        return [r for r in main
                if r[0].startswith("dslib.") and s <= r[1] and r[2] <= e]

    return {"within": within, "others": others, "counts": counts}


def _one(rows, name):
    got = [r for r in rows if r[0] == name]
    assert len(got) == 1, (name, [r[0] for r in rows])
    return got[0]


@pytest.mark.parametrize("path,vocabulary", [
    ("fit", FIT_VOCABULARY), ("product", PRODUCT_VOCABULARY),
    ("product_summa", PRODUCT_VOCABULARY - {"dslib.array.force"})])
def test_a_path_records_exactly_its_vocabulary(recorded, path, vocabulary):
    rows = recorded["within"](path)
    assert {r[0] for r in rows} == vocabulary
    assert set(recorded["counts"][path]["spans"]) \
        == vocabulary | {"test." + path}


def test_fit_spans_nest_by_layer_and_count_every_host_read(recorded):
    rows = recorded["within"]("fit")
    fit = _one(rows, "dslib.kmeans.fit")
    run = _one(rows, "dslib.fitloop.run")
    chunk = _one(rows, "dslib.fitloop.chunk")
    wait = _one(rows, "dslib.fitloop.wait")
    commit = _one(rows, "dslib.fitloop.commit")
    init = _one(rows, "dslib.kmeans.init_centers")
    for outer, inner in [(fit, run), (run, init), (run, chunk),
                         (chunk, wait), (run, commit)]:
        assert outer[1] <= inner[1] and inner[2] <= outer[2], (outer, inner)
    assert chunk[2] <= commit[1]
    assert isinstance(fit[3].get("call"), int)
    assert chunk[3].get("it") == 0
    reads = [r for r in rows if r[0] == "dslib.host_read"]
    counts = recorded["counts"]["fit"]
    assert len(reads) == counts["transfers"] == 3
    assert counts["spans"]["dslib.host_read"]["count"] == 3
    assert all(fit[1] <= r[1] and r[2] <= fit[2] for r in reads)


def test_watchdog_thread_read_lies_inside_the_callers_wait(recorded):
    # the watched read runs on the watchdog's thread: its span is on that
    # thread's line, inside the caller's wait, and counted like the others
    rows = recorded["within"]("fit_watchdog")
    wait = _one(rows, "dslib.fitloop.wait")
    on_main = [r for r in rows if r[0] == "dslib.host_read"]
    moved = [r for r in recorded["others"] if r[0] == "dslib.host_read"
             and wait[1] <= r[1] and r[2] <= wait[2]]
    assert len(on_main) == 2 and len(moved) == 1
    assert recorded["counts"]["fit_watchdog"]["transfers"] == 3


@pytest.mark.parametrize("path,route", [("product", "xla"),
                                        ("product_summa", "summa")])
def test_product_span_names_its_route(recorded, path, route):
    rows = recorded["within"](path)
    entry = _one(rows, "dslib.matmul")
    assert entry[3].get("route") == route
    assert isinstance(entry[3].get("call"), int)
    wait = _one(rows, "dslib.array.wait")
    assert entry[2] <= wait[1]
    if route == "xla":                  # the force runs between the two
        force = _one(rows, "dslib.array.force")
        assert entry[2] <= force[1] and force[2] <= wait[1]


@pytest.mark.parametrize("path", ["fit", "fit_watchdog", "product",
                                  "product_summa"])
def test_spans_add_no_dispatch(recorded, path):
    assert recorded["counts"][path]["dispatches"] == 1
    assert recorded["counts"][path]["traces"] == 0


# -- the device scopes, in the compiled text ------------------------------------

@functools.lru_cache(maxsize=None)
def _compiled_text(path):
    from dislib_tpu.cluster import kmeans as km
    from dislib_tpu.ops import precision as px
    from dislib_tpu.ops.summa import summa_matmul
    from dislib_tpu.parallel import mesh as _mesh
    if path == "kmeans_fit":
        x = ds.random_array((64, 6), random_state=0)
        return profiling.op_graph(
            lambda xp, c: km._kmeans_fit(xp, x.shape, c, 3, 0.0),
            x._data, jnp.ones((3, 6), jnp.float32))
    if path == "kmeans_fit_sparse":
        import scipy.sparse as sp
        from dislib_tpu.data.sparse import SparseArray
        xs = SparseArray.from_scipy(sp.random(
            64, 6, density=0.3, format="csr", dtype=np.float32,
            random_state=0))
        mesh = _mesh.get_mesh()
        return profiling.op_graph(
            lambda d, lr, cc, rsq, c: km._kmeans_fit_sparse_sharded(
                d, lr, cc, rsq, c, 64, 3, 0.0, mesh),
            *xs.sharded_rows(), jnp.ones((3, 6), jnp.float32))
    assert path == "summa"
    ds.init((2, 2), devices=jax.devices()[:4])
    a, b = _operands(64, 5)
    mesh = _mesh.get_mesh()
    return profiling.op_graph(
        lambda ad, bd: summa_matmul(ad, bd, mesh, px.FLOAT32), a._data,
        b._data)


@pytest.mark.parametrize("path,scope", [
    ("kmeans_fit", "dslib.kmeans.norms"),
    ("kmeans_fit", "dslib.kmeans.assign"),
    ("kmeans_fit", "dslib.kmeans.update"),
    ("kmeans_fit_sparse", "dslib.kmeans.assign"),
    ("kmeans_fit_sparse", "dslib.kmeans.update"),
    ("summa", "dslib.summa.fetch_a"),
    ("summa", "dslib.summa.fetch_b"),
    ("summa", "dslib.summa.gemm"),
    ("summa", "dslib.pdot")])
def test_device_scope_is_in_an_op_name(path, scope):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for the 2x2 mesh")
    text = _compiled_text(path)
    assert f'/{scope}/' in text and 'op_name="' in text, scope
