"""The two readers of the program's own spans, on hand-made traces with
hand-worked answers, and the metrics that use them, end to end in a
traced rehearsal of the cells that list them."""

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
from benchmark.readers import idle_in_span, span_time  # noqa: E402

MS = 1_000_000          # ns

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)
SPAN_READERS = ("span_time", "idle_in_span")


def _ctx(host, ops=None, calls=2, units=4):
    tr = rt.Trace(t0=0, t1=100 * MS, host=host, ops=ops or {})
    return SimpleNamespace(trace_data=tr, calls=calls, units=units)


def _self_time(**extra):
    return {"span": r"^dslib\.kmeans\.fit$", "stat": "ms", "per": "call",
            **extra}


# -- span_time ------------------------------------------------------------------

def test_self_time_is_the_span_less_what_the_child_covers():
    # two fits of 40 ms, each with a child of 30 ms wholly inside
    host = [("dslib.kmeans.fit", 0, 40 * MS),
            ("dslib.fitloop.run", 5 * MS, 30 * MS),
            ("dslib.kmeans.fit", 50 * MS, 40 * MS),
            ("dslib.fitloop.run", 55 * MS, 30 * MS),
            ("$kmeans.py:1 fit", 0, 40 * MS)]
    assert span_time.read(_ctx(host), _self_time()) == pytest.approx(40.0)
    got = span_time.read(_ctx(host),
                         _self_time(minus=r"^dslib\.fitloop\.run$"))
    assert got == pytest.approx(10.0)
    per_unit = span_time.read(_ctx(host), {**_self_time(), "per": "unit"})
    assert per_unit == pytest.approx(20.0)


def test_a_span_across_the_windows_edge_counts_with_the_part_inside():
    # 20 ms of the first span and 10 ms of the last lie inside 0..100 ms;
    # the child straddles the same edge and takes 5 ms of those 10 away
    host = [("dslib.kmeans.fit", -20 * MS, 40 * MS),
            ("dslib.kmeans.fit", 90 * MS, 40 * MS),
            ("dslib.fitloop.run", 95 * MS, 30 * MS),
            ("dslib.kmeans.fit", 200 * MS, 10 * MS)]
    assert span_time.read(_ctx(host), _self_time()) == pytest.approx(15.0)
    got = span_time.read(_ctx(host),
                         _self_time(minus=r"^dslib\.fitloop\.run$"))
    assert got == pytest.approx(12.5)


def test_count_of_spans_inside_an_enclosing_span():
    host = [("dslib.kmeans.fit", 0, 40 * MS),
            ("dslib.host_read", 1 * MS, 2 * MS),
            ("dslib.host_read", 30 * MS, 2 * MS),
            ("dslib.host_read", 45 * MS, 2 * MS),   # the benchmark's own
            ("dslib.kmeans.fit", 50 * MS, 40 * MS),
            ("dslib.host_read", 60 * MS, 2 * MS)]
    params = {"span": r"^dslib\.host_read$", "stat": "count", "per": "call"}
    assert span_time.read(_ctx(host), params) == pytest.approx(2.0)
    params["inside"] = r"^dslib\.kmeans\.fit$"
    assert span_time.read(_ctx(host), params) == pytest.approx(1.5)


@pytest.mark.parametrize("reader,params", [
    (span_time, _self_time()),
    (span_time, {"span": r"^dslib\.host_read$", "stat": "count",
                 "per": "call", "inside": r"^dslib\.kmeans\.fit$"}),
    (idle_in_span, {"span": r"^dslib\.kmeans", "per": "call"})])
def test_no_matching_span_reads_nothing_and_never_zero(reader, params):
    # what the parent of this PR gives: frames and the benchmark's spans
    host = [("bench.window", 0, 100 * MS), ("bench.call", 0, 50 * MS),
            ("$array.py:631 _value", 10 * MS, 5 * MS),
            ("dslib.host_read", 45 * MS, 2 * MS)]
    ops = {0: [("fusion", 0, 40 * MS)]}
    assert reader.read(_ctx(host, ops), params) is None
    assert reader.read(_ctx([], ops), params) is None


def test_a_wrong_stat_or_per_is_an_error_not_a_number():
    host = [("dslib.kmeans.fit", 0, 40 * MS)]
    with pytest.raises(ValueError):
        span_time.read(_ctx(host), {**_self_time(), "stat": "seconds"})
    with pytest.raises(KeyError):
        span_time.read(_ctx(host), {**_self_time(), "per": "fit"})


# -- idle_in_span ---------------------------------------------------------------

def test_spans_and_no_gap_in_them_is_zero():
    # the device idles from 40 to 50 ms only, between the two products
    host = [("dslib.matmul", 0, 1 * MS), ("dslib.array.wait", 2 * MS, 37 * MS),
            ("dslib.matmul", 50 * MS, 1 * MS),
            ("dslib.array.wait", 52 * MS, 48 * MS)]
    ops = {0: [("fusion", 0, 40 * MS), ("fusion", 50 * MS, 50 * MS)]}
    got = idle_in_span.read(_ctx(host, ops),
                            {"span": r"^dslib\.matmul", "per": "unit"})
    assert got == 0.0
    assert idle_in_span.read(
        _ctx(host, ops), {"span": r"^dslib\.array\.wait$", "per": "unit"}) \
        == 0.0


def test_a_gap_goes_to_the_innermost_program_span_not_to_a_frame_in_it():
    # gaps: 0-2 ms (inside dslib.matmul, and inside a Python frame deeper
    # still), 36-44 ms (2 ms of it in dslib.outer alone, 6 in
    # dslib.array.wait inside dslib.outer; its middle, 40, is in the wait),
    # 90-100 ms (no dslib. span at all)
    host = [("bench.window", 0, 100 * MS),
            ("dslib.matmul", 0, 3 * MS),
            ("$base.py:110 matmul", 0, 3 * MS),
            ("$array.py:9 _linearize", 0.5 * MS, 1 * MS),
            ("dslib.outer", 30 * MS, 30 * MS),
            ("dslib.array.wait", 38 * MS, 10 * MS),
            ("$array.py:595 block_until_ready", 41 * MS, 2 * MS)]
    ops = {0: [("fusion", 2 * MS, 34 * MS), ("fusion", 44 * MS, 46 * MS)],
           1: [("fusion", 0, 100 * MS)]}            # first device only
    ctx = _ctx(host, ops, calls=1, units=2)
    assert idle_in_span.read(ctx, {"span": r"^dslib\.matmul$",
                                   "per": "unit"}) == pytest.approx(1.0)
    assert idle_in_span.read(ctx, {"span": r"^dslib\.array\.wait$",
                                   "per": "unit"}) == pytest.approx(3.0)
    assert idle_in_span.read(ctx, {"span": r"^dslib\.outer$",
                                   "per": "call"}) == pytest.approx(2.0)
    assert idle_in_span.read(ctx, {"span": r"^dslib\.", "per": "call"}) \
        == pytest.approx(10.0)


def test_a_gap_over_several_spans_is_shared_out_by_overlap():
    # one product's gap, 40-44 ms: 0.5 ms under no span, dslib.matmul 1 ms,
    # 0.5 ms under none, dslib.array.force 1 ms, then 1 ms of the wait.
    # Its middle lies in the force, which must not take the whole gap
    host = [("dslib.matmul", 40.5 * MS, 1 * MS),
            ("dslib.array.force", 42 * MS, 1 * MS),
            ("dslib.array.wait", 43 * MS, 50 * MS)]
    ops = {0: [("fusion", 0, 40 * MS), ("fusion", 44 * MS, 56 * MS)]}
    ctx = _ctx(host, ops, units=1)
    path = idle_in_span.read(
        ctx, {"span": r"^dslib\.(matmul|array\.force)", "per": "unit"})
    wait = idle_in_span.read(
        ctx, {"span": r"^dslib\.array\.wait$", "per": "unit"})
    assert path == pytest.approx(2.0) and wait == pytest.approx(1.0)
    # two spans of one extent: the moments go to one of them, once
    twice = host + [("dslib.array.force", 42 * MS, 1 * MS)]
    assert idle_in_span.read(
        _ctx(twice, ops, units=1),
        {"span": r"^dslib\.array\.force$", "per": "unit"}) \
        == pytest.approx(1.0)


def test_idle_is_cut_to_the_window():
    host = [("dslib.array.wait", -50 * MS, 200 * MS)]
    ops = {0: [("fusion", 10 * MS, 80 * MS)]}
    got = idle_in_span.read(_ctx(host, ops, units=1),
                            {"span": r"^dslib\.array\.wait$", "per": "unit"})
    assert got == pytest.approx(20.0)


# -- the manifest and the cells ---------------------------------------------------

def _span_metrics(cell=None):
    out = []
    for m in BENCH["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               m["name"] + ".json"), encoding="utf-8") as f:
            if json.load(f)["reader"] in SPAN_READERS \
                    and (cell is None or cell in m.get("workloads", [])):
                out.append(m)
    return out


PR26_METRICS = [
    "kmeans.host_self_ms_per_fit", "fitloop.host_self_ms_per_fit",
    "fitloop.host_reads_per_fit", "fitloop.sync_idle_ms_per_fit",
    "array.host_self_ms_per_product", "array.dispatch_idle_ms_per_product",
    "device.wait_idle_ms_per_product"]


def test_manifest_is_sound_and_the_span_metrics_list_their_cells():
    assert manifest.problems(ROOT) == []
    # appended after the nine that were there (later entries follow these)
    assert [m["name"] for m in BENCH["per_layer"][9:16]] == PR26_METRICS
    mine = _span_metrics()
    assert set(PR26_METRICS) <= {m["name"] for m in mine}
    # a metric that reads the program's spans names its cells: it must not
    # land in a later cell whose program opens none of them
    assert all(m.get("workloads") for m in mine)


@pytest.mark.parametrize("cell", ["kmeans_fit_sustained",
                                  "matmul_summa_2x2"])
def test_traced_rehearsal_prints_every_span_metric_of_the_cell(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", cell, "--seed", "2600000011", "--seconds", "0.5",
        "--trace", "1", "--rehearsal"]
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    earlier, last = (json.loads(line) for line in
                     done.stdout.strip().splitlines()[-2:])
    assert earlier["silent_metrics"] == []
    want = _span_metrics(cell)
    assert len(want) >= 3
    for m in want:
        got = last["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        assert got["value"] is not None and got["value"] >= 0
    if cell == "kmeans_fit_sustained":
        assert last["metrics"]["fitloop.host_reads_per_fit"]["value"] == 3.0
