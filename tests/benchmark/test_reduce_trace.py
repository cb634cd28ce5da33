"""The reduction from a trace to numbers, on small hand-made event lists
with hand-worked answers: busy union with overlapping ops, idle share,
per-op sums inside named programs, and a collective half hidden by
compute."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reduce_trace as rt  # noqa: E402

MS = 1_000_000          # ns


def _trace(**kw):
    return rt.Trace(t0=0, t1=100 * MS, **kw)


def test_merge_and_union_with_overlap_and_touching():
    assert rt.merge([(0, 10), (5, 20), (20, 30), (40, 50)]) \
        == [[0, 30], [40, 50]]
    assert rt.union_ns([(0, 10), (5, 20), (40, 50), (41, 42)]) == 30


def test_subtract_leaves_what_nothing_covers():
    assert rt.subtract([(0, 100)], [(10, 20), (15, 30), (90, 120)]) \
        == [(0, 10), (30, 90)]
    assert rt.subtract([(0, 10)], []) == [(0, 10)]
    assert rt.subtract([(0, 10)], [(0, 10)]) == []


def test_busy_is_the_union_not_the_sum_and_idle_is_the_rest():
    # two ops overlap from 10 to 20 ms: busy 0-30 and 50-60 = 40 ms
    tr = _trace(ops={0: [("a", 0, 20 * MS), ("b", 10 * MS, 20 * MS),
                         ("c", 50 * MS, 10 * MS)]})
    assert rt.busy_seconds(tr) == pytest.approx(0.040)
    assert rt.idle_pct(tr) == pytest.approx(60.0)


def test_busy_is_cut_to_the_window_and_averaged_over_devices():
    tr = _trace(ops={0: [("a", -10 * MS, 20 * MS)],        # 10 ms inside
                     1: [("a", 90 * MS, 30 * MS)]})        # 10 ms inside
    assert rt.busy_seconds(tr) == pytest.approx(0.010)
    tr = _trace(ops={0: [("a", 0, 100 * MS)], 1: [("a", 0, 50 * MS)]})
    assert rt.busy_seconds(tr) == pytest.approx(0.075)
    assert rt.idle_pct(tr) == pytest.approx(25.0)


def test_async_ops_count_as_busy():
    tr = _trace(ops={0: [("a", 0, 10 * MS)]},
                async_ops={0: [("copy-start.1", 5 * MS, 15 * MS)]})
    assert rt.busy_seconds(tr) == pytest.approx(0.020)


def test_no_device_ops_reads_nothing():
    assert rt.busy_seconds(_trace()) is None
    assert rt.idle_pct(_trace()) is None
    assert rt.op_seconds(_trace()) is None
    assert rt.exposed_collective_pct(_trace()) is None


def test_op_sums_by_name():
    tr = _trace(
        ops={0: [("fusion.1", 0, 10 * MS), ("copy.2", 10 * MS, 2 * MS),
                 ("fusion.1", 20 * MS, 10 * MS),
                 ("fusion.9", 50 * MS, 7 * MS)]})
    # every op of the window: 10 + 2 + 10 + 7
    assert rt.op_seconds(tr) == pytest.approx(0.029)
    # but data movement: 10 + 10 + 7
    assert rt.op_seconds(tr, exclude_re="^copy") == pytest.approx(0.027)
    assert rt.op_seconds(tr, op_re="^copy") == pytest.approx(0.002)
    assert rt.op_seconds(tr, op_re=r"^fusion\.1$") == pytest.approx(0.020)
    assert rt.op_seconds(tr, op_re="^absent") is None


def test_op_sums_average_over_devices():
    tr = _trace(ops={0: [("f", 0, 10 * MS)], 1: [("f", 0, 30 * MS)]})
    assert rt.op_seconds(tr) == pytest.approx(0.020)


def test_a_collective_half_hidden_by_compute_is_half_exposed():
    # all-reduce 10-30 ms; a dot runs 20-40 ms: exposed 10-20 = 10 ms
    tr = _trace(ops={0: [("all-reduce.1", 10 * MS, 20 * MS),
                         ("fusion.3", 20 * MS, 20 * MS)]})
    assert rt.exposed_collective_pct(tr) == pytest.approx(10.0)


def test_exposed_collectives_take_the_worst_device_and_the_async_line():
    tr = _trace(
        ops={0: [("fusion", 0, 50 * MS)],
             1: [("fusion", 0, 20 * MS)]},
        async_ops={0: [("all-reduce-start.1", 10 * MS, 20 * MS)],   # hidden
                   1: [("all-reduce-start.1", 10 * MS, 20 * MS)]})  # 20-30
    assert rt.exposed_collective_pct(tr) == pytest.approx(10.0)


def test_one_chip_has_no_collective_to_report():
    tr = _trace(ops={0: [("fusion", 0, 50 * MS)]})
    assert rt.exposed_collective_pct(tr) is None


def test_top_ops_sum_by_name_longest_first_at_most_ten():
    rows = [(f"op{i}", i * MS, (i + 1) * MS // 2) for i in range(12)]
    tr = _trace(ops={0: rows + [("op11", 50 * MS, 20 * MS)]})
    top = rt.top_ops(tr)
    assert len(top) == 10
    assert top[0][0] == "op11" and top[0][1] == pytest.approx(0.026)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = _trace(
        ops={0: [("f", 0, 40 * MS), ("f", 60 * MS, 40 * MS)]},
        host=[(rt.WINDOW_SPAN, 0, 100 * MS), ("bench.call", 0, 100 * MS),
              ("$kmeans.py:130 fit", 30 * MS, 40 * MS),
              ("device_get", 45 * MS, 10 * MS)])
    assert rt.idle_gaps(tr) == [["device_get", pytest.approx(0.020)]]
    tr.host = []
    assert rt.idle_gaps(tr) == [["no host span", pytest.approx(0.020)]]


def _idle_gaps_by_scan(trace):
    """What ``idle_gaps`` reads, the plain way: for each gap every span,
    shortest first."""
    dev = sorted(trace.ops)[0]
    busy = rt.merge(rt.clip(trace.ops[dev], trace.t0, trace.t1))
    host = sorted(trace.host, key=lambda e: e[2])
    total = {}
    for s, e in rt.subtract([(trace.t0, trace.t1)], busy):
        mid = (s + e) / 2.0
        name = next((hn for hn, hs, hd in host if hn != rt.WINDOW_SPAN
                     and hs <= mid <= hs + hd), "no host span")
        total[name] = total.get(name, 0.0) + (e - s)
    return [[name, ns / 1e9] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:10]]


@pytest.mark.parametrize("seed", range(4))
def test_idle_gaps_name_each_gap_as_a_scan_of_every_span_would(seed):
    """Nested spans, spans of equal length, spans that end or begin at a
    gap's middle, gaps under no span: the sweep agrees with the scan."""
    import random
    rnd = random.Random(seed)
    ops = []
    t = 0
    while t < 100 * MS:
        d = rnd.choice([1, 2, 50, 1_000, 300_000])
        ops.append(("f", t, d))
        t += d + rnd.choice([1, 2, 3, 1_000, 2_000_000])
    host = [(rt.WINDOW_SPAN, 0, 100 * MS)]
    for j in range(400):
        s = rnd.randrange(0, 100 * MS)
        host.append((f"span{j % 37}", s, rnd.choice(
            [1, 10, 1_000, 1_000_000, 5_000_000, 40_000_000])))
    tr = _trace(ops={0: ops}, host=host)
    assert len(rt.subtract([(0, 100 * MS)], rt.merge(rt.clip(
        ops, 0, 100 * MS)))) > 100
    assert rt.idle_gaps(tr) == _idle_gaps_by_scan(tr)


def test_idle_gaps_take_one_sweep_over_many_gaps_and_spans():
    # 100 000 gaps a nanosecond wide and 200 000 spans: a scan of the
    # spans for each gap would make 10^10 comparisons
    ops = [("f", 3 * i, 2) for i in range(100_000)]
    host = [(rt.WINDOW_SPAN, 0, 300_000)] + [
        (f"$frame{i % 5}", i, 7 + i % 3) for i in range(200_000)]
    tr = rt.Trace(t0=0, t1=300_000, ops={0: ops}, host=host)
    import time
    t = time.perf_counter()
    got = rt.idle_gaps(tr)
    assert time.perf_counter() - t < 30
    assert sum(s for _, s in got) == pytest.approx(100_000e-9)


def test_a_while_spanning_its_body_is_left_out_by_the_loaders_pattern():
    import json
    import re
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        tr = json.load(f)["devices"]["TPU v5 lite"]["trace"]
    name = re.search(tr["op_name"], "%while.2 = (f32[10,100]{1,0}) while(")
    assert name.group(1) == "while.2"
    assert re.search(tr["container_op"], "while.2")
    assert not re.search(tr["container_op"], "fusion.32")
    assert re.search(tr["device_plane"], "/device:TPU:3").group(1) == "3"
    assert not re.search(tr["device_plane"], "/device:CUSTOM:Megascale Trace")


def test_a_collective_is_known_by_its_opcode_not_by_its_name():
    # what the v5e's trace gives for SUMMA's masked psum, and a fusion whose
    # operand is merely named after one
    text = {
        "psum_invariant.32": "%psum_invariant.32 = f32[20480,20480]{1,0:T(8,128)}"
                             " all-reduce(f32[20480,20480]{1,0:T(8,128)} %gte.1)",
        "fusion": "%fusion = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0} "
                  "%all-reduce.3), kind=kOutput, calls=%fused_computation",
        "ar-start": "%ar-start = (f32[8]{0}, f32[8]{0}) all-reduce-start("
                    "f32[8]{0} %x)"}
    tr = _trace(ops={0: [("psum_invariant.32", 0, 10 * MS),
                         ("fusion", 10 * MS, 30 * MS)]},
                async_ops={0: [("ar-start", 35 * MS, 10 * MS)]}, text=text)
    assert rt.is_collective(tr, "psum_invariant.32")
    assert rt.is_collective(tr, "ar-start")
    assert not rt.is_collective(tr, "fusion")
    # exposed: 0-10 whole, and 40-45 of the async one: 15 ms of 100
    assert rt.exposed_collective_pct(tr) == pytest.approx(15.0)


def test_the_dot_ops_of_a_product_are_found_by_text_or_name():
    import json
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "pdot_roofline.json")) as f:
        params = json.load(f)["params"]
    text = {
        "fusion": "%fusion = f32[20480,20480]{1,0:T(8,128)} fusion(f32[8] %p), "
                  "kind=kOutput, calls=%fused_computation",
        "convolution_add_fusion": "%convolution_add_fusion = f32[8]{0} "
                                  "fusion(f32[8] %fusion), kind=kOutput",
        "psum_invariant.32": "%psum_invariant.32 = f32[8]{0} all-reduce(f32[8] %x)",
        "broadcast_select_fusion": "%broadcast_select_fusion = (f32[8]{0}, "
                                   "f32[8]{0}) fusion(f32[8] %x), kind=kLoop",
        "panel_gemm.3": "%panel_gemm.3 = f32[8,8]{1,0:T(8,128)} "
                        "custom-call(f32[8,8] %a, f32[8,8] %b), "
                        "custom_call_target=\"tpu_custom_call\""}
    # no name of the program is matched: a program may be renamed or split
    # and the metric still reads
    assert "module" not in params
    tr = _trace(
        ops={0: [("broadcast_select_fusion", 0, 8 * MS),
                 ("psum_invariant.32", 8 * MS, 40 * MS),
                 ("fusion", 48 * MS, 20 * MS),
                 ("convolution_add_fusion", 68 * MS, 22 * MS)]},
        text=text)
    got = rt.op_seconds(tr, params.get("op"), params.get("exclude"))
    assert got == pytest.approx(0.042)
    # a hand-written kernel in the dot's place is a custom call: counted
    tr.ops[0].append(("panel_gemm.3", 90 * MS, 5 * MS))
    tr.t1 = 95 * MS
    assert rt.op_seconds(tr, params.get("op"),
                         params.get("exclude")) == pytest.approx(0.047)
    tr.ops[0].pop()
    tr.text = {}                     # names alone, as on a bare event list
    assert rt.op_seconds(tr, params.get("op"),
                         params.get("exclude")) == pytest.approx(0.042)


def test_an_op_that_straddles_the_windows_edge_counts_for_its_part_inside():
    # the window is 0-100 ms: 5 ms of the first op and 10 ms of the last
    tr = _trace(ops={0: [("fusion", -5 * MS, 10 * MS),
                         ("fusion", 90 * MS, 30 * MS),
                         ("fusion", 120 * MS, 10 * MS)]})
    assert rt.op_seconds(tr) == pytest.approx(0.015)
    assert rt.busy_seconds(tr) == pytest.approx(0.015)


# -- the loader: planes and lines of the profiler's file ----------------------

class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns
        self.stats = ()


class _Named:
    def __init__(self, name, **kw):
        self.name = name
        self.__dict__.update(kw)


def _fake_profile(monkeypatch, tmp_path, thread, with_window=True):
    """A profile of one device with one op, and a host plane whose
    caller's thread is called ``thread``."""
    import jax.profiler
    caller = [_Ev("bench.call", 10 * MS, 80 * MS),
              _Ev("$array.py:595 block_until_ready", 60 * MS, 30 * MS)]
    if with_window:
        caller.insert(0, _Ev("bench.window", 10 * MS, 80 * MS))
    planes = [
        _Named("/device:TPU:0", lines=[
            _Named("XLA Ops", events=[
                _Ev("%fusion = f32[8]{0} fusion(f32[8] %p), kind=kOutput",
                    5 * MS, 65 * MS)]),
            _Named("XLA Modules", events=[_Ev("jit_f(1)", 5 * MS, 65 * MS)])]),
        _Named("/host:CPU", lines=[
            _Named("tf_pjrt_thread", events=[_Ev("other", 0, 100 * MS)]),
            _Named(thread, events=caller)])]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _Named("p", planes=planes)))
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        import json
        return json.load(f)["devices"]["TPU v5 lite"]["trace"]


@pytest.mark.parametrize("thread", ["python", "python3", "python3.12",
                                    "MainThread"])
def test_the_window_is_found_whatever_the_callers_thread_is_called(
        monkeypatch, tmp_path, thread):
    # the driver's command is `python3 ...`: its thread is no "python"
    patterns = _fake_profile(monkeypatch, tmp_path, thread)
    tr = rt.load(str(tmp_path), patterns)
    assert (tr.t0, tr.t1) == (10 * MS, 90 * MS)
    assert rt.busy_seconds(tr) == pytest.approx(0.060)      # 10-70 of 10-90
    assert rt.idle_pct(tr) == pytest.approx(25.0)
    # the gap is named by the caller's own innermost span, not another
    # thread's
    assert rt.idle_gaps(tr) == [["$array.py:595 block_until_ready",
                                 pytest.approx(0.020)]]


def test_a_trace_without_the_windows_span_is_an_error_not_a_guess(
        monkeypatch, tmp_path):
    patterns = _fake_profile(monkeypatch, tmp_path, "python3",
                             with_window=False)
    with pytest.raises(rt.MissingWindow):
        rt.load(str(tmp_path), patterns)
