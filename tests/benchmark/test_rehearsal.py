"""Each cell's command end to end on the CPU at a tiny size: one
subprocess a cell, as the driver would start it but with ``--rehearsal``,
the 2x2 cell on virtual devices.  The last line has exactly the contract's
keys (and the rehearsal's stamp); without ``--rehearsal`` the command
finds no accelerator, prints no result and exits non-zero."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(cell, trace, rehearsal=True, seed=2_400_000_011):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable] + BENCH["command"][1:] + [
        "--workload", cell, "--seed", str(seed), "--seconds", "0.5",
        "--trace", str(trace)]
    if rehearsal:
        cmd.append("--rehearsal")
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=240)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_prints_the_contracts_line(cell):
    # even cells untraced, odd cells traced: both shapes of the line are
    # driven, one subprocess a cell
    trace = CELLS.index(cell) % 2
    done = _run(cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    want = CONTRACT_KEYS | {"rehearsal", "compared"}
    if trace:
        want = want | {"breakdown"}
    assert set(last) == want
    assert list(last)[-1] == "compared"          # its own key, last
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] >= 1 and last["failed"] == 0
    dev = last["device"]
    assert DEVICE_KEYS <= set(dev)
    assert dev["platform"] == "cpu"
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    assert dev["count"] == chips
    names = {m["name"] for m in
             BENCH["per_layer" if trace else "end_to_end"]
             if cell in m.get("workloads", [cell])}
    for name, m in last["metrics"].items():
        assert name in names and set(m) == {"value", "unit"}
        assert m["value"] == m["value"] and m["value"] is not None
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in last["breakdown"].values())
    else:
        assert set(last["metrics"]) == names       # setup_s and the rate
    # each number compared, beside its limit, ends standard error too
    tail = done.stderr.strip().splitlines()
    assert tail[-1].startswith("correct: ")
    for name, row in last["compared"].items():
        assert set(row) == {"value", "limit"}
        assert any(line.startswith(f"compared {name}: ") for line in tail)
    # the split of setup_s is on an earlier line
    earlier = json.loads(done.stdout.strip().splitlines()[-2])
    assert {"import_s", "device_s", "data_s", "compile_and_warm_up_s"} \
        == set(earlier["setup_split"])


def test_without_rehearsal_there_is_no_chip_no_result_and_no_zero_exit():
    done = _run(CELLS[0], 0, rehearsal=False)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no accelerator" in done.stderr
