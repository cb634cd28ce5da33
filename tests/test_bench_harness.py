"""Bench orchestrator tests: the parent must survive a hung config
(skip-and-continue), abort after two consecutive timeouts, fail fast when
the backend probe dies (and replay nothing), and exit non-zero when any
config row carried an error.

The subprocess tests spawn the real ``bench.py`` parent with the fake-hang
test hook; no config body runs, so they are cheap."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(env_extra, timeout=120):
    env = {**os.environ, "BENCH_SMOKE": "1", "JAX_PLATFORMS": "cpu",
           **env_extra}
    return subprocess.run([sys.executable, BENCH], env=env, timeout=timeout,
                          capture_output=True, text=True)


def _lines(out):
    return [json.loads(ln) for ln in out.strip().splitlines() if ln.strip()]


class TestBenchOrchestrator:
    def test_skip_and_continue_then_abort_on_second_timeout(self):
        # hang the first two configs (dispatch_rtt, kmeans_smoke) so no
        # config body ever really runs — keeps the test cheap/deterministic
        res = _run({"DSLIB_BENCH_FAKE_HANG": "dispatch_rtt,kmeans_smoke",
                    "DSLIB_BENCH_CONFIG_S": "5"})
        assert res.returncode == 2
        lines = _lines(res.stdout)
        errs = [l for l in lines if l.get("error")]
        # first hang: skipped-and-continuing; second: abort
        assert any("skipped, continuing" in l["error"] for l in errs)
        assert lines[-1]["metric"] == "abort"
        assert "two consecutive" in lines[-1]["error"]

    def test_probe_failure_is_fast_and_recorded(self):
        res = _run({"JAX_PLATFORMS": "bogus_platform",
                    "DSLIB_BENCH_PROBE_S": "30"})
        assert res.returncode == 2
        lines = _lines(res.stdout)
        # the probe's own error row and nothing else: no chip means
        # failure, never a replay of an older capture
        assert [l["metric"] for l in lines] == ["backend_init"]
        assert "probe failed" in lines[0]["error"]


def _load_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location("_bench_under_test", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestFailedRunIsNotRcZero:
    def test_guard_reports_failure(self, capsys):
        bench = _load_bench()
        assert bench._guard("ok", lambda: {"metric": "ok"}) is True

        def boom():
            raise ValueError("boom")
        assert bench._guard("bad", boom) is False
        rows = _lines(capsys.readouterr().out)
        assert rows[1]["metric"] == "bad"
        assert "ValueError: boom" in rows[1]["error"]

    def test_parent_exits_nonzero_when_a_row_carried_error(
            self, monkeypatch, capsys):
        """Configs stay isolated (the run continues past the bad one) but
        the parent's exit code says a row failed."""
        bench = _load_bench()
        monkeypatch.setattr(bench, "_configs",
                            lambda: [("bad", None), ("good", None)])
        ran = []

        def fake_run(cmd, **kw):
            if "--one" not in cmd:                      # the device probe
                return subprocess.CompletedProcess(cmd, 0, "", "")
            name = cmd[-1]
            ran.append(name)
            row = {"metric": name, "value": 1.0}
            if name == "bad":
                row = {"metric": name, "value": None, "error": "X: y"}
            return subprocess.CompletedProcess(
                cmd, 1 if name == "bad" else 0, json.dumps(row) + "\n", "")

        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        try:
            bench.main()
            code = 0
        except SystemExit as e:
            code = e.code
        assert ran == ["bad", "good"]
        assert code == 1
        assert [l["metric"] for l in _lines(capsys.readouterr().out)] == \
            ["bad", "good"]
