"""chip_smoke.py's contract, as far as a CPU rig can hold it: the
rehearsal (tiny size, Pallas interpreted) runs every phase green and
stamps every line; with no chip and no rehearsal flag the script fails
and prints no result; a phase that raises fails the run.  Plus the
compile-cache lint: the cache directory is decided in exactly one place,
and that place cannot produce a directory that moves between runs.
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
PHASES = ["device", "train", "mixture", "rsvd", "serve", "bundle",
          "kernels", "summa"]


def _run(*args, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "DSLIB_MESH", "DSLIB_OVERLAP")}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def _json_lines(out):
    return [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]


def test_rehearsal_runs_every_phase_stamped(tmp_path):
    cache = tmp_path / "cache"
    res = _run("--rehearsal", JAX_COMPILATION_CACHE_DIR=str(cache))
    assert res.returncode == 0, res.stderr[-2000:]
    lines = _json_lines(res.stdout)
    assert [ln["phase"] for ln in lines[:-1]] == PHASES
    for ln in lines[:-1]:
        assert ln["ok"] is True and ln["rehearsal"] is True
        assert ln["platform"] == "cpu" and ln["n_devices"] == 4
        assert "device_kind" in ln and "peak_bytes_in_use" in ln
        assert "first_call_s" in ln and "warm_call_s" in ln
    # the result is the LAST line of stdout, and says what it ran on
    assert json.loads(res.stdout.strip().splitlines()[-1]) == lines[-1]
    assert lines[-1] == {"ok": True, "rehearsal": True,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 4}}
    by = {ln["phase"]: ln for ln in lines[:-1]}
    # the cache is resolved to where the environment said; on the CPU
    # backend persistence stays off (see compile_cache.enable)
    assert by["device"]["compile_cache_dir"] == str(cache)
    assert not cache.exists() or not any(cache.iterdir())
    assert by["train"]["fit_info"] == {"chunks": 2, "rollbacks": 0}
    assert by["train"]["collectives"]["all-reduce"] >= 1
    # the interpreter's backend keeps the two XLA passes; a TPU reads "fused"
    assert by["train"]["kmeans_step"] == ["two_pass"]
    # the mixture fit's step is the blocked one on every backend, and its
    # packed partial sums cross the mesh in one all-reduce
    assert by["mixture"]["gm_step"] == ["blocked"]
    # its E-step's product is ONE whole GEMM here; a TPU, which packs it,
    # cuts it along the factors' triangle and reads "triangle"
    assert by["mixture"]["gm_e_step"] == ["whole"]
    # its M-step's product is XLA's own six passes here; a TPU reads "packed"
    assert by["mixture"]["gm_m_step"] == ["six_pass"]
    assert by["mixture"]["collectives"] == {"all-reduce": 1}
    assert by["mixture"]["predict_agreement"] >= 0.9999
    # the randomized SVD's panels take the Householder tree here; a TPU
    # reads ["blocked", "one_product"]
    assert by["rsvd"]["tsqr_local"] == ["householder_tree"]
    # on every route Q is one application that carries the tree's Q2
    assert by["rsvd"]["tsqr_assemble"] == ["folded"]
    assert by["rsvd"]["values_gap_vs_numpy"] <= 1e-4
    assert by["rsvd"]["u_orthogonality"] <= 5e-6
    assert by["serve"]["traces_after_start"] == 0
    assert by["serve"]["dispatches_per_batch_max"] == 1
    assert by["bundle"]["traces_after_load"] == 0
    assert by["bundle"]["fallback"] is False
    assert by["kernels"]["interpret"] is True
    assert by["kernels"]["mesh_shape"] == [2, 2]
    assert any(k.startswith("summa_matmul:") for k in by["summa"]["schedules"])
    # SUMMA's panels are fetched from their owners, in both phases that run it
    assert by["summa"]["schedules"]["summa_fetch:direct"] == 2
    assert by["kernels"]["panel_gemm"]["summa_fetch:direct"] == 3


def test_no_chip_and_no_rehearsal_fails_without_a_result():
    res = _run()
    assert res.returncode not in (0, None)
    assert _json_lines(res.stdout) == []
    assert "no accelerator" in res.stderr


def test_a_failing_phase_fails_the_run():
    res = _run("--rehearsal", "--fail-phase", "device")
    assert res.returncode != 0
    assert _json_lines(res.stdout) == []
    assert "forced to fail" in res.stderr


# -- compile cache: the resolver and its lint -------------------------------

def test_resolve_dir(monkeypatch):
    from dislib_tpu.runtime import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.resolve_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.resolve_dir() == os.path.join(REPO, ".jax_cache")


RESOLVER = "dislib_tpu/runtime/compile_cache.py"
_CACHE_WRITE = re.compile(
    r"""(update\(\s*['"]jax_compilation_cache_dir['"]
         |environ\s*\[\s*['"]JAX_COMPILATION_CACHE_DIR['"]\s*\]\s*=
         |(setdefault|putenv)\(\s*['"]JAX_COMPILATION_CACHE_DIR['"]
         |\bJAX_COMPILATION_CACHE_DIR=)""", re.VERBOSE)
_MOVING = re.compile(r"tempfile|getpid|\btime\b|datetime|uuid|random")


def _sources():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in ("__pycache__",
                                                          "chiprun_out")]
        for f in files:
            if f.endswith((".py", ".sh")):
                full = os.path.join(root, f)
                yield os.path.relpath(full, REPO).replace(os.sep, "/"), full


def test_compile_cache_dir_is_set_in_exactly_one_place():
    writes = {}
    for rel, full in _sources():
        if rel == "tests/test_chip_smoke.py":
            continue            # this file quotes the patterns
        with open(full, encoding="utf-8", errors="replace") as f:
            n = len(_CACHE_WRITE.findall(f.read()))
        if n:
            writes[rel] = n
    assert writes == {RESOLVER: 1}, (
        "the compile-cache directory is decided by "
        f"{RESOLVER}::enable and nowhere else; found writes in {writes}")
    with open(os.path.join(REPO, RESOLVER), encoding="utf-8") as f:
        moving = _MOVING.findall(f.read())
    assert not moving, (
        f"{RESOLVER} must resolve a directory that never moves (the path "
        f"is part of the cache key); found {moving}")
