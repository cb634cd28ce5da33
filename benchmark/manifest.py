"""``BENCHMARK.json`` and the files it points to: loading, and the rules.

The rules are the syntax the driver refuses a manifest over before it
runs anything (names, units, key sets, bounds, the share of four-chip
cells) and the links between the manifest and the benchmark's own data
files (a cell's config and traffic file, a metric's file and reader, the
peaks table).  :func:`problems` returns every breach as a sentence; the
harness refuses to run on a non-empty list and
``tests/benchmark/test_manifest.py`` asserts it is empty, so a stray
character costs a second on the CPU and not a session on the chip.

Nothing here imports JAX.
"""

from __future__ import annotations

import json
import os
import re

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH_RE = re.compile(r"[A-Za-z0-9_./-]{1,200}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
END_TO_END_SOURCES = ("host_clock", "device_trace")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
END_TO_END_KEYS = {"name", "unit", "better", "bound", "source"}
PER_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
METRIC_FILE_KEYS = {"reader", "params", "what"}
# a width may never be listed under `reduced`
WIDTH_RE = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state_size|"
                      r"proj|head_size|head_dim|expansion|per_tok")
MAX_BYTES = 64 * 1024
MAX_RUN_SECONDS = 51
BENCH_DIR = "benchmark"


def root_dir() -> str:
    """The checkout this file lives in."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    """The manifest and lazy access to the per-name data files."""

    def __init__(self, root=None):
        self.root = root or root_dir()
        self.path = os.path.join(self.root, "BENCHMARK.json")
        self.data = _read_json(self.path)

    # -- files found by name ---------------------------------------------

    def bench_path(self, *parts) -> str:
        return os.path.join(self.root, BENCH_DIR, *parts)

    def config_entry(self, name) -> dict:
        return _by_name(self.data["configs"], name, "configuration")

    def config(self, name) -> dict:
        return _read_json(os.path.join(self.root,
                                       self.config_entry(name)["file"]))

    def traffic(self, name) -> dict:
        return _read_json(self.bench_path("traffic", name + ".json"))

    def metric_file(self, name) -> dict:
        return _read_json(self.bench_path("metrics", name + ".json"))

    def peaks(self) -> dict:
        return _read_json(self.bench_path("peaks.json"))

    def workload(self, name) -> dict:
        return _by_name(self.data["workloads"], name, "workload")

    # -- which metrics a cell reports ------------------------------------

    def end_to_end_of(self, cell) -> list[dict]:
        """The end-to-end metrics ``cell`` reports: those that list it
        under ``workloads``, and those with no such key."""
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer_of(self, cell) -> list[dict]:
        """The per-layer metrics ``cell`` reports: those that list it, and
        those with no ``workloads`` key whose ``moves`` it reports."""
        e2e = {m["name"] for m in self.end_to_end_of(cell)}
        out = []
        for m in self.data["per_layer"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out


def _by_name(entries, name, what):
    for e in entries:
        if e.get("name") == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _one_line(s, what, out, limit=200):
    if not isinstance(s, str) or not 1 <= len(s) <= limit \
            or "\n" in s or "\t" in s or "\r" in s:
        out.append(f"{what} must be 1 to {limit} characters on one line "
                   f"with no tab, not {s!r}")


def _name(s, what, out):
    if not isinstance(s, str) or not NAME_RE.fullmatch(s):
        out.append(f"{what} {s!r} is no name: a letter, digit or '_', then "
                   "at most 63 letters, digits, '_', '.' and '-'")


def _keys(entry, allowed, what, out, optional=()):
    if not isinstance(entry, dict):
        out.append(f"{what} must be an object")
        return False
    keys = set(entry)
    missing = allowed - keys
    extra = keys - allowed - set(optional)
    if missing:
        out.append(f"{what} lacks {sorted(missing)}")
    if extra:
        out.append(f"{what} has keys the contract refuses: {sorted(extra)}")
    return not missing


def _under(path, dirs):
    norm = os.path.normpath(path)
    return any(norm == d or norm.startswith(d.rstrip("/") + "/")
               for d in dirs)


def problems(root=None) -> list[str]:
    """Every breach of the rules in the checkout at ``root``; empty when
    the manifest and its files are sound."""
    root = root or root_dir()
    out: list[str] = []
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        return [f"{path} is missing"]
    if os.path.getsize(path) > MAX_BYTES:
        out.append("BENCHMARK.json is over 64 KiB")
    try:
        m = Manifest(root)
    except (OSError, ValueError) as e:
        return [f"BENCHMARK.json does not parse: {e}"]
    d = m.data
    if not isinstance(d, dict) or set(d) != TOP_KEYS:
        return [f"BENCHMARK.json must have exactly the keys "
                f"{sorted(TOP_KEYS)}"]

    # paths and command
    paths = d["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        out.append("paths must list 1 to 16 directories")
        paths = []
    for p in paths:
        if not isinstance(p, str) or not PATH_RE.fullmatch(p) \
                or p.startswith("/") or ".." in p.split("/"):
            out.append(f"path {p!r} must be relative, inside the repo, of "
                       "letters, digits, '_', '.', '-' and '/'")
        elif not os.path.isdir(os.path.join(root, p)):
            out.append(f"path {p!r} is no directory")
    cmd = d["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        out.append("command must be a list of 1 to 32 strings")
        cmd = []
    for w in cmd:
        _one_line(w, "a word of command", out)
        if isinstance(w, str):
            if w.startswith("/") or ".." in w.split("/"):
                out.append(f"command word {w!r} leaves the repo")
            elif os.path.exists(os.path.join(root, w)) and "/" in w \
                    and not _under(w, paths):
                out.append(f"command names {w!r}, a file outside paths")
    rs = d["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) \
            or not 1 <= rs <= MAX_RUN_SECONDS:
        out.append(f"run_seconds must be a whole number from 1 to "
                   f"{MAX_RUN_SECONDS}")

    # configurations
    configs = d["configs"] if isinstance(d["configs"], list) else []
    if not 1 <= len(configs) <= 24:
        out.append("configs must hold 1 to 24 configurations")
    seen_files = set()
    for c in configs:
        what = f"configuration {c.get('name') if isinstance(c, dict) else c!r}"
        if not _keys(c, CONFIG_KEYS, what, out):
            continue
        _name(c["name"], "configuration name", out)
        _one_line(c["source"], f"{what}: source", out)
        _one_line(c["why"], f"{what}: why", out)
        red = c["reduced"]
        if not isinstance(red, list) or len(red) > 16:
            out.append(f"{what}: reduced must be a list of at most 16 keys")
            red = []
        for k in red:
            _name(k, f"{what}: reduced key", out)
            if isinstance(k, str) and WIDTH_RE.search(k):
                out.append(f"{what}: reduced names a width, {k!r}")
        f = c["file"]
        if not isinstance(f, str) or not PATH_RE.fullmatch(f) \
                or not _under(f, paths):
            out.append(f"{what}: file {f!r} must lie under paths")
        elif f in seen_files:
            out.append(f"{what}: file {f!r} is another configuration's too")
        elif not os.path.isfile(os.path.join(root, f)):
            out.append(f"{what}: file {f!r} does not exist")
        else:
            seen_files.add(f)
            out.extend(_config_file_problems(m, c, red))
    _unique([c.get("name") for c in configs if isinstance(c, dict)],
            "configuration", out)
    config_names = {c.get("name") for c in configs if isinstance(c, dict)}

    # end-to-end metrics
    e2e = d["end_to_end"] if isinstance(d["end_to_end"], list) else []
    if not 1 <= len(e2e) <= 16:
        out.append("end_to_end must hold 1 to 16 metrics")
    for e in e2e:
        what = f"end-to-end metric {e.get('name') if isinstance(e, dict) else e!r}"
        if not _keys(e, END_TO_END_KEYS, what, out, optional=("workloads",)):
            continue
        _metric_common(e, what, out)
        if e["source"] not in END_TO_END_SOURCES:
            out.append(f"{what}: source must be one of {END_TO_END_SOURCES}")
        b = e["bound"]
        if not isinstance(b, (int, float)) or isinstance(b, bool) \
                or not 0.01 <= b <= 0.1:
            out.append(f"{what}: bound must lie between 0.01 and 0.1")
    e2e_names = [e.get("name") for e in e2e if isinstance(e, dict)]
    if "setup_s" not in e2e_names:
        out.append("end_to_end must hold setup_s")

    # cells
    cells = d["workloads"] if isinstance(d["workloads"], list) else []
    if not 1 <= len(cells) <= 24:
        out.append("workloads must hold 1 to 24 cells")
    pairs = set()
    for w in cells:
        what = f"cell {w.get('name') if isinstance(w, dict) else w!r}"
        if not _keys(w, WORKLOAD_KEYS, what, out):
            continue
        _name(w["name"], "cell name", out)
        _name(w["config"], f"{what}: config", out)
        _name(w["traffic"], f"{what}: traffic", out)
        _one_line(w["why"], f"{what}: why", out)
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            out.append(f"{what}: chips must be 1 or 4")
        if w["config"] not in config_names:
            out.append(f"{what}: no configuration {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"{what}: the pair of {w['config']} and "
                       f"{w['traffic']} appears twice")
        pairs.add((w["config"], w["traffic"]))
        out.extend(_traffic_file_problems(m, w, what))
    cell_names = [w.get("name") for w in cells if isinstance(w, dict)]
    _unique(cell_names, "cell", out)
    unused = config_names - {w.get("config") for w in cells
                             if isinstance(w, dict)}
    if unused:
        out.append(f"configurations no cell uses: {sorted(unused)}")
    four = sum(1 for w in cells if isinstance(w, dict)
               and w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        out.append(f"{four} cells ask for four chips; at most a quarter of "
                   f"{len(cells)}, rounded down, or one, may")

    # per-layer metrics
    per = d["per_layer"] if isinstance(d["per_layer"], list) else []
    if not 1 <= len(per) <= 128:
        out.append("per_layer must hold 1 to 128 metrics")
    layers = {}
    for p in per:
        what = f"per-layer metric {p.get('name') if isinstance(p, dict) else p!r}"
        if not _keys(p, PER_LAYER_KEYS, what, out, optional=("workloads",)):
            continue
        _metric_common(p, what, out)
        _one_line(p["layer"], f"{what}: layer", out)
        if p["moves"] not in e2e_names or p["moves"] == "setup_s":
            out.append(f"{what}: moves must name one end-to-end metric "
                       f"other than setup_s, not {p['moves']!r}")
        if isinstance(p["layer"], str):
            layers.setdefault(p["layer"].lower(), set()).add(p["layer"])
        out.extend(_metric_file_problems(m, p, what))
    for spellings in layers.values():
        if len(spellings) > 1:
            out.append(f"one layer spelt several ways: {sorted(spellings)}")
    _unique(e2e_names + [p.get("name") for p in per if isinstance(p, dict)],
            "metric", out)

    # which cell reports what
    for e in e2e + per:
        if isinstance(e, dict) and "workloads" in e:
            wl = e["workloads"]
            if not isinstance(wl, list) or not wl:
                out.append(f"metric {e.get('name')}: workloads must be a "
                           "list of cells")
                continue
            for c in wl:
                if c not in cell_names:
                    out.append(f"metric {e.get('name')}: no cell {c!r}")
    if not out:
        for c in cell_names:
            mine = {e["name"] for e in m.end_to_end_of(c)}
            if "setup_s" not in mine:
                out.append(f"cell {c} does not report setup_s")
            if len(mine - {"setup_s"}) < 1:
                out.append(f"cell {c} reports no end-to-end metric besides "
                           "setup_s")
            if not m.per_layer_of(c):
                out.append(f"cell {c} reports no per-layer metric")
            t = m.traffic(m.workload(c)["traffic"])
            if t["end_to_end"] not in mine:
                out.append(f"cell {c}: its traffic reports "
                           f"{t['end_to_end']!r}, which BENCHMARK.json does "
                           "not give that cell")
        for p in per:
            for c in p.get("workloads", cell_names):
                reports = {e["name"] for e in m.end_to_end_of(c)}
                if "workloads" in p and p["moves"] not in reports:
                    out.append(f"per-layer metric {p['name']}: cell {c} "
                               f"does not report {p['moves']}")

    out.extend(_peaks_problems(m))
    return out


def _unique(names, what, out):
    seen = set()
    for n in names:
        if n in seen:
            out.append(f"two {what}s are named {n!r}")
        seen.add(n)


def _metric_common(e, what, out):
    _name(e["name"], "metric name", out)
    if not isinstance(e["unit"], str) or not UNIT_RE.fullmatch(e["unit"]):
        out.append(f"{what}: unit {e['unit']!r} must be 1 to 16 characters "
                   "from letters, digits, '_', '/', '%', '.' and '-'")
    if e["better"] not in ("lower", "higher"):
        out.append(f"{what}: better must be 'lower' or 'higher'")
    if e["source"] not in SOURCES:
        out.append(f"{what}: source must be one of {SOURCES}")
    name = e["name"] if isinstance(e["name"], str) else ""
    if ("roofline" in name or "mfu" in re.split(r"[_.-]", name)) \
            and e["unit"] != "%":
        out.append(f"{what}: a share of a roofline or of a peak has the "
                   "unit '%'")


def _config_file_problems(m, entry, reduced):
    out = []
    what = f"configuration file {entry['file']}"
    try:
        cfg = m.config(entry["name"])
    except (OSError, ValueError) as e:
        return [f"{what} does not parse: {e}"]
    for key in ("source", "scaled", "assumed", "reference", "precision",
                "limits"):
        if key not in cfg:
            out.append(f"{what} lacks {key!r}")
    if out:
        return out
    if cfg["source"] != entry["source"]:
        out.append(f"{what}: source differs from BENCHMARK.json's")
    if sorted(cfg["scaled"]) != sorted(reduced):
        out.append(f"{what}: scaled {sorted(cfg['scaled'])} and the "
                   f"manifest's reduced {sorted(reduced)} differ")
    ref = m.bench_path("reference", str(cfg["reference"]) + ".py")
    if not os.path.isfile(ref):
        out.append(f"{what}: no plain reference {ref}")
    for name, lim in cfg["limits"].items():
        _name(name, f"{what}: compared number", out)
        if not isinstance(lim, dict) or not isinstance(
                lim.get("limit"), (int, float)) or lim["limit"] < 0:
            out.append(f"{what}: limit of {name!r} must be a number >= 0 "
                       "under 'limit'")
    return out


def _traffic_file_problems(m, w, what):
    path = m.bench_path("traffic", str(w["traffic"]) + ".json")
    if not os.path.isfile(path):
        return [f"{what}: no traffic file {path}"]
    try:
        t = m.traffic(w["traffic"])
    except ValueError as e:
        return [f"{what}: traffic file does not parse: {e}"]
    out = []
    for key in ("driver", "end_to_end", "why"):
        if key not in t:
            out.append(f"{what}: traffic file lacks {key!r}")
    if not out and not os.path.isfile(
            m.bench_path("drivers", str(t["driver"]) + ".py")):
        out.append(f"{what}: no driver {t['driver']!r}")
    return out


def _metric_file_problems(m, p, what):
    """A metric's file holds what only the benchmark needs to read it
    (``reader``, ``params``, ``what``).  Unit, layer, ``moves`` and the
    cells live in BENCHMARK.json alone, so a later PR that lists a new
    cell there edits no file that is already here."""
    path = m.bench_path("metrics", str(p["name"]) + ".json")
    if not os.path.isfile(path):
        return [f"{what}: no metric file {path}"]
    try:
        f = m.metric_file(p["name"])
    except ValueError as e:
        return [f"{what}: metric file does not parse: {e}"]
    out = []
    if not isinstance(f, dict) or not {"reader", "what"} <= set(f):
        return [f"{what}: its file needs 'reader' and 'what'"]
    extra = set(f) - METRIC_FILE_KEYS
    if extra:
        out.append(f"{what}: its file repeats or adds {sorted(extra)}; "
                   f"only {sorted(METRIC_FILE_KEYS)} belong there")
    if not os.path.isfile(m.bench_path("readers",
                                       str(f.get("reader")) + ".py")):
        out.append(f"{what}: no reader {f.get('reader')!r}")
    if not isinstance(f.get("params", {}), dict):
        out.append(f"{what}: params must be an object")
    return out


def _peaks_problems(m):
    try:
        peaks = m.peaks()
    except (OSError, ValueError) as e:
        return [f"peaks.json does not load: {e}"]
    out = []
    src = peaks.get("source")
    if not isinstance(src, str) or not src.strip():
        out.append("peaks.json has no source")
    devices = peaks.get("devices")
    if not isinstance(devices, dict) or not devices:
        return out + ["peaks.json lists no device"]
    for kind, row in devices.items():
        for key in ("flops_per_s", "hbm_bytes_per_s", "hbm_bytes"):
            v = row.get(key)
            if not isinstance(v, (int, float)) or v <= 0:
                out.append(f"peaks.json: {kind!r} needs {key} > 0")
        tr = row.get("trace", {})
        for key in ("device_plane", "op_line"):
            try:
                re.compile(tr[key])
            except (KeyError, TypeError, re.error):
                out.append(f"peaks.json: {kind!r} needs a trace.{key} "
                           "pattern")
    return out
