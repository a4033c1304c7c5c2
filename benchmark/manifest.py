"""Loads ``BENCHMARK.json`` and the data files it names, and checks both
against the contract. Pure Python: importing it touches no JAX."""

import json
import os
import re
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_dim", "head_size", "expansion", "experts_per_tok")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` plus lazy access to each named data file.

    ``extra_dirs`` lets a caller (a test, a later PR's scratch) overlay
    further directories that hold ``configs/``, ``workloads/``,
    ``traffic/`` and ``metrics/`` of their own; the first hit wins."""

    def __init__(self, path: str = None, extra_dirs: List[str] = ()):
        self.path = path or os.path.join(ROOT, "BENCHMARK.json")
        self.root = os.path.dirname(os.path.abspath(self.path))
        self.data = load_json(self.path)
        self.dirs = list(extra_dirs) + [
            os.path.join(self.root, p) for p in self.data["paths"]]

    # ---- lookups ---------------------------------------------------- #
    def cells(self) -> Dict[str, dict]:
        return {w["name"]: w for w in self.data["workloads"]}

    def cell(self, name: str) -> dict:
        cells = self.cells()
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
        return cells[name]

    def _find(self, kind: str, name: str) -> str:
        for d in self.dirs:
            p = os.path.join(d, kind, name + ".json")
            if os.path.exists(p):
                return p
        raise FileNotFoundError(f"no {kind}/{name}.json under {self.dirs}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"unknown config {name!r}")

    def workload_file(self, cell: str) -> dict:
        return load_json(self._find("workloads", cell))

    def traffic(self, name: str) -> dict:
        return load_json(self._find("traffic", name))

    def metric_file(self, name: str) -> dict:
        return load_json(self._find("metrics", name))

    def metrics_for(self, cell: str, group: str) -> List[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell
        reports: those that list it, or list no cells at all and (for a
        per-layer metric) move an end-to-end metric the cell reports."""
        e2e = {m["name"]: m for m in self.data["end_to_end"]}

        def reports(m):
            return "workloads" not in m or cell in m["workloads"]

        if group == "end_to_end":
            return [m for m in e2e.values() if reports(m)]
        return [m for m in self.data["per_layer"]
                if reports(m) and reports(e2e[m["moves"]])]


def validate(data: dict, root: str = ROOT) -> List[str]:
    """Every breach of the contract's static rules, as text. Empty when
    the file may be handed to the driver."""
    errs = []

    def need(cond, msg):
        if not cond:
            errs.append(msg)

    need(set(data) == TOP_KEYS, f"top-level keys {sorted(data)} != {sorted(TOP_KEYS)}")
    need(len(json.dumps(data)) <= 64 * 1024, "file over 64 KiB")
    paths = data.get("paths", [])
    need(1 <= len(paths) <= 16, "1..16 paths")
    for p in paths:
        need(re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and not p.startswith("/")
             and ".." not in p.split("/"), f"bad path {p!r}")
    cmd = data.get("command", [])
    need(1 <= len(cmd) <= 32, "command of 1..32 words")
    for w in cmd:
        need(1 <= len(w) <= 200 and "\n" not in w and "\t" not in w,
             f"bad command word {w!r}")
        need(not w.startswith("/") and ".." not in w.split("/"),
             f"command word leaves the repo: {w!r}")
    rs = data.get("run_seconds")
    need(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds 1..51")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    def line(s, what):
        need(isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
             and "\t" not in s, f"{what}: 1..200 characters on one line")

    cfgs = data.get("configs", [])
    need(1 <= len(cfgs) <= 24, "1..24 configs")
    need(len({c["name"] for c in cfgs}) == len(cfgs), "config names repeat")
    need(len({c["file"] for c in cfgs}) == len(cfgs), "config files repeat")
    for c in cfgs:
        need(set(c) == {"name", "source", "file", "reduced", "why"},
             f"config {c.get('name')}: keys {sorted(c)}")
        need(NAME_RE.match(c["name"]), f"bad config name {c['name']!r}")
        line(c["source"], f"config {c['name']} source")
        line(c["why"], f"config {c['name']} why")
        need(under_paths(c["file"]), f"{c['file']} not under paths")
        need(os.path.exists(os.path.join(root, c["file"])), f"{c['file']} missing")
        need(len(c["reduced"]) <= 16, "reduced has over 16 keys")
        for k in c["reduced"]:
            need(NAME_RE.match(k), f"bad reduced key {k!r}")
            need(not (k.endswith("_dim") or k.endswith("_rank")
                      or any(w in k for w in WIDTH_WORDS)),
                 f"reduced names a width: {k!r}")
    cells = data.get("workloads", [])
    need(1 <= len(cells) <= 24, "1..24 workloads")
    names = [w["name"] for w in cells]
    need(len(set(names)) == len(names), "workload names repeat")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    need(len(set(pairs)) == len(pairs), "a (config, traffic) pair repeats")
    cfg_names = {c["name"] for c in cfgs}
    for w in cells:
        need(set(w) == {"name", "config", "traffic", "chips", "why"},
             f"workload {w.get('name')}: keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            need(NAME_RE.match(w[k]), f"bad {k} {w[k]!r}")
        need(w["config"] in cfg_names, f"{w['name']}: unknown config")
        need(w["chips"] in (1, 4), f"{w['name']}: chips 1 or 4")
        line(w["why"], f"workload {w['name']} why")
    need({w["config"] for w in cells} == cfg_names, "a config is used by no cell")
    four = sum(1 for w in cells if w["chips"] == 4)
    need(four <= max(1, len(cells) // 4), f"{four} four-chip cells of {len(cells)}")

    e2e = data.get("end_to_end", [])
    per = data.get("per_layer", [])
    need(1 <= len(e2e) <= 16, "1..16 end_to_end")
    need(1 <= len(per) <= 128, "1..128 per_layer")
    all_names = [m["name"] for m in e2e + per]
    need(len(set(all_names)) == len(all_names), "metric names repeat")
    need("setup_s" in {m["name"] for m in e2e}, "no setup_s")
    e2e_by = {m["name"]: m for m in e2e}

    def cells_of(m):
        return set(m.get("workloads", names))

    for m in e2e:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"},
             f"end_to_end {m.get('name')}: keys {sorted(m)}")
        need(m["source"] in ("host_clock", "device_trace"),
             f"{m['name']}: end-to-end source {m['source']!r}")
        need(isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.1,
             f"{m['name']}: bound {m['bound']}")
    for m in per:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                        "layer", "moves"},
             f"per_layer {m.get('name')}: keys {sorted(m)}")
        need(m["source"] in SOURCES, f"{m['name']}: source {m['source']!r}")
        line(m["layer"], f"{m['name']} layer")
        need(m["moves"] in e2e_by, f"{m['name']} moves unknown {m['moves']!r}")
        if m["moves"] in e2e_by:
            need(cells_of(m) <= cells_of(e2e_by[m["moves"]]) or "workloads" not in m,
                 f"{m['name']}: a cell does not report {m['moves']}")
    for m in e2e + per:
        need(NAME_RE.match(m["name"]), f"bad metric name {m['name']!r}")
        need(UNIT_RE.match(m["unit"]), f"bad unit {m['unit']!r}")
        need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        for c in m.get("workloads", []):
            need(c in names, f"{m['name']}: unknown cell {c!r}")
    for c in names:
        mine = [m for m in e2e if c in cells_of(m)]
        need(any(m["name"] == "setup_s" for m in mine), f"{c}: no setup_s")
        need(len(mine) >= 2, f"{c}: no end-to-end metric besides setup_s")
        need(any(c in cells_of(m) and c in cells_of(e2e_by.get(m["moves"], {}))
                 for m in per), f"{c}: no per-layer metric")
    return errs
