"""BENCHMARK.json and every data file it names hold to the contract, and
a cell, a configuration and a per-layer metric can each be added as new
files plus entries (``tests/bench/data``) without editing ``benchmark/``."""

import glob
import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf

DATA = os.path.join(os.path.dirname(__file__), "data")
TOY = os.path.join(DATA, "BENCHMARK.toy.json")


@pytest.fixture(scope="module")
def man():
    return mf.Manifest()


def test_manifest_holds_to_the_contract(man):
    assert mf.validate(man.data) == []


SERVING = [w["name"] for w in mf.Manifest().data["workloads"]
           if w["traffic"] != "train"]


@pytest.mark.parametrize("cell", SERVING)
def test_a_serving_cell_offers_whole_requests_at_a_share_of_a_stated_knee(man, cell):
    """The rate is fixed in the mix's file, never searched: it offers a
    whole number of requests in a run, and the mix's ``why`` and the
    cell's both say what knee it is a share of."""
    entry = man.cell(cell)
    mix = man.traffic(entry["traffic"])
    n = man.data["run_seconds"] * mix["arrivals"]["rate_per_s"]
    assert n >= 8 and abs(n - round(n)) < 1e-9
    assert "knee" in mix["why"] and "knee" in entry["why"]
    assert f"{mix['arrivals']['rate_per_s']:g}" in entry["why"]


@pytest.mark.parametrize("breach", [
    lambda d: d["end_to_end"][0].update(bound=0.2),
    lambda d: d["workloads"][0].update(name="has space"),
    lambda d: d["per_layer"][0].update(unit="tokens per s"),
    lambda d: d["per_layer"][0].update(moves="no_such_metric"),
    lambda d: d["per_layer"][0].update(why="not allowed on a metric"),
    lambda d: [w.update(chips=4) for w in d["workloads"]],
    lambda d: d["configs"][0].update(reduced=["hidden_size"]),
    lambda d: d.update(extra=1),
    lambda d: d["command"].append("/etc/passwd"),
    lambda d: d["end_to_end"].pop(),      # setup_s
])
def test_validate_sees_each_breach(man, breach):
    data = json.loads(json.dumps(man.data))
    breach(data)
    assert mf.validate(data), "breach went unseen"


def test_every_data_file_loads_and_is_named_by_the_rules(man):
    files = [f for p in man.data["paths"]
             for f in glob.glob(os.path.join(mf.ROOT, p, "**", "*"), recursive=True)
             if os.path.isfile(f) and "__pycache__" not in f]
    assert files
    for f in files:
        rel = os.path.relpath(f, mf.ROOT)
        assert all(mf.NAME_RE.match(part) for part in rel.split("/")), rel
        if f.endswith(".json"):
            mf.load_json(f)


def test_every_cell_finds_its_files_and_every_metric_its_reader(man):
    for name, cell in man.cells().items():
        wf = man.workload_file(name)
        assert wf["config"] == cell["config"] and wf["chips"] == cell["chips"]
        assert wf["traffic"] == cell["traffic"]
        assert man.traffic(cell["traffic"])["kind"] == wf["runner"]
        cfg = man.config(cell["config"])
        importlib.import_module(f"benchmark.runners.{wf['runner']}")
        for key in next(c for c in man.data["configs"]
                        if c["name"] == cell["config"])["reduced"]:
            assert key in cfg
        assert man.metrics_for(name, "end_to_end")
        for m in man.metrics_for(name, "per_layer"):
            spec = man.metric_file(m["name"])
            mod = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
            assert callable(mod.read)


def test_moves_names_a_metric_each_of_the_cells_reports(man):
    for name in man.cells():
        mine = {m["name"] for m in man.metrics_for(name, "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        for m in man.metrics_for(name, "per_layer"):
            assert m["moves"] in mine, (name, m["name"])


def test_a_cell_a_config_and_a_metric_are_added_as_files():
    """The toy manifest adds two configurations, three cells and a metric
    of its own under tests/bench/data; the harness finds each by name."""
    toy = mf.Manifest(TOY, extra_dirs=[mf.BENCH_DIR])
    assert toy.config("toy-neox")["family"] == "gpt_neox"
    assert toy.workload_file("toy-bert.train")["runner"] == "train"
    assert toy.traffic("toy-serve")["kind"] == "serve"
    assert toy.metric_file("toy_dispatch_ms")["reducer"] == "host_span_ms"
    names = [m["name"] for m in toy.metrics_for("toy-neox.train", "per_layer")]
    assert "toy_dispatch_ms" in names and "slot_occupancy_pct" not in names
    # the real metric files are found too, through the overlay
    assert toy.metric_file("slot_occupancy_pct")["reducer"] == "counter"


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmark/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_a_host_without_a_tpu():
    r = _run(["--workload", "neox-1.3b.train", "--seed", "1", "--seconds", "1",
              "--trace", "0"], mf.ROOT)
    assert r.returncode != 0
    assert "TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(mf.ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(["--workload", "neox-1.3b.train", "--seed", "1", "--seconds", "1",
              "--trace", "0"], str(tmp_path))
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
