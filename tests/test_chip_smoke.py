"""chip_smoke.py contract tests (CPU, toy width behind the explicit flag).

The chip run itself cannot happen here; these pin the script's contract:
the phases run, a failing phase fails the run, no TPU and no flag is a
failure with no result line, and the compile cache is placed from outside
or at one fixed in-checkout path."""

import argparse
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deeperspeed_tpu.utils import compile_cache  # noqa: E402


def _phase_args(phase):
    return argparse.Namespace(phase=phase, tiny_cpu=True, zero_stage=None)


@pytest.mark.parametrize("phase", chip_smoke.PHASES)
def test_tiny_phase_runs_and_names_the_cpu(phase, capsys):
    assert chip_smoke.run_phase(_phase_args(phase)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-1].startswith(chip_smoke.RESULT_PREFIX)
    result = json.loads(lines[-1][len(chip_smoke.RESULT_PREFIX):])
    assert result["device"]["platform"] == "cpu" and result["size"] == "tiny"
    # every other line says where it ran
    assert all("platform=cpu" in ln and "size=tiny" in ln
               for ln in lines[:-1])


def test_phase_that_raises_is_not_swallowed(monkeypatch):
    def boom(args, say):
        raise RuntimeError("boom")

    monkeypatch.setattr(chip_smoke, "phase_trainer", boom)
    with pytest.raises(RuntimeError, match="boom"):
        chip_smoke.run_phase(_phase_args("trainer"))


def _fake_child(monkeypatch, code):
    monkeypatch.setattr(chip_smoke, "_child_cmd",
                        lambda phase, args: [sys.executable, "-c", code])


def test_failed_child_fails_the_run_and_prints_no_result(monkeypatch, capfd):
    _fake_child(monkeypatch, "print('partial'); raise SystemExit(3)")
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capfd.readouterr().out


def test_parent_prints_device_json_last(monkeypatch, capfd):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    _fake_child(monkeypatch, "print(%r)" % (
        chip_smoke.RESULT_PREFIX + json.dumps({"device": dev})))
    assert chip_smoke.main([]) == 0
    last = capfd.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}


def test_no_tpu_and_no_flag_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_cache_placed_from_outside_leaves_jax_alone(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_default_is_one_fixed_ignored_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    # host CPU backend: nothing is placed
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
