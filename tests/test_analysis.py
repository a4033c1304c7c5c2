"""Static-analysis layer tests: every AST rule against a planted-
violation fixture module, the suppression machinery (mandatory
reasons), HLO-level donation/collective/callback/fp64 checks against
real lowerings (including the actual engine train step), the
mesh-construction fixes' placement regression, and the CLI's exit
codes."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.analysis import (
    ConfigKeyUndeclaredRule,
    Finding,
    HostSyncInJitRule,
    MeshConstructionRule,
    PRNGKeyInTracedRule,
    ProgramSpec,
    SuppressionError,
    TraceEventNamesRule,
    all_gather_result_bytes,
    apply_suppressions,
    audit_program,
    count_alias_pairs,
    lint_paths,
    load_suppressions,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = ("tests/analysis_fixtures",)


def _lint_fixtures(rule):
    return lint_paths(REPO, dirs=FIXTURES, rules=[rule])


# ------------------------------------------------------------------ #
# AST rules vs planted fixtures
# ------------------------------------------------------------------ #


def test_mesh_rule_catches_planted_constructions():
    got = _lint_fixtures(MeshConstructionRule())
    hits = [f for f in got if f.rule == "mesh-construction"]
    assert len(hits) == 2, got
    assert all(f.path.endswith("fixture_mesh.py") for f in hits)
    assert all(f.severity == "error" for f in hits)


def test_mesh_rule_exempts_construction_site():
    # the one allowed site must produce zero findings
    got = lint_paths(REPO, dirs=("deeperspeed_tpu/sharding",),
                     rules=[MeshConstructionRule()])
    assert got == []


def test_hostsync_rule_catches_planted_syncs():
    got = _lint_fixtures(HostSyncInJitRule())
    hits = [f for f in got if f.rule == "host-sync-in-jit"]
    assert len(hits) == 3, got
    assert all(f.path.endswith("fixture_hostsync.py") for f in hits)
    # the host-side helper must NOT be flagged
    lines = open(os.path.join(REPO, FIXTURES[0],
                              "fixture_hostsync.py")).read().splitlines()
    for f in hits:
        assert "host_side_ok" not in lines[f.line - 1]


def test_prng_rule_catches_planted_key():
    got = _lint_fixtures(PRNGKeyInTracedRule())
    hits = [f for f in got if f.rule == "prngkey-in-traced"]
    assert len(hits) == 1, got
    assert hits[0].path.endswith("fixture_prng.py")


def test_config_rule_catches_undeclared_key():
    got = _lint_fixtures(ConfigKeyUndeclaredRule())
    hits = [f for f in got if f.rule == "config-key-undeclared"]
    assert len(hits) == 1, got
    assert hits[0].detail["key"] == "mystery_knob"


def test_event_rule_both_directions():
    rule = TraceEventNamesRule(schemas={"x/s": ("a",)},
                               prefixes=("x/",),
                               names={"known_lone"})
    got = _lint_fixtures(rule)
    errors = [f for f in got if f.severity == "error"]
    warnings = [f for f in got if f.severity == "warning"]
    # forward: emitted but unregistered
    assert any(f.detail and f.detail.get("name") == "bogus/evt"
               for f in errors), got
    # reverse: registered but never emitted
    assert any(f.detail and f.detail.get("name") == "known_lone"
               for f in warnings), got
    # the registered schema name and the dynamic x/ emission are fine
    assert not any(f.detail and f.detail.get("name") in ("x/s",)
                   for f in errors)


def test_repo_lint_clean_with_committed_suppressions():
    """The acceptance gate: the full AST lint of the repo, after this
    PR's fixes and with the committed suppression file, has zero
    unsuppressed findings — which also proves monitor/validate.py's
    registry and the emitting code agree in BOTH directions (any
    disagreement is a trace-event-names finding)."""
    findings = lint_paths(REPO)
    sups = load_suppressions(os.path.join(REPO,
                                          "ANALYSIS_SUPPRESSIONS.json"))
    kept, suppressed = apply_suppressions(findings, sups)
    assert kept == [], [f.to_dict() for f in kept]
    # two documented PRNGKey waivers remain: the engine.py one retired
    # when request_sample_key became a delegate to
    # models.speculative.engine_sample_key (plain host function, so the
    # constant base key no longer sits inside a traced program)
    assert len(suppressed) == 2


# ------------------------------------------------------------------ #
# suppression machinery
# ------------------------------------------------------------------ #


def test_suppression_reason_is_mandatory(tmp_path):
    p = tmp_path / "sup.json"
    p.write_text(json.dumps([{"rule": "x", "path": "y", "reason": ""}]))
    with pytest.raises(SuppressionError):
        load_suppressions(str(p))
    p.write_text(json.dumps([{"rule": "x", "path": "y"}]))
    with pytest.raises(SuppressionError):
        load_suppressions(str(p))


def test_suppression_matching_and_used_marking(tmp_path):
    p = tmp_path / "sup.json"
    p.write_text(json.dumps([
        {"rule": "r1", "path": "a.py", "reason": "because"},
        {"rule": "r1", "path": "b.py", "line": 7, "reason": "pinned"},
    ]))
    sups = load_suppressions(str(p))
    f1 = Finding("r1", "error", "a.py", 3, "m")
    f2 = Finding("r1", "error", "b.py", 8, "m")  # line mismatch
    kept, suppressed = apply_suppressions([f1, f2], sups)
    assert [f.path for f in kept] == ["b.py"]
    assert [f.path for f, _ in suppressed] == ["a.py"]
    assert sups[0].used and not sups[1].used


# ------------------------------------------------------------------ #
# HLO-level checks on real lowerings
# ------------------------------------------------------------------ #


def test_real_train_step_donations_alias():
    """The shipped fused train step's donate_argnums must survive into
    the compiled executable as input-output aliases."""
    engine, *_ = deepspeed.initialize(
        model=lambda p, b: jnp.mean((b @ p["w"]) ** 2),
        model_parameters={"w": jnp.zeros((8, 4), jnp.float32)},
        config_params={"train_batch_size": max(8, jax.device_count()),
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}}})
    raw = np.ones((max(8, jax.device_count()), 8), np.float32)
    engine.train_batch(batch=raw)
    batch = engine._pack_pld(engine._place_batch(raw))
    args = (engine.state, batch, np.float32(1e-3), engine._rng_args())
    fn = engine._train_batch_fn()

    from deeperspeed_tpu.analysis.hlo import _abstractify, _donated_leaves
    a_args, a_kw = _abstractify(args, {})
    lowered = fn.lower(*a_args, **a_kw)
    donated = _donated_leaves(lowered)
    assert donated > 0, "train step no longer donates its state?"
    pairs = count_alias_pairs(lowered.compile().as_text())
    assert pairs > 0, "declared donations never became aliases"

    findings = audit_program(ProgramSpec("engine/train_step", fn, args))
    assert not [f for f in findings if f.rule.startswith("donation")], \
        [f.to_dict() for f in findings]


def test_broken_donation_is_caught():
    # donated arg that cannot alias any output (shape/dtype mismatch):
    # XLA silently drops it — the audit must not
    bad = jax.jit(lambda big, s: s * 2.0, donate_argnums=(0,))
    findings = audit_program(ProgramSpec(
        "t/bad", bad, (jnp.zeros((64, 64)), jnp.zeros(8))))
    rules = {f.rule: f.severity for f in findings}
    assert rules.get("donation-dropped") == "error", findings


def test_host_callback_flagged_in_hot_path():
    dbg = jax.jit(lambda x: (jax.debug.print("x={x}", x=x), x * 2)[1])
    findings = audit_program(ProgramSpec("t/dbg", dbg, (jnp.zeros(8),)))
    assert any(f.rule == "host-callback" and f.severity == "error"
               for f in findings), findings
    # cold path: same program, info only
    findings = audit_program(ProgramSpec("t/dbg", dbg, (jnp.zeros(8),),
                                         hot=False))
    assert any(f.rule == "host-callback" and f.severity == "info"
               for f in findings)


def test_collective_axis_checked_against_mesh():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deeperspeed_tpu.sharding.mesh import make_mesh

    mesh = make_mesh(np.array(jax.devices()[:1]), ("data",))
    fn = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P()))
    x = jnp.zeros((8,), jnp.float32)
    # audited against its own mesh: clean
    ok = audit_program(ProgramSpec("t/coll", fn, (x,), mesh=mesh))
    assert not [f for f in ok if f.rule.startswith("collective")], ok
    # audited against a mesh without the axis: error
    other = make_mesh(np.array(jax.devices()[:1]), ("tp",))
    bad = audit_program(ProgramSpec("t/coll", fn, (x,), mesh=other))
    assert any(f.rule == "collective-axis" and f.severity == "error"
               for f in bad), bad


def test_fp64_flagged():
    jax.config.update("jax_enable_x64", True)
    try:
        fn = jax.jit(lambda x: x * np.float64(2.0))
        findings = audit_program(ProgramSpec(
            "t/f64", fn, (jnp.zeros(4, jnp.float64),)))
    finally:
        jax.config.update("jax_enable_x64", False)
    assert any(f.rule == "fp64-in-program" for f in findings), findings


def test_weak_promotion_flagged():
    fn = jax.jit(lambda a, b: a + b)
    findings = audit_program(ProgramSpec(
        "t/promo", fn,
        (jnp.zeros(4, jnp.bfloat16), jnp.zeros(4, jnp.float32))))
    assert any(f.rule == "weak-promotion" for f in findings), findings
    # bf16 + python scalar stays bf16: no finding
    fn2 = jax.jit(lambda a: a * 3.0 + 1.0)
    clean = audit_program(ProgramSpec(
        "t/weak-ok", fn2, (jnp.zeros(4, jnp.bfloat16),)))
    assert not [f for f in clean if f.rule == "weak-promotion"], clean


def test_hlo_text_parsers():
    hlo = """HloModule m, input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }
  %ag = f32[8,1024] all-gather(f32[1,1024] %p), dimensions={0}
  %ag2 = (bf16[16], bf16[128]) all-gather-start(bf16[16] %q)
"""
    assert count_alias_pairs(hlo) == 2
    sizes = all_gather_result_bytes(hlo)
    assert 8 * 1024 * 4 in sizes      # f32[8,1024]
    assert 128 * 2 in sizes           # bf16[128] (largest of the tuple)
    assert count_alias_pairs("HloModule m\n") == 0


# ------------------------------------------------------------------ #
# mesh-construction fixes: placement regression
# ------------------------------------------------------------------ #


def test_stage_meshes_placement_unchanged():
    """The make_mesh rewrite of pipe/engine.py's _stage_meshes must
    place stages on exactly the devices the raw Mesh() code did."""
    from jax.sharding import Mesh

    from deeperspeed_tpu.runtime.pipe.engine import _stage_meshes

    # no-mesh path (old line 83): round-robin over devices
    devices = jax.devices()
    for num_stages in (1, 2):
        got = _stage_meshes(None, num_stages)
        assert len(got) == num_stages
        for s, m in enumerate(got):
            ref = Mesh(np.array([devices[s % len(devices)]]), ("data",))
            assert m.axis_names == ref.axis_names
            assert (m.devices == ref.devices).all()

    # pipe-mesh path (old line 67): slice along the pipe axis. Both the
    # 2-D ('pipe','data') shape build_mesh produces and the degenerate
    # 1-D pipe-only mesh must land stages on the sliced devices.
    pipe_mesh = Mesh(np.array(devices).reshape(1, len(devices)),
                     ("pipe", "data"))
    got = _stage_meshes(pipe_mesh, 1)
    assert got[0].axis_names == ("data",)
    assert (got[0].devices == np.array(devices)).all()

    pipe_only = Mesh(np.array(devices[:1]), ("pipe",))
    got = _stage_meshes(pipe_only, 1)
    assert got[0].axis_names == ("data",)
    assert (got[0].devices == np.array(devices[:1])).all()


def test_zero_init_default_mesh_unchanged():
    """zero.Init()'s default mesh (old init_ctx.py:44) must still span
    every device on the data axis."""
    from deeperspeed_tpu.runtime.zero.init_ctx import Init

    ctx = Init(enabled=False)
    assert ctx.mesh.axis_names == ("data",)
    assert (ctx.mesh.devices == np.array(jax.devices())).all()


# ------------------------------------------------------------------ #
# CLI exit codes
# ------------------------------------------------------------------ #


def _run_cli(*args, cwd=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "deeperspeed_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd or REPO, env=env,
        timeout=300)


def test_cli_lint_level_exits_zero_on_repo():
    r = _run_cli("--no-programs")
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_exits_nonzero_on_planted_violation(tmp_path):
    # a fake repo root whose package contains one planted violation
    pkg = tmp_path / "deeperspeed_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "from jax.sharding import Mesh\n"
        "def build(devs):\n"
        "    return Mesh(devs, ('data',))\n")
    r = _run_cli("--no-programs", "--root", str(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "mesh-construction" in r.stdout


def test_cli_rejects_reasonless_suppression(tmp_path):
    pkg = tmp_path / "deeperspeed_tpu"
    pkg.mkdir()
    (pkg / "ok.py").write_text("x = 1\n")
    (tmp_path / "ANALYSIS_SUPPRESSIONS.json").write_text(
        json.dumps([{"rule": "r", "path": "p"}]))
    r = _run_cli("--no-programs", "--root", str(tmp_path))
    assert r.returncode == 2, r.stdout + r.stderr
    assert "reason" in r.stderr


@pytest.mark.slow
def test_cli_full_repo_exits_zero():
    """End-to-end acceptance: both levels on the real repo, committed
    suppressions, rc 0. Slow: compiles three toy engines."""
    r = _run_cli()
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 error(s)" in r.stdout
