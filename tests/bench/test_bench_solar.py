"""Solar Open 2's part of the benchmark at a toy size on the CPU: the
``serve_reason`` runner end to end (the check passes on the sound program;
the float8 control and the eight references that leave a part out come out
over a limit), the new per-layer metrics' readers on hand-written events,
and the configuration's cut against the published row. The toy window is
DRAINED and the check samples the schedule's first requests, so what is
compared does not depend on the machine's load. No time or rate is
asserted here, and nothing pins where in ``BENCHMARK.json`` the cell
stands or how many stand beside it."""

import importlib
import json
import os
import types

import jax
import pytest

from benchmark import device
from benchmark import manifest as mf
from benchmark import peaks_mellum as pm
from benchmark import peaks_solar as psl
from benchmark import profiling
from benchmark import run as brun
from benchmark import trace as tr
from benchmark.reducers import (call_roofline_pct, counter,
                                experts_roofline_pct, full_list_roofline_pct)
from benchmark.runners import serve_reason

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "toy-solar.serve-reason"
REAL = "solar-open2-250b.serve-reason"
CONFIG = "solar-open2-250b"
ALSO = ["prefill_chunk_device_ms.solar", "chunk_gap_share_pct.solar",
        "slot_occupancy_pct", "state_gib_per_step.solar",
        "kv_pages_per_slot.solar", "experts_touched_pct.solar",
        "experts_away_pct.solar", "expert_load_max_over_mean.solar",
        "kda_chunk_roofline", "kda_row_update_roofline",
        "moe_experts_roofline.solar", "paged_attn_roofline.solar"]
CONTROLS = ["fp8", "nodelta", "headdecay", "posbeta", "noconv", "noshared",
            "nobias", "nogate", "softmaxroute"]


def context(seed, seconds=1.0):
    man = mf.Manifest(os.path.join(DATA, "BENCHMARK.toy-solar.json"),
                      extra_dirs=[mf.BENCH_DIR])
    devs = jax.devices()[:1]
    lines = []
    ctx = brun.build_context(man, CELL, seed, seconds, 0, devs,
                             device.describe(devs), lines.append)
    ctx.device["kind"] = "TPU v5 lite"
    ctx.lines = lines
    return ctx


def line(ctx, start):
    return next(l for l in ctx.lines if l.startswith(start))


# ------------------------------------------------------------------ #
# the runner
# ------------------------------------------------------------------ #


def test_toy_cell_runs_through_the_harness():
    ctx = context(3_000_000_031)
    out = brun.run_cell(ctx)
    assert out["failed"] == 0
    assert out["attempted"] == round(ctx.traffic["arrivals"]["rate_per_s"])
    assert set(out["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert "compiles inside the window: 0" in line(ctx, "chunk-gap share")
    assert "delta rule runs as xla" in line(ctx, "warmed")
    c = ctx.spans.counters
    assert 0.0 < c["chunk_gap_share_pct"] < 100.0
    # prompts of 5 to 70 in pages of 4: a page for every 4 positions, for
    # the 2 GQA layers alone
    assert 2.0 <= c["kv_pages_per_slot"] <= 24.0
    # 4 slots x 6 KDA layers x (4 heads x 16 x 16 float32 + 3 taps x 192
    # channels float32), read and written a step
    state = 4 * 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert c["state_gib_per_step"] == pytest.approx(2 * state / 2 ** 30)
    # 2 of 16 experts a live lane, 4 held: most assignments leave
    assert 40.0 <= c["experts_away_pct"] <= 100.0
    assert 0.0 < c["experts_touched_pct"] <= 100.0
    assert c["expert_load_max_over_mean"] >= 1.0
    assert c["kv_pool_bytes"] == 2 * 2 * 97 * 2 * 4 * 16 * 4


def test_the_drained_toy_cell_agrees_and_every_control_reads_over_a_limit():
    """The first requests of the schedule, drained: the same sample
    whatever else the machine runs."""
    ctx = context(7)
    assert ctx.cell_file["check"]["controls"] == CONTROLS
    out = serve_reason.run(ctx, CONTROLS, drain=True)
    limits = ctx.cell_file["check"]["limits"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["check"]["widest_gap"] <= limits["served_logit_gap"]
    assert out["check"]["tokens"] >= ctx.cell_file["check"]["min_served_tokens"]
    assert list(out["check"]["controls"]) == CONTROLS
    for name in CONTROLS:
        assert out["check"]["controls"][name] > limits["served_logit_gap"] \
            or out["check"]["controls_request_mean"][name] \
            > limits["served_logit_gap_request_mean"], name


# ------------------------------------------------------------------ #
# the new metrics' readers, on hand-written events
# ------------------------------------------------------------------ #


def _op(name, start, dur):
    return tr.Ev(name, start, dur, "/device:TPU:0", tr.OPS_LINE)


def _span(name, start, dur, **args):
    text = name + " " + " ".join(f"{k}:{v}" for k, v in args.items())
    return tr.Ev(name, start, dur, "/host:CPU", "python3", text)


def metric(name):
    return mf.Manifest().metric_file(name)


WINDOW = tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")
OTHER = tr.Ev("%fusion.1 = bf16[8]{0} fusion()", 5.0, 1.0, "/device:TPU:0",
              tr.OPS_LINE)
CHUNK = ("%kda_chunk.3 = (f32[1024,8192]{1,0}, f32[64,128,128]{2,1,0}) "
         "custom-call(f32[1024,8192] %q)")
ROWS = ("%kda_row_update.5 = (f32[3,48,64,128,128]{4,3,2,1,0}, "
        "f32[48,8,8,128]{3,2,1,0}) custom-call(s32[1] %l)")
GMM = "%gmm.7 = bf16[384,1280]{1,0} custom-call(s32[] %a, s32[161] %b)"
GMM_DOWN = "%gmm.8 = bf16[384,4096]{1,0} custom-call(s32[] %a, s32[161] %b)"
LIST = ("%paged_sparse_attn_slots.16 = bf16[48,64,128]{2,1,0} "
        "custom-call(s32[1] %l, s32[48] %n)")


def run_of(events):
    return {"trace": profiling.traced_run(events + [WINDOW], 1), "notes": [],
            "spans": profiling.Spans(), "device": {"kind": "TPU v5 lite"}}


def test_the_kernels_rooflines_count_what_their_shapes_need():
    chunk, rows = metric("kda_chunk_roofline"), metric("kda_row_update_roofline")
    c = psl.kda_chunk_call(**chunk["params"]["shape"])
    # 16 blocks x 64 heads: two score matrices, the solve and the scores'
    # products with V', three products with the state
    assert c["flops"] == 1024 * (4 * 64 * 64 * 128 + 4 * 64 * 64 * 128
                                 + 6 * 64 * 128 * 128)
    assert c["bytes"] == 4 * 1024 * 64 * 6 * 128 + 2 * 4 * 64 * 128 * 128
    r = psl.kda_row_update_call(**rows["params"]["shape"])
    assert r["bytes"] == 4 * 48 * 64 * (2 * 128 * 128 + 6 * 128)
    assert r["bytes"] / 2 ** 20 == pytest.approx(384, rel=0.03)
    ops = [_op(CHUNK, 1.0 + 0.01 * i, 3e-3) for i in range(3)] \
        + [_op(ROWS, 2.0 + 0.01 * i, 1e-3) for i in range(6)]
    run = run_of(ops)
    got = call_roofline_pct.read(run, chunk["params"])
    least = max(c["bytes"] / 819e9, c["flops"] / 197e12)
    assert got == pytest.approx(100 * 3 * least / 9e-3) and 0 < got < 100
    got = call_roofline_pct.read(run, rows["params"])
    assert got == pytest.approx(100 * 6 * r["bytes"] / 819e9 / 6e-3)
    assert 0 < got < 100 and "memory-bound" in run["notes"][-1]
    # a program without the kernels (the parent): nothing, and no error
    for spec in (chunk, rows):
        assert call_roofline_pct.read(run_of([OTHER]), spec["params"]) is None


def test_the_held_experts_roofline_reads_the_slices_own_counts():
    spec = metric("moe_experts_roofline.solar")
    # ONE decode step's 4 layers: two products to 1,280 and one to 4,096
    ops = [_op(n, 1.0 + 1e-3 * i, 3e-4)
           for i, n in enumerate([GMM, GMM, GMM_DOWN] * 4)]
    # that step touched 4 x 22 held experts with 4 x 37 assignments here
    emit = _span("serving/decode/emit", 3.0, 1e-4, experts=88,
                 assignments=148, max_load=5, away=1036)
    run = run_of(ops + [emit])
    got = experts_roofline_pct.read(run, spec["params"])
    one = lambda k, n: pm.experts_product(37, 22, k, n, 2)
    least = 4 * (2 * one(4096, 1280)["bytes"] + one(1280, 4096)["bytes"]) / 819e9
    assert got == pytest.approx(100 * least / (12 * 3e-4))
    assert 0 < got < 100 and "22.0 of 40 experts touched" in run["notes"][0]
    assert experts_roofline_pct.read(run_of([OTHER, emit]),
                                     spec["params"]) is None
    assert experts_roofline_pct.read(run_of(ops), spec["params"]) is None


def test_the_page_lists_roofline_shares_a_steps_pages_among_its_calls():
    spec = metric("paged_attn_roofline.solar")
    ops = [_op(LIST, 1.0 + 1e-2 * i, 2e-3) for i in range(2)]
    # two steps whose live slots list 1,000 pages of 256 KiB each
    spans = [_span("serving/decode/dispatch", 0.9 + 0.1 * i, 1e-4,
                   full_pages=1000, state_rows=144) for i in range(2)]
    run = run_of(ops + spans)
    got = full_list_roofline_pct.read(run, spec["params"])
    want = pm.slot_list_call(2000, 96, 64, 8, 128, 64, 2)
    assert want["bytes"] > 2000 * 2 ** 18
    assert got == pytest.approx(100 * want["bytes"] / 819e9 / 4e-3)
    assert 0 < got < 100 and "2 calls" in run["notes"][0]
    # no kernel, or no count in the slice: no value, a note, no error
    for events in ([OTHER] + spans, ops):
        run = run_of(events)
        assert full_list_roofline_pct.read(run, spec["params"]) is None
        assert "no value" in run["notes"][0]


@pytest.mark.parametrize("name,key", [
    ("chunk_gap_share_pct.solar", "chunk_gap_share_pct"),
    ("state_gib_per_step.solar", "state_gib_per_step"),
    ("kv_pages_per_slot.solar", "kv_pages_per_slot"),
    ("experts_touched_pct.solar", "experts_touched_pct"),
    ("experts_away_pct.solar", "experts_away_pct"),
    ("expert_load_max_over_mean.solar", "expert_load_max_over_mean")])
def test_counter_metrics_read_the_runners_counters(name, key):
    spec = metric(name)
    spans = types.SimpleNamespace(counters={key: 0.25})
    assert counter.read({"spans": spans}, spec["params"]) == 0.25
    assert counter.read({"spans": types.SimpleNamespace(counters={})},
                        spec["params"]) is None


def test_also_read_prints_each_metric_of_a_traced_run():
    from benchmark.runners import serve_long

    man = mf.Manifest()
    events = [_op(GMM, 1.0, 4e-4), _op(LIST, 1.1, 2e-3), _op(CHUNK, 1.2, 3e-3),
              _op(ROWS, 1.3, 1e-3),
              _span("serving/decode/emit", 1.2, 1e-4, experts=88,
                    assignments=148, max_load=5, away=1036),
              _span("serving/decode/dispatch", 0.9, 1e-4, full_pages=1000,
                    state_rows=144),
              tr.Ev("jit_ds_prefill_chunk(7)", 1.0, 0.031, "/device:TPU:0",
                    "XLA Modules"),
              tr.Ev("jit_ds_prefill_chunk(7)", 2.0, 0.033, "/device:TPU:0",
                    "XLA Modules"), WINDOW]
    spans = profiling.Spans()
    spans.counters.update({"chunk_gap_share_pct": 14.0, "slot_occupancy": 0.5,
                           "kv_pages_per_slot": 80.5, "state_gib_per_step": 1.1,
                           "experts_touched_pct": 55.0, "experts_away_pct": 87.4,
                           "expert_load_max_over_mean": 2.5})
    said = []
    ctx = types.SimpleNamespace(
        spans=spans, device={"kind": "TPU v5 lite"}, notes=[], devices=[0],
        cell_file=man.workload_file(REAL), manifest=man, say=said.append,
        profiler=types.SimpleNamespace(events=lambda: events))
    assert ctx.cell_file["also_read"] == ALSO
    got = serve_long.read_also(ctx, ALSO)
    assert got["prefill_chunk_device_ms.solar"] == pytest.approx(32.0)
    assert got["chunk_gap_share_pct.solar"] == 14.0
    assert got["state_gib_per_step.solar"] == 1.1
    assert got["experts_away_pct.solar"] == 87.4
    assert got["slot_occupancy_pct"] == 50.0
    for name in ("kda_chunk_roofline", "kda_row_update_roofline",
                 "moe_experts_roofline.solar", "paged_attn_roofline.solar"):
        assert 0 < got[name] < 100, name
    assert len(said) == len(ALSO) and all(l.startswith("metric ") for l in said)
    # a slice that holds none of it (the parent's): every reader returns
    # no value and none raises, bar the program's own time, which says so
    ctx.profiler = types.SimpleNamespace(events=lambda: [OTHER, WINDOW])
    ctx.spans = profiling.Spans()
    rest = [n for n in ALSO if n != "prefill_chunk_device_ms.solar"]
    assert set(serve_long.read_also(ctx, rest).values()) == {None}


# ------------------------------------------------------------------ #
# the configuration and the manifest
# ------------------------------------------------------------------ #


def published():
    """The catalog row's ``config`` (model-configs guide), every key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        return next(json.loads(l) for l in f if '"Solar-Open2-250B"' in l)


def test_every_published_key_is_in_the_file_and_only_the_cut_differs():
    row = published()
    cfg = mf.Manifest().config(CONFIG)
    differ = {k for k in row["config"] if cfg[k] != row["config"][k]}
    assert differ == {"n_routed_experts", "vocab_size"}
    assert set(cfg["reduced"]) == differ | {"num_layers"}
    assert cfg["source"] == row["source_url"]
    assert cfg["share"]["published_n_routed_experts"] \
        == row["config"]["n_routed_experts"] == cfg["share"]["experts_routed_over"]
    assert cfg["share"]["published_vocab_size"] == row["config"]["vocab_size"]


def test_the_file_states_its_cut():
    cfg = mf.Manifest().config(CONFIG)
    assert cfg["num_layers"] == 4 and cfg["num_hidden_layers"] == 48
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert cfg["family"] == "solar_open2"
    # one whole period of the published pattern, the published 1 : 3
    assert [i in cfg["gqa_layers"] for i in range(4)] == [True, False, False,
                                                          False]
    assert (cfg["n_routed_experts"], cfg["share"]["chips_a_layer"],
            cfg["share"]["first_expert"]) == (40, 8, 0)
    assert cfg["vocab_size"] * 8 == cfg["share"]["published_vocab_size"]
    for key in ("num_layers", "n_routed_experts", "vocab_size", "gqa_gate",
                "kda", "low_rank_pairs", "routing", "convolution", "weights",
                "unused_keys", "arithmetic"):
        assert key in cfg["assumed"], key
    assert "8 chips" in cfg["deployment"] and "6.16 GiB" in cfg["deployment"]


def test_the_parameter_count_of_the_cut():
    from benchmark.refs import solar_open2 as ref

    cfg = mf.Manifest().config(CONFIG)
    leaves = jax.tree.leaves(ref.leaf_specs(cfg),
                             is_leaf=lambda s: hasattr(s, "shape"))
    n = 0
    for s in leaves:
        k = 1
        for d in s.shape:
            k *= d
        n += k
    assert n == cfg["parameters"] == 3_308_353_344
    D, F = 4096, 1280
    expert = 3 * D * F
    mlp = 40 * expert + D * 320 + 320 + expert      # held, router, bias, shared
    gqa = 2 * D + D * 80 * 128 + 2 * D * 8192 + mlp
    kda = 2 * D + D * 24576 + 4 * 24576 + 2 * (D * 128 + 128 * 8192) + 8192 \
        + 64 + D * 64 + 128 + 8192 * D + mlp
    assert (expert, mlp) == (15_728_640, 646_185_280)
    assert n == gqa + 3 * kda + 2 * 24576 * D + D


def test_the_program_is_handed_the_published_sizes():
    from benchmark.adapters import solar_open2 as adapter

    cfg = adapter.model_config(mf.Manifest().config(CONFIG))
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size) == (4, 4096, 64, 8, 128, 1280, 24576)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_normalize_gates,
            cfg.moe_held, cfg.moe_shared, cfg.moe_rule) == (
                320, 8, True, (0, 40), 1, "sigmoid_bias")
    assert cfg.layer_kinds == ("full_attn", "kda", "kda", "kda")
    k = cfg.kda
    assert (k.n_heads, k.head_k, k.head_v, k.d_conv, k.low_rank,
            k.beta_scale) == (64, 128, 128, 4, 128, 2.0)
    assert not cfg.gqa.rotary and cfg.gqa.out_gate and not cfg.gqa.qk_norm
    assert cfg.layernorm_eps == 1e-5 and not cfg.tie_embeddings
    assert cfg.max_seq == 1048576 and cfg.fp32_logits


def test_the_manifest_holds_the_cell_its_configuration_and_its_files():
    """Found BY NAME, wherever they stand and whatever stands beside them."""
    data = mf.load_json(os.path.join(mf.ROOT, "BENCHMARK.json"))
    assert mf.validate(data) == []
    man = mf.Manifest()
    cell = man.cell(REAL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "serve-reason")
    entry = next(c for c in data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert {m["name"] for m in man.metrics_for(REAL, "end_to_end")} == {
        "tpot_p95_ms", "setup_s"}
    assert {m["name"] for m in man.metrics_for(REAL, "per_layer")} == {
        "step_host_ms.serve", "decode_step_device_ms", "prefill_share_pct",
        "device_idle_pct.serve", "hbm_peak_gib.serve"}
    for name in man.workload_file(REAL)["also_read"]:
        spec = man.metric_file(name)
        assert callable(importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}").read)
    for sub in ("adapters/solar_open2.py", "refs/solar_open2.py",
                "peaks_solar.py", "runners/serve_reason.py",
                "traffic/serve-reason.json", f"workloads/{REAL}.json"):
        assert os.path.exists(os.path.join(mf.BENCH_DIR, sub)), sub


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(mf.BENCH_DIR, "refs", "solar_open2.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] \
        + [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
           for a in n.names]
    assert not any("deeperspeed" in n for n in names)


def test_the_cells_parameters_are_the_issues():
    man = mf.Manifest()
    w, t = man.workload_file(REAL), man.traffic("serve-reason")
    assert w["serving"] == {"num_slots": 48, "block_size": 64,
                            "num_blocks": 15361, "max_seq_len": 20480,
                            "max_new_tokens": 4096, "prefill_chunk": 1024,
                            "prefill_token_budget": 1024}
    assert w["weights_dtype"] == "bfloat16" and w["runner"] == "serve_reason"
    assert t["kind"] == "serve_reason" and t["temperature"] == 0.0
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                  "sigma": 0.8, "min": 512, "max": 16384}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                  "sigma": 0.7, "min": 128, "max": 4096}
    assert t["arrivals"]["process"] == "poisson"
    # a whole number of requests in the window, and the file says where
    # the rate comes from
    n = t["arrivals"]["rate_per_s"] * 40
    assert abs(n - round(n)) < 1e-9 and "knee" in t["why"]
    # one order of arrivals for every seed, and the file says why
    assert isinstance(t["arrivals"]["deal"], int) and "deal" in t["why"]
    # the longest request fits a slot, and 48 full slots fit the pool
    assert 16384 + 4096 <= w["serving"]["max_seq_len"]
    assert w["serving"]["num_blocks"] == 48 * 320 + 1
    assert w["check"]["controls"] == CONTROLS
    limits = w["check"]["limits"]
    assert set(limits) == {"served_logit_gap", "served_logit_gap_request_mean"}
    assert 0 < limits["served_logit_gap_request_mean"] \
        < limits["served_logit_gap"] < 10
    assert len(w["check"]["why"]) > 200 and len(t["why"]) > 200
