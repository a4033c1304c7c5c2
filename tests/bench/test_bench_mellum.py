"""Mellum 2's part of the benchmark at a toy size on the CPU: the
``serve_code`` runner end to end (the check passes on the sound program;
the float8 control and the four references that leave a part out come out
over the limit, and so does a PROGRAM that forgets its window), the new
per-layer metrics' readers on hand-written events, and the configuration's
cut against the published row. The toy window is DRAINED and the check
samples the schedule's first requests, so what is compared does not depend
on the machine's load. No time or rate is asserted here, and nothing pins
where in ``BENCHMARK.json`` the cell stands or how many stand beside it."""

import importlib
import json
import os
import types

import jax
import pytest

from benchmark import device
from benchmark import manifest as mf
from benchmark import peaks_mellum as pm
from benchmark import profiling
from benchmark import run as brun
from benchmark import trace as tr
from benchmark.reducers import (counter, experts_roofline_pct,
                                slot_list_roofline_pct)
from benchmark.runners import serve_code

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "toy-mellum.serve-code"
REAL = "mellum2-12b-a2.5b.serve-code"
CONFIG = "mellum2-12b-a2.5b"
ALSO = ["prefill_chunk_device_ms.mellum", "chunk_gap_share_pct.mellum",
        "slot_occupancy_pct", "kv_pages_per_slot.mellum",
        "experts_touched_pct.mellum", "expert_load_max_over_mean.mellum",
        "moe_experts_roofline", "paged_attn_roofline.mellum"]


def context(seed, seconds=1.0):
    man = mf.Manifest(os.path.join(DATA, "BENCHMARK.toy-mellum.json"),
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
    c = ctx.spans.counters
    assert 0.0 < c["chunk_gap_share_pct"] < 100.0
    # prompts of 5 to 70 over a window of 8 in pages of 4: a live slot
    # holds the ring's 2 pages and a page of every key for every 4
    # positions
    assert 1.0 < c["kv_window_pages_per_slot"] <= 2.0
    assert 2.0 <= c["kv_full_pages_per_slot"] <= 24.0
    assert c["kv_pages_per_slot"] == pytest.approx(
        c["kv_window_pages_per_slot"] + c["kv_full_pages_per_slot"])
    assert c["window_wraps"] > 0
    # 2 of 8 experts a live lane: at least a quarter of a layer's experts
    assert 25.0 <= c["experts_touched_pct"] <= 100.0
    assert c["expert_load_max_over_mean"] >= 1.0
    assert c["kv_pool_bytes"] == sum(pm.pool_bytes(4, 96, 4, 8, 2, 6, 2, 16, 4))


def test_the_drained_toy_cell_agrees_and_every_control_reads_over_the_limit():
    """The first requests of the schedule, drained: the same sample
    whatever else the machine runs."""
    ctx = context(7)
    out = serve_code.run(ctx, ctx.cell_file["check"]["controls"], drain=True)
    limit = ctx.cell_file["check"]["limits"]["served_logit_gap"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["check"]["widest_gap"] <= limit
    assert out["check"]["tokens"] >= ctx.cell_file["check"]["min_served_tokens"]
    assert list(out["check"]["controls"]) == [
        "fp8", "nowindow", "noyarn", "rawgates", "noqknorm"]
    assert all(g > limit for g in out["check"]["controls"].values())


def test_a_program_that_forgets_its_window_is_not_correct(monkeypatch):
    """Leaving mathematics out inside the tolerance is not a speed-up: a
    program whose window layers are told a window twice the model's
    (its ring then holds, and its queries see, keys the model's window has
    left behind)."""
    from benchmark.adapters import mellum as adapter
    from deeperspeed_tpu.models.gpt import GroupedAttnConfig

    real = adapter.model_config

    def wide(config, **overrides):
        cfg = real(config)
        g = cfg.gqa
        return real(config, gqa=GroupedAttnConfig(
            2 * g.window, g.qk_norm, g.full_rope, g.window_rope), **overrides)

    monkeypatch.setattr(adapter, "model_config", wide)
    ctx = context(11)
    out = serve_code.run(ctx, drain=True)
    assert out["correct"] is False and out["failed"] == 0
    assert "OVER" in line(ctx, "check served_logit_gap")


def test_a_dealt_schedule_is_one_order_for_every_seed():
    """Without ``arrivals.deal`` a seed orders its own schedule (the
    generator's); with it every seed offers the same lengths and gaps in
    ONE order and draws its own token ids."""
    from benchmark import generator as tg

    mix = mf.load_json(os.path.join(DATA, "traffic", "toy-code.json"))
    assert "deal" not in mix["arrivals"]
    a, b = (serve_code.schedule(mix, s, 1.0, 96) for s in (1, 2))
    assert a == tg.serve_requests(mix, 1, 1.0, 96)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    dealt = dict(mix, arrivals=dict(mix["arrivals"], deal=5))
    a, b = (serve_code.schedule(dealt, s, 1.0, 96) for s in (1, 2))
    assert [(len(r["prompt"]), r["max_new_tokens"], r["due_s"]) for r in a] \
        == [(len(r["prompt"]), r["max_new_tokens"], r["due_s"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


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


def run_of(events):
    return {"trace": profiling.traced_run(events + [WINDOW], 1), "notes": [],
            "spans": profiling.Spans(), "device": {"kind": "TPU v5 lite"}}


def test_experts_roofline_counts_the_touched_experts_of_the_slices_steps():
    spec = metric("moe_experts_roofline")
    up = "%gmm.7 = bf16[256,896]{1,0} custom-call(s32[] %a, s32[385] %b)"
    down = "%gmm.8 = bf16[256,2304]{1,0} custom-call(s32[] %a, s32[385] %b)"
    user = "%fusion.9 = bf16[256,2304]{1,0} fusion(bf16[256,2304] %gmm.8)"
    # ONE decode step's 8 layers: two products to 896 and one to 2,304 each
    ops = [_op(n, 1.0 + 1e-3 * i, 4e-4)
           for i, n in enumerate([up, up, down] * 8)] + [_op(user, 2.0, 1e-6)]
    # that step touched 8 x 48 experts with 8 x 160 assignments
    emit = _span("serving/decode/emit", 3.0, 1e-4, experts=384,
                 assignments=1280, max_load=9)
    run = run_of(ops + [emit])
    got = experts_roofline_pct.read(run, spec["params"])
    one = lambda k, n: pm.experts_product(160, 48, k, n, 2)
    assert one(2304, 896)["bytes"] == 2 * (48 * 2304 * 896 + 160 * 3200)
    assert one(2304, 896)["flops"] == 2 * 160 * 2304 * 896
    least = 8 * (2 * one(2304, 896)["bytes"] + one(896, 2304)["bytes"]) / 819e9
    assert got == pytest.approx(100 * least / (24 * 4e-4))
    assert 0 < got < 100 and "24 decode calls" in run["notes"][0]
    assert "48.0 of 64 experts touched" in run["notes"][0]
    # a chunk's calls are filed by their rows and counted from the counts
    # of the chunks the slice's steps read
    wide = "%gmm.2 = bf16[8192,896]{1,0} custom-call(s32[] %a)"
    emit2 = _span("serving/decode/emit", 3.0, 1e-4, experts=384,
                  assignments=1280, max_load=9, chunks=1, chunk_experts=512,
                  chunk_assignments=65536)
    run = run_of(ops + [_op(wide, 4.0, 8e-4), emit2])
    both = experts_roofline_pct.read(run, spec["params"])
    chunk = pm.experts_product(8192, 64, 2304, 896, 2)
    assert both == pytest.approx(100 * (least + max(
        chunk["bytes"] / 819e9, chunk["flops"] / 197e12)) / (24 * 4e-4 + 8e-4))
    # a program without the kernel, or without the counts (the parent):
    # nothing, and no error
    assert experts_roofline_pct.read(run_of([OTHER, emit]),
                                     spec["params"]) is None
    assert experts_roofline_pct.read(run_of(ops), spec["params"]) is None


def test_slot_list_roofline_shares_a_steps_listed_pages_among_its_calls():
    spec = metric("paged_attn_roofline.mellum")
    call = ("%paged_sparse_attn_slots.16 = bf16[32,32,128]{2,1,0} "
            "custom-call(s32[1] %l, s32[32] %n)")
    ops = [_op(call, 1.0 + 1e-3 * i, 2e-4) for i in range(8)]
    # the step's live slots list 2,000 pages of every key (each of the 2
    # full layers reads them) and 300 whole pages of their rings (each of
    # the 6 window layers)
    span = _span("serving/decode/dispatch", 0.9, 1e-4, full_pages=2000,
                 window_pages=300, wraps=0)
    run = run_of(ops + [span])
    got = slot_list_roofline_pct.read(run, spec["params"])
    pages = 2 * 2000 + 6 * 300
    nbytes = pages * 2 * 4 * 64 * 128 * 2 \
        + 8 * 32 * 32 * (2 * 128 * 2 + 128 * 4 + 2 * 128 * 4)
    assert pm.slot_list_call(pages, 8 * 32, 32, 4, 128, 64, 2)["bytes"] == nbytes
    assert got == pytest.approx(100 * nbytes / 819e9 / 16e-4)
    assert 0 < got < 100 and "8 calls" in run["notes"][0]
    assert slot_list_roofline_pct.read(run_of([OTHER, span]),
                                       spec["params"]) is None
    assert slot_list_roofline_pct.read(run_of(ops), spec["params"]) is None


@pytest.mark.parametrize("name,key", [
    ("chunk_gap_share_pct.mellum", "chunk_gap_share_pct"),
    ("kv_pages_per_slot.mellum", "kv_pages_per_slot"),
    ("experts_touched_pct.mellum", "experts_touched_pct"),
    ("expert_load_max_over_mean.mellum", "expert_load_max_over_mean")])
def test_counter_metrics_read_the_runners_counters(name, key):
    spec = metric(name)
    spans = types.SimpleNamespace(counters={key: 0.25})
    assert counter.read({"spans": spans}, spec["params"]) == 0.25
    assert counter.read({"spans": types.SimpleNamespace(counters={})},
                        spec["params"]) is None


def test_also_read_prints_each_metric_of_a_traced_run():
    from benchmark.runners import serve_long

    man = mf.Manifest()
    gmm = "%gmm.7 = bf16[256,896]{1,0} custom-call(s32[] %a)"
    call = "%paged_sparse_attn_slots.1 = bf16[32,32,128]{2,1,0} custom-call()"
    events = [_op(gmm, 1.0, 4e-4), _op(call, 1.1, 1e-4),
              _span("serving/decode/emit", 1.2, 1e-4, experts=384,
                    assignments=1280, max_load=9),
              _span("serving/decode/dispatch", 0.9, 1e-4, full_pages=2000,
                    window_pages=300),
              tr.Ev("jit_ds_prefill_chunk(7)", 1.0, 0.031, "/device:TPU:0",
                    "XLA Modules"),
              tr.Ev("jit_ds_prefill_chunk(7)", 2.0, 0.033, "/device:TPU:0",
                    "XLA Modules"), WINDOW]
    spans = profiling.Spans()
    spans.counters.update({"chunk_gap_share_pct": 31.0, "slot_occupancy": 0.5,
                           "kv_pages_per_slot": 180.5,
                           "experts_touched_pct": 88.0,
                           "expert_load_max_over_mean": 2.5})
    said = []
    ctx = types.SimpleNamespace(
        spans=spans, device={"kind": "TPU v5 lite"}, notes=[], devices=[0],
        cell_file=man.workload_file(REAL), manifest=man, say=said.append,
        profiler=types.SimpleNamespace(events=lambda: events))
    assert ctx.cell_file["also_read"] == ALSO
    got = serve_long.read_also(ctx, ALSO)
    assert got["prefill_chunk_device_ms.mellum"] == pytest.approx(32.0)
    assert got["chunk_gap_share_pct.mellum"] == 31.0
    assert got["kv_pages_per_slot.mellum"] == 180.5
    assert got["experts_touched_pct.mellum"] == 88.0
    assert got["slot_occupancy_pct"] == 50.0
    assert got["moe_experts_roofline"] > 0
    assert got["paged_attn_roofline.mellum"] > 0
    assert len(said) == 8 and all(l.startswith("metric ") for l in said)


# ------------------------------------------------------------------ #
# the configuration and the manifest
# ------------------------------------------------------------------ #


def published():
    """The catalog row's ``config`` (model-configs guide), every key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        return next(json.loads(l) for l in f
                    if '"Mellum2-12B-A2.5B-Instruct"' in l)


def test_every_published_key_is_in_the_file_unchanged():
    row = published()
    cfg = mf.Manifest().config(CONFIG)
    assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert cfg["source"] == row["source_url"]


def test_the_file_states_its_cut():
    cfg = mf.Manifest().config(CONFIG)
    assert cfg["num_layers"] == 8 and cfg["num_hidden_layers"] == 28
    assert cfg["reduced"] == ["num_layers"] and cfg["family"] == "mellum"
    # two whole periods of the published pattern
    assert cfg["layer_types"][:8] == (["sliding_attention"] * 3
                                      + ["full_attention"]) * 2
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["vocab_size"]) == (
                64, 8, 896, 98304)
    for key in ("num_layers", "qk_norm", "rotary", "mtp_head", "routing",
                "page_rule", "weights", "unused_keys", "arithmetic"):
        assert key in cfg["assumed"], key
    for key in ("intermediate_size", "max_window_layers",
                "use_sliding_window", "hidden_act", "model_type",
                "max_position_embeddings"):
        assert key in cfg["assumed"]["unused_keys"], key
    assert "7.07 GiB" in cfg["deployment"] and "4.00 GiB" in cfg["deployment"]


def test_the_parameter_count_of_the_cut():
    from benchmark.refs import mellum as ref

    cfg = mf.Manifest().config(CONFIG)
    leaves = jax.tree.leaves(ref.leaf_specs(cfg),
                             is_leaf=lambda s: hasattr(s, "shape"))
    n = 0
    for s in leaves:
        k = 1
        for d in s.shape:
            k *= d
        n += k
    assert n == cfg["parameters"] == 3_794_968_832
    # one layer by hand: q, k, v, o; two norms; the q and k norms; the
    # router; 64 experts of three matrices
    layer = 2 * 2304 * 4096 + 2 * 2304 * 512 + 2 * 2304 + 2 * 128 \
        + 2304 * 64 + 64 * 3 * 2304 * 896
    assert layer == 417_747_712
    assert n == 8 * layer + 2 * 98304 * 2304 + 2304
    # the whole model's 28 layers are the name's 12 B, a token's 2.5 B
    assert 28 * layer + 2 * 98304 * 2304 + 2304 == 12_149_923_072
    active = 2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64 + 8 * 3 * 2304 * 896
    assert round((28 * active + 2 * 98304 * 2304) / 1e9, 2) == 2.44


def test_the_program_is_handed_the_published_sizes():
    from benchmark.adapters import mellum as adapter

    cfg = adapter.model_config(mf.Manifest().config(CONFIG))
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size) == (8, 2304, 32, 4, 128, 896, 98304)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_normalize_gates) == (
        64, 8, True)
    assert cfg.layer_kinds == (("window_attn",) * 3 + ("full_attn",)) * 2
    assert cfg.gqa.window == 1024 and cfg.gqa.qk_norm
    assert cfg.gqa.full_rope.theta == cfg.gqa.window_rope.theta == 500000.0
    assert (cfg.gqa.full_rope.factor, cfg.gqa.full_rope.original_positions,
            cfg.gqa.full_rope.beta_fast, cfg.gqa.full_rope.beta_slow) == (
                16.0, 8192, 32.0, 1.0)
    assert cfg.gqa.full_rope.attention_factor == 1.2772588722239782
    assert cfg.layernorm_eps == 1e-6 and not cfg.tie_embeddings
    assert cfg.max_seq == 131072 and cfg.fp32_logits


def test_the_manifest_holds_the_cell_its_configuration_and_its_files():
    """Wherever they stand and whatever stands beside them."""
    data = mf.load_json(os.path.join(mf.ROOT, "BENCHMARK.json"))
    assert mf.validate(data) == []
    man = mf.Manifest()
    cell = man.cell(REAL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "serve-code")
    entry = next(c for c in data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers"]
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
    for sub in ("adapters/mellum.py", "refs/mellum.py", "peaks_mellum.py",
                "runners/serve_code.py", "traffic/serve-code.json",
                f"workloads/{REAL}.json"):
        assert os.path.exists(os.path.join(mf.BENCH_DIR, sub)), sub
    assert not any(w["chips"] == 4 for w in data["workloads"])


def test_the_cells_parameters_are_the_issues():
    man = mf.Manifest()
    w, t = man.workload_file(REAL), man.traffic("serve-code")
    assert w["serving"] == {"num_slots": 32, "block_size": 64,
                            "num_blocks": 16385, "max_seq_len": 32768,
                            "max_new_tokens": 1024, "prefill_chunk": 1024,
                            "prefill_token_budget": 1024}
    assert w["weights_dtype"] == "bfloat16" and w["runner"] == "serve_code"
    assert t["kind"] == "serve_code" and t["temperature"] == 0.0
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                  "sigma": 0.8, "min": 1024, "max": 30720}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 192,
                                  "sigma": 0.6, "min": 32, "max": 1024}
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["stretches"] == 8 and t["first_token_cap_s"] == 60.0
    # the longest request fits a slot, and 32 full slots fit both pools
    assert 30720 + 1024 <= w["serving"]["max_seq_len"]
    assert w["serving"]["num_blocks"] == 32 * 512 + 1
    assert w["check"]["controls"] == ["fp8", "nowindow", "noyarn",
                                      "rawgates", "noqknorm"]
    # two statistics of one comparison, each under its own limit: the
    # widest gap of a served token, the widest of a request's means
    limits = w["check"]["limits"]
    assert set(limits) == {"served_logit_gap", "served_logit_gap_request_mean"}
    assert 0 < limits["served_logit_gap_request_mean"] \
        < limits["served_logit_gap"] < 10
    # one order of arrivals for every seed, and the file says why
    assert isinstance(t["arrivals"]["deal"], int) and "deal" in t["why"]
    assert len(w["check"]["why"]) > 200 and len(t["why"]) > 200
