"""Falcon-H1's part of the benchmark at a toy size on the CPU: the
``serve_chat`` runner end to end (the check passes on the sound program;
the float8 control, the reference without the state-space branch and the
reference without the attention branch come out over the limit, and so
does a PROGRAM that forgets its state rows), the new per-layer metrics'
readers on hand-written events, and the configuration's cut against the
published row. The toy window is DRAINED and the check samples the
schedule's first requests, so what is compared does not depend on the
machine's load. No time or rate is asserted here."""

import importlib
import json
import os
import types

import jax
import pytest

from benchmark import device
from benchmark import manifest as mf
from benchmark import peaks_sala as ps
from benchmark import profiling
from benchmark import run as brun
from benchmark import trace as tr
from benchmark import peaks_falcon_h1 as ph
from benchmark.reducers import call_roofline_pct, counter, kernel_roofline_pct
from benchmark.runners import serve_chat

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "toy-h1.serve-chat"
REAL = "falcon-h1-34b.serve-chat"
ALSO = ["prefill_chunk_device_ms.h1", "paged_attn_roofline.h1",
        "ssm_row_update_roofline", "chunk_gap_share_pct.h1",
        "state_gib_per_step.h1", "slot_occupancy_pct"]


def context(seed, seconds=1.0):
    man = mf.Manifest(os.path.join(DATA, "BENCHMARK.toy-h1.json"),
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
    # layers x slots x (heads x head_dim x state + 3 x channels) x float32
    assert c["state_bytes"] == 2 * 4 * (4 * 16 * 16 + 3 * 128) * 4
    assert c["state_gib_per_step"] == 2 * c["state_bytes"] / 2**30
    assert c["state_resets"] == out["attempted"]
    assert c["paged_pages_per_decode_call"] > 0


def test_the_drained_toy_cell_agrees_and_every_control_reads_over_the_limit():
    """The first requests of the schedule, drained: the same sample
    whatever else the machine runs."""
    ctx = context(7)
    out = serve_chat.run(ctx, ctx.cell_file["check"]["controls"], drain=True)
    limit = ctx.cell_file["check"]["limits"]["served_logit_gap"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["check"]["widest_gap"] <= limit
    assert out["check"]["tokens"] >= ctx.cell_file["check"]["min_served_tokens"]
    assert list(out["check"]["controls"]) == ["fp8", "nossm", "noattn"]
    assert all(g > limit for g in out["check"]["controls"].values())


def test_a_program_that_forgets_its_state_rows_is_not_correct(monkeypatch):
    """Leaving mathematics out inside the tolerance is not a speed-up: a
    decode step whose recurrence starts from an empty row every token."""
    import jax.numpy as jnp

    from deeperspeed_tpu.models import mixers

    real = mixers.ssm_rows_xla
    monkeypatch.setattr(
        mixers, "ssm_rows_xla",
        lambda rows, *a: (rows, real(jnp.zeros_like(rows), *a)[1]))
    ctx = context(11)
    out = serve_chat.run(ctx, drain=True)
    assert out["correct"] is False and out["failed"] == 0
    assert "OVER" in line(ctx, "check served_logit_gap")


def test_warm_sends_one_prompt_of_two_chunks_the_second_ragged():
    from benchmark.runners import serve

    ctx = context(5)
    engine = serve.build_engine(ctx)
    n = serve_chat.warm(engine, 320, 5)
    assert n == 16 + 37 and n % 16 not in (0,)
    assert engine.decode_compile_count == 1
    assert engine._chunk_step._cache_size() == 1
    assert engine.metrics.prefill_chunks == -(-n // 16)


def test_first_finished_takes_the_schedules_first_requests():
    req = lambda n, reason="length": types.SimpleNamespace(
        state="finished", finish_reason=reason, prompt=[1], generated=[2] * n)
    recs = [{"req": req(5)}, {"req": req(9, "timeout")}, {"req": None},
            {"req": req(7)}, {"req": req(4)}]
    assert [len(r["output"]) for r in serve_chat.first_finished(recs, 10)] \
        == [5, 7]
    assert serve_chat.first_finished(recs, 0) == []


# ------------------------------------------------------------------ #
# the new metrics' readers, on hand-written events
# ------------------------------------------------------------------ #


def _op(name, start, dur):
    return tr.Ev(name, start, dur, "/device:TPU:0", tr.OPS_LINE)


def metric(name):
    return mf.Manifest().metric_file(name)


def test_paged_roofline_reads_the_decode_calls_of_five_queries_a_key_head():
    spec = metric("paged_attn_roofline.h1")
    call = "%paged_sparse_attn.3 = bf16[192,5,128]{2,1,0} custom-call(...)"
    user = ("%fusion.9 = bf16[48,1,2560]{2,1,0} fusion(%paged_sparse_attn.3)")
    events = [_op(call, 1.0, 2e-4), _op(call, 2.0, 2e-4), _op(user, 2.5, 1e-6),
              tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")]
    spans = profiling.Spans()
    spans.counters["paged_pages_per_decode_call"] = 2000.0
    run = {"trace": profiling.traced_run(events, 1), "notes": [],
           "spans": spans, "device": {"kind": "TPU v5 lite"}}
    got = kernel_roofline_pct.read(run, spec["params"])
    # by hand: 2,000 pages of 64 keys and 64 values of 128 bf16 entries,
    # 192 rows of 5 queries: q and o in bf16, the accumulator, maximum and
    # sum in float32
    nbytes = 2000 * 2 * 64 * 128 * 2 \
        + 192 * (2 * 5 * 128 * 2 + 5 * 128 * 4 + 2 * 5 * 128 * 4)
    assert ps.paged_sparse_call(2000.0, 192, 5, 128, 64, 2)["bytes"] == nbytes
    assert ps.paged_sparse_call(2000.0, 192, 5, 128, 64, 2)["flops"] \
        == 4 * 2000 * 64 * 5 * 128
    assert got == pytest.approx(100 * 2 * nbytes / 819e9 / 4e-4)
    assert "2 decode calls" in run["notes"][0]
    # a program without the kernel (the parent): nothing, and no error
    run["trace"] = profiling.traced_run(
        [_op("%fusion.1 = bf16[8]{0} fusion()", 1.0, 1.0), events[-1]], 1)
    assert kernel_roofline_pct.read(run, spec["params"]) is None


def test_row_update_roofline_and_its_counts_by_hand():
    spec = metric("ssm_row_update_roofline")
    call = ("%ssm_row_update.2 = (f32[6,48,32,128,256]{4,3,2,1,0}, "
            "f32[48,4,128,8]{3,2,1,0}) custom-call(...)")
    user = "%fusion.4 = f32[48,32,128]{2,1,0} fusion(%ssm_row_update.2)"
    events = [_op(call, 1.0, 6e-4), _op(call, 2.0, 6e-4), _op(user, 2.5, 1e-6),
              tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")]
    run = {"trace": profiling.traced_run(events, 1), "notes": [],
           "spans": profiling.Spans(), "device": {"kind": "TPU v5 lite"}}
    got = call_roofline_pct.read(run, spec["params"])
    # one small shape by hand: 2 slots x 4 heads x 8 x 128 float32 entries
    # = 8,192: 5 operations each; in and out 2 x 8,192 x 4 B; decay 2 x 4,
    # dx and y 2 x 2 x 4 x 8, B and C 2 x 2 x 2 x 128 entries of 4 B
    c = ph.ssm_row_update_call(2, 4, 8, 128, 2)
    assert c["flops"] == 5 * 8192
    assert c["bytes"] == 2 * 8192 * 4 + (8 + 128 + 1024) * 4
    # the cell's: 48 x 32 x 128 x 256 entries a call, 192 MiB each way
    c = ph.ssm_row_update_call(**spec["params"]["shape"])
    assert c["bytes"] == pytest.approx(2 * 192 * 2**20, rel=0.01)
    assert got == pytest.approx(100 * 2 * c["bytes"] / 819e9 / 1.2e-3)
    assert got < 100 and "2 calls" in run["notes"][0]
    # a program without the kernel (the parent): nothing, and no error
    run["trace"] = profiling.traced_run(
        [_op("%fusion.1 = bf16[8]{0} fusion()", 1.0, 1.0), events[-1]], 1)
    assert call_roofline_pct.read(run, spec["params"]) is None


@pytest.mark.parametrize("name,key", [
    ("chunk_gap_share_pct.h1", "chunk_gap_share_pct"),
    ("state_gib_per_step.h1", "state_gib_per_step")])
def test_counter_metrics_read_the_runners_counters(name, key):
    spec = metric(name)
    spans = types.SimpleNamespace(counters={key: 0.25})
    assert counter.read({"spans": spans}, spec["params"]) == 0.25
    assert counter.read({"spans": types.SimpleNamespace(counters={})},
                        spec["params"]) is None


def test_also_read_prints_each_metric_of_a_traced_run():
    """``read_also`` on hand-written events: the counters read, the
    kernel's share read, the chunk program's median read."""
    from benchmark.runners import serve_long

    man = mf.Manifest()
    call = "%paged_sparse_attn.3 = bf16[192,5,128]{2,1,0} custom-call(...)"
    upd = ("%ssm_row_update.2 = (f32[6,48,32,128,256]{4,3,2,1,0}, "
           "f32[48,4,128,8]{3,2,1,0}) custom-call(...)")
    events = [_op(call, 1.0, 3e-4), _op(upd, 3.0, 6e-4),
              tr.Ev("jit_ds_prefill_chunk(7)", 1.0, 0.021, "/device:TPU:0", "XLA Modules"),
              tr.Ev("jit_ds_prefill_chunk(7)", 2.0, 0.023, "/device:TPU:0", "XLA Modules"),
              tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")]
    spans = profiling.Spans()
    spans.counters.update({"chunk_gap_share_pct": 31.0, "slot_occupancy": 0.5,
                           "state_gib_per_step": 2.25,
                           "paged_pages_per_decode_call": 1500.0})
    said = []
    ctx = types.SimpleNamespace(
        spans=spans, device={"kind": "TPU v5 lite"}, notes=[], devices=[0],
        cell_file=man.workload_file(REAL), manifest=man, say=said.append,
        profiler=types.SimpleNamespace(events=lambda: events))
    assert ctx.cell_file["also_read"] == ALSO
    got = serve_long.read_also(ctx, ALSO)
    assert got["prefill_chunk_device_ms.h1"] == pytest.approx(22.0)
    assert got["chunk_gap_share_pct.h1"] == 31.0
    assert got["state_gib_per_step.h1"] == 2.25
    assert got["slot_occupancy_pct"] == 50.0
    assert 0 < got["paged_attn_roofline.h1"] <= 100
    assert 0 < got["ssm_row_update_roofline"] <= 100
    assert len(said) == 6 and all(l.startswith("metric ") for l in said)


# ------------------------------------------------------------------ #
# the configuration and the manifest
# ------------------------------------------------------------------ #

# the catalog row's ``config`` (model-configs guide, architectures.jsonl),
# every key
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


def test_every_published_key_is_in_the_file_unchanged():
    cfg = mf.Manifest().config("falcon-h1-34b")
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["num_layers"] == 6 and cfg["num_hidden_layers"] == 72
    assert cfg["reduced"] == ["num_layers"] and cfg["family"] == "falcon_h1"
    assert cfg["source"] == ("https://huggingface.co/tiiuae/"
                             "Falcon-H1-34B-Instruct/blob/main/config.json")
    for key in ("num_layers", "weights", "dt_bias", "conv_layout", "rotary",
                "projections", "norm_groups", "multipliers", "chunking"):
        assert key in cfg["assumed"], key
    assert "twelve" in cfg["deployment"]


def test_the_parameter_count_of_the_cut():
    from benchmark.refs import falcon_h1 as ref

    cfg = mf.Manifest().config("falcon-h1-34b")
    leaves = jax.tree.leaves(ref.leaf_specs(cfg),
                             is_leaf=lambda s: hasattr(s, "shape"))
    n = 0
    for s in leaves:
        k = 1
        for d in s.shape:
            k *= d
        n += k
    assert n == cfg["parameters"] == 5_254_594_112
    # one layer by hand: attention, the state-space branch, the MLP, two norms
    attn = 5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120
    ssm = 5120 * 9248 + 4096 * 5120 + 5120 * 4 + 5120 + 4096 + 3 * 32
    assert attn == 31_457_280 and ssm == 68_351_072
    layer = attn + ssm + 3 * 5120 * 21504 + 2 * 5120
    assert layer == 430_120_032
    assert n == 6 * layer + 2 * 261120 * 5120 + 5120


def test_the_program_is_handed_the_published_sizes():
    from benchmark.adapters import falcon_h1 as adapter

    cfg = adapter.model_config(mf.Manifest().config("falcon-h1-34b"))
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size) == (6, 5120, 20, 4, 128, 21504, 261120)
    assert cfg.d_model // cfg.n_head == 256       # not the head's size
    m = cfg.ssm
    assert (m.n_heads, m.head_dim, m.d_state, m.n_groups, m.d_conv, m.chunk,
            m.d_ssm, m.conv_dim, m.proj_dim) == (32, 128, 256, 2, 4, 128,
                                                 4096, 5120, 9248)
    assert (m.attn_out, m.ssm_in, m.ssm_out) == (0.0375, 0.25, PUBLISHED[
        "ssm_out_multiplier"])
    assert cfg.rope_theta == 1e11 and not cfg.tie_embeddings
    assert cfg.layer_kinds == ("mamba_attn",) * 6 and cfg.sparse is None


def test_manifest_holds_the_cell_and_its_metrics():
    data = mf.load_json(os.path.join(mf.ROOT, "BENCHMARK.json"))
    assert mf.validate(data) == []
    # wherever they stand and whatever stands beside them
    entry = next(c for c in data["configs"] if c["name"] == "falcon-h1-34b")
    assert entry["file"] == "benchmark/configs/falcon-h1-34b.json"
    man = mf.Manifest()
    cell = man.cell(REAL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "falcon-h1-34b", "serve-chat")
    per = {m["name"] for m in man.metrics_for(REAL, "per_layer")}
    assert per == {"step_host_ms.serve", "decode_step_device_ms",
                   "prefill_share_pct", "device_idle_pct.serve",
                   "hbm_peak_gib.serve"}
    assert {m["name"] for m in man.metrics_for(REAL, "end_to_end")} == {
        "tpot_p95_ms", "setup_s"}
    # this configuration's own metrics are files the traced run reads and
    # prints (``also_read``); tests/bench pins BENCHMARK.json's per_layer
    # list, so their entries wait for a benchmark PR
    for name in man.workload_file(REAL)["also_read"]:
        spec = man.metric_file(name)
        assert callable(importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}").read)


def test_the_cells_parameters_are_the_issues():
    man = mf.Manifest()
    w, t = man.workload_file(REAL), man.traffic("serve-chat")
    assert w["serving"] == {"num_slots": 48, "block_size": 64,
                            "num_blocks": 2305, "max_seq_len": 3072,
                            "max_new_tokens": 1024, "prefill_chunk": 512,
                            "prefill_token_budget": 512}
    assert w["weights_dtype"] == "bfloat16" and w["runner"] == "serve_chat"
    assert t["kind"] == "serve_chat" and t["temperature"] == 0.0
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.8, "min": 32, "max": 2048}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 192,
                                  "sigma": 0.7, "min": 16, "max": 1024}
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["stretches"] == 8 and t["first_token_cap_s"] == 20.0
    # a whole number of requests in the 40 s window
    assert (40 * t["arrivals"]["rate_per_s"]) % 1 == 0
    # the longest context fits a slot's pages, and 48 full slots the pool
    assert 2048 + 1024 <= w["serving"]["max_seq_len"] == 48 * 64
    assert w["serving"]["num_blocks"] == 48 * 48 + 1
    assert json.dumps(w["check"]["controls"]) == '["fp8", "nossm", "noattn"]'
    assert set(w["check"]["limits"]) == {"served_logit_gap"}
