"""EvaByte's part of the benchmark at a toy size on the CPU: the
``serve_bytes`` runner end to end (the check passes on the sound program;
the float8 control, the reference that forgot its summaries and the
reference that pools by plain means come out over the limit, and so does
a PROGRAM that skips its summaries), the new per-layer metrics' readers
on hand-written events, and the configuration's cut against the published
row. The toy window is DRAINED and the check samples the schedule's first
requests, so what is compared does not depend on the machine's load. No
time or rate is asserted here."""

import importlib
import json
import os
import types

import jax
import pytest

from benchmark import device
from benchmark import manifest as mf
from benchmark import peaks_evabyte as pe
from benchmark import peaks_sala as ps
from benchmark import profiling
from benchmark import run as brun
from benchmark import trace as tr
from benchmark.reducers import counter, kernel_roofline_pct
from benchmark.runners import serve_bytes

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "toy-eva.serve-bytes"
REAL = "evabyte-6.5b.serve-bytes"
CHAT = "falcon-h1-34b.serve-chat"
ALSO = ["prefill_chunk_device_ms.eva", "paged_attn_roofline.eva",
        "chunk_gap_share_pct.eva", "kv_pages_per_slot.eva",
        "slot_occupancy_pct"]


def context(seed, seconds=1.0):
    man = mf.Manifest(os.path.join(DATA, "BENCHMARK.toy-eva.json"),
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
    # every prompt is beyond two windows of 32: a live slot holds the
    # window's 4 pages and 2 to 4 of summaries, where a page for every 8
    # positions would be 9 to 16
    assert c["kv_window_pages_per_slot"] == 4.0
    assert 2.0 <= c["kv_summary_pages_per_slot"] <= 4.0
    assert c["kv_pages_per_slot"] == pytest.approx(
        4.0 + c["kv_summary_pages_per_slot"])
    assert c["summary_rows_chunk"] > c["summary_rows_decode"] > 0
    assert c["paged_pages_per_decode_call"] > 0


def test_the_drained_toy_cell_agrees_and_every_control_reads_over_the_limit():
    """The first requests of the schedule, drained: the same sample
    whatever else the machine runs."""
    ctx = context(7)
    out = serve_bytes.run(ctx, ctx.cell_file["check"]["controls"], drain=True)
    limit = ctx.cell_file["check"]["limits"]["served_logit_gap"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["check"]["widest_gap"] <= limit
    assert out["check"]["tokens"] >= ctx.cell_file["check"]["min_served_tokens"]
    assert list(out["check"]["controls"]) == ["fp8", "nosum", "flatpool"]
    assert all(g > limit for g in out["check"]["controls"].values())


def test_a_program_that_skips_its_summaries_is_not_correct(monkeypatch):
    """Leaving mathematics out inside the tolerance is not a speed-up: a
    program whose page lists name no summary page (every query reads its
    window's exact keys alone, as the chunk program and the decode step
    build their lists through one function)."""
    import jax.numpy as jnp

    from deeperspeed_tpu.serving import engine, kv_cache

    real = kv_cache.eva_page_list

    def window_only(ev, scfg, table, n, width):
        pages, _ = real(ev, scfg, table, n % ev.window, width)
        return pages, (n % ev.window).astype(jnp.int32)

    monkeypatch.setattr(kv_cache, "eva_page_list", window_only)
    monkeypatch.setattr(engine, "eva_page_list", window_only)
    ctx = context(11)
    out = serve_bytes.run(ctx, drain=True)
    assert out["correct"] is False and out["failed"] == 0
    assert "OVER" in line(ctx, "check served_logit_gap")


def test_every_seed_is_offered_the_same_schedule_and_other_bytes():
    """The cell's schedule is the generator's as the mix's ``deal`` orders
    it: every seed offers the same lengths and gaps in the SAME order
    (which answers overlap is work here: a decode step reads every live
    slot's pages), and the seed draws the bytes as the generator would."""
    from benchmark import generator as tg

    mix = mf.Manifest().traffic("serve-bytes")
    shape = lambda rs: [(r["rid"], r["due_s"], len(r["prompt"]),
                         r["max_new_tokens"]) for r in rs]
    a = serve_bytes.schedule(mix, 3_100_000_411, 40.0, 320)
    b = serve_bytes.schedule(mix, 7, 40.0, 320)
    dealt = tg.serve_requests(mix, mix["arrivals"]["deal"], 40.0, 320)
    assert len(a) == 38 and shape(a) == shape(b) == shape(dealt)
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    # the same seed, the same bytes; and the bytes the generator would
    # draw for that seed over these lengths
    again = serve_bytes.schedule(mix, 7, 40.0, 320)
    assert [r["prompt"] for r in again] == [r["prompt"] for r in b]
    tok = tg.rng_for(7, 2)
    assert b[0]["prompt"] == tok.integers(0, 320, len(b[0]["prompt"])).tolist()
    assert max(max(r["prompt"]) for r in b) < 320
    # the generator's own order for a seed is another one
    assert shape(tg.serve_requests(mix, 7, 40.0, 320)) != shape(b)


def test_warm_sends_one_prompt_beyond_two_windows_and_crosses_a_chunk():
    from benchmark.runners import serve

    ctx = context(5)
    engine = serve.build_engine(ctx)
    n = serve_bytes.warm(engine, 320, 5)
    assert n == 2 * 32 + 37 and n % 16 != 0
    assert engine.decode_compile_count == 1
    assert engine._chunk_step._cache_size() == 1
    assert engine.metrics.prefill_chunks == -(-n // 16)
    # its decode steps completed chunks of 4 and wrote their summaries
    assert engine.metrics.summary_rows_decode >= 1


# ------------------------------------------------------------------ #
# the new metrics' readers, on hand-written events
# ------------------------------------------------------------------ #


def _op(name, start, dur):
    return tr.Ev(name, start, dur, "/device:TPU:0", tr.OPS_LINE)


def metric(name):
    return mf.Manifest().metric_file(name)


def test_paged_roofline_reads_the_decode_calls_of_one_query_a_key_head():
    spec = metric("paged_attn_roofline.eva")
    call = "%paged_sparse_attn.3 = bf16[512,1,128]{2,1,0} custom-call(...)"
    user = "%fusion.9 = bf16[16,1,4096]{2,1,0} fusion(%paged_sparse_attn.3)"
    events = [_op(call, 1.0, 7e-4), _op(call, 2.0, 7e-4), _op(user, 2.5, 1e-6),
              tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")]
    spans = profiling.Spans()
    spans.counters["paged_pages_per_decode_call"] = 6000.0
    run = {"trace": profiling.traced_run(events, 1), "notes": [],
           "spans": spans, "device": {"kind": "TPU v5 lite"}}
    got = kernel_roofline_pct.read(run, spec["params"])
    # by hand: 6,000 pages of 64 keys and 64 values of 128 bf16 entries,
    # 512 rows of one query: q and o in bf16, the accumulator, maximum and
    # sum in float32
    nbytes = 6000 * 2 * 64 * 128 * 2 \
        + 512 * (2 * 128 * 2 + 128 * 4 + 2 * 128 * 4)
    assert ps.paged_sparse_call(6000.0, 512, 1, 128, 64, 2)["bytes"] == nbytes
    assert got == pytest.approx(100 * 2 * nbytes / 819e9 / 1.4e-3)
    assert 0 < got < 100 and "2 decode calls" in run["notes"][0]
    # a program without the kernel (the parent): nothing, and no error
    run["trace"] = profiling.traced_run(
        [_op("%fusion.1 = bf16[8]{0} fusion()", 1.0, 1.0), events[-1]], 1)
    assert kernel_roofline_pct.read(run, spec["params"]) is None


def test_the_pages_a_list_counts_by_hand():
    """What the program counts (``PageRule.live``, the new byte's page
    included) beside the benchmark's own arithmetic of what a list
    names."""
    from deeperspeed_tpu.serving.config import PageRule

    rule = PageRule(window=2048, chunk=16)
    # 14,000 positions cached: 6 windows behind (12 summary pages) and
    # 1,712 rows of the window (27 pages)
    assert pe.listed_pages(14000, 2048, 16, 64) == 12 + 27
    assert rule.live(14001, 64) == 12 + 27      # row 1,712 is on page 27
    assert pe.listed_pages(2048, 2048, 16, 64) == 2     # summaries alone
    assert rule.live(2049, 64) == 3             # and the new byte's page
    assert pe.listed_pages(100, 2048, 16, 64) == 2
    c = pe.eva_decode_call([14000, 2048], 32, 128, 2048, 16, 64, 2)
    assert c == ps.paged_sparse_call(32 * (39 + 2), 64, 1, 128, 64, 2)
    # a slot's list at 14,000 positions is 39 pages x 32 heads x 32 KiB
    assert c["bytes"] > 32 * 41 * 2 * 64 * 128 * 2


@pytest.mark.parametrize("name,key", [
    ("chunk_gap_share_pct.eva", "chunk_gap_share_pct"),
    ("kv_pages_per_slot.eva", "kv_pages_per_slot")])
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
    call = "%paged_sparse_attn.3 = bf16[512,1,128]{2,1,0} custom-call(...)"
    events = [_op(call, 1.0, 7e-4),
              tr.Ev("jit_ds_prefill_chunk(7)", 1.0, 0.031, "/device:TPU:0", "XLA Modules"),
              tr.Ev("jit_ds_prefill_chunk(7)", 2.0, 0.033, "/device:TPU:0", "XLA Modules"),
              tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")]
    spans = profiling.Spans()
    spans.counters.update({"chunk_gap_share_pct": 31.0, "slot_occupancy": 0.5,
                           "kv_pages_per_slot": 45.5,
                           "paged_pages_per_decode_call": 6000.0})
    said = []
    ctx = types.SimpleNamespace(
        spans=spans, device={"kind": "TPU v5 lite"}, notes=[], devices=[0],
        cell_file=man.workload_file(REAL), manifest=man, say=said.append,
        profiler=types.SimpleNamespace(events=lambda: events))
    assert ctx.cell_file["also_read"] == ALSO
    got = serve_long.read_also(ctx, ALSO)
    assert got["prefill_chunk_device_ms.eva"] == pytest.approx(32.0)
    assert got["chunk_gap_share_pct.eva"] == 31.0
    assert got["kv_pages_per_slot.eva"] == 45.5
    assert got["slot_occupancy_pct"] == 50.0
    assert 0 < got["paged_attn_roofline.eva"] < 100
    assert len(said) == 5 and all(l.startswith("metric ") for l in said)


# ------------------------------------------------------------------ #
# the configuration and the manifest
# ------------------------------------------------------------------ #

# the catalog row's ``config`` (model-configs guide, architectures.jsonl),
# every key
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


def test_every_published_key_is_in_the_file_unchanged():
    cfg = mf.Manifest().config("evabyte-6.5b")
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["num_layers"] == 8 and cfg["num_hidden_layers"] == 32
    assert cfg["reduced"] == ["num_layers"] and cfg["family"] == "evabyte"
    assert cfg["source"] == ("https://huggingface.co/EvaByte/EvaByte/blob/"
                             "main/config.json")
    for key in ("num_layers", "head_dim", "rotary", "pooling", "own_window",
                "head", "page_rule", "weights", "unused_keys", "arithmetic"):
        assert key in cfg["assumed"], key
    assert "four pipeline stages" in cfg["deployment"]
    assert "3.04 GiB" in cfg["deployment"] and "8.01 GiB" in cfg["deployment"]


def test_the_parameter_count_of_the_cut():
    from benchmark.refs import evabyte as ref

    cfg = mf.Manifest().config("evabyte-6.5b")
    leaves = jax.tree.leaves(ref.leaf_specs(cfg),
                             is_leaf=lambda s: hasattr(s, "shape"))
    n = 0
    for s in leaves:
        k = 1
        for d in s.shape:
            k *= d
        n += k
    assert n == cfg["parameters"] == 1_630_932_992
    # one layer by hand: attention, the MLP, two norms, the pooling vectors
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    # the embedding, the head of 8 x 320 columns, the final norm
    assert n == 8 * layer + 320 * 4096 + 4096 * 2560 + 4096


def test_the_program_is_handed_the_published_sizes():
    from benchmark.adapters import evabyte as adapter

    cfg = adapter.model_config(mf.Manifest().config("evabyte-6.5b"))
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size, cfg.n_pred) == (
                8, 4096, 32, 32, 128, 11008, 320, 8)
    assert (cfg.eva.window, cfg.eva.chunk, cfg.eva.summaries) == (2048, 16, 128)
    assert cfg.rope_theta == 1e5 and cfg.layernorm_eps == 1e-5
    assert cfg.norm_offset == 1.0 and cfg.fp32_stream and not cfg.tie_embeddings
    assert cfg.layer_kinds == ("eva",) * 8 and cfg.max_seq == 32768


def test_manifest_holds_five_cells_and_the_new_ones_metrics():
    data = mf.load_json(os.path.join(mf.ROOT, "BENCHMARK.json"))
    assert mf.validate(data) == []
    # wherever they stand and whatever stands beside them
    assert all(w["chips"] == 1 for w in data["workloads"])
    entry = next(c for c in data["configs"] if c["name"] == "evabyte-6.5b")
    assert entry["file"] == "benchmark/configs/evabyte-6.5b.json"
    man = mf.Manifest()
    cell = man.cell(REAL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "evabyte-6.5b", "serve-bytes")
    for name in (REAL, CHAT):
        per = {m["name"] for m in man.metrics_for(name, "per_layer")}
        assert per == {"step_host_ms.serve", "decode_step_device_ms",
                       "prefill_share_pct", "device_idle_pct.serve",
                       "hbm_peak_gib.serve"}
        assert {m["name"] for m in man.metrics_for(name, "end_to_end")} == {
            "tpot_p95_ms", "setup_s"}
        # a configuration's own metrics are files the traced run reads and
        # prints (``also_read``); tests/bench pins BENCHMARK.json's
        # per_layer list, so their entries wait for a benchmark PR
        for metric_name in man.workload_file(name)["also_read"]:
            spec = man.metric_file(metric_name)
            assert callable(importlib.import_module(
                f"benchmark.reducers.{spec['reducer']}").read)
    # the chat cell is as PR 31 put it
    chat = man.cell(CHAT)
    assert (chat["chips"], chat["config"], chat["traffic"]) == (
        1, "falcon-h1-34b", "serve-chat")


def test_the_cells_parameters_are_the_issues():
    man = mf.Manifest()
    w, t = man.workload_file(REAL), man.traffic("serve-bytes")
    assert w["serving"] == {"num_slots": 16, "block_size": 64,
                            "num_blocks": 1025, "max_seq_len": 32768,
                            "max_new_tokens": 1024, "prefill_chunk": 1024,
                            "prefill_token_budget": 1024}
    assert w["weights_dtype"] == "bfloat16" and w["runner"] == "serve_bytes"
    assert t["kind"] == "serve_bytes" and t["temperature"] == 0.0
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 12288,
                                  "sigma": 0.5, "min": 4352, "max": 30720}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 256,
                                  "sigma": 0.6, "min": 64, "max": 1024}
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["stretches"] == 8 and t["first_token_cap_s"] == 20.0
    # ONE order for every seed, the generator's for this number (the PR's)
    assert t["arrivals"]["deal"] == 35
    # ISSUE 35's 0.8 of the knee of 1.2/s (0.96), taken down to a whole
    # number of requests in the 40 s window
    assert t["arrivals"]["rate_per_s"] == 0.95
    assert 40 * t["arrivals"]["rate_per_s"] == 38
    # every prompt reaches beyond two windows, the longest request fits a
    # slot, and 16 full slots of 32 + 32 pages fit the pool
    assert t["prompt_tokens"]["min"] > 2 * 2048
    assert 30720 + 1024 <= w["serving"]["max_seq_len"]
    assert w["serving"]["num_blocks"] == 16 * (32 + 32) + 1
    assert json.dumps(w["check"]["controls"]) == '["fp8", "nosum", "flatpool"]'
    assert set(w["check"]["limits"]) == {"served_logit_gap"}
