"""MiniCPM-SALA's part of the benchmark at a toy size on the CPU: the
``serve_long`` runner end to end (the check passes on the sound program,
the float8 control and the control that skips the selection come out over
the limit, and so does a PROGRAM that skips the selection), the new
per-layer metrics' readers on hand-written events, the kernels' operation
and byte counts against hand arithmetic, and the configuration's cut
against the published list. No time or rate is asserted here."""

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
from benchmark.reducers import counter, kernel_roofline_pct

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "toy-sala.serve-longdoc"
REAL = "minicpm-sala.serve-longdoc"


def context(seed, seconds=0.5):
    man = mf.Manifest(os.path.join(DATA, "BENCHMARK.toy-sala.json"),
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


def test_toy_cell_runs_and_agrees_with_its_reference():
    ctx = context(3_000_000_019)
    out = brun.run_cell(ctx)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == round(0.5 * ctx.traffic["arrivals"]["rate_per_s"])
    assert set(out["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert float(line(ctx, "check served_logit_gap").split()[2]) <= 0.004
    assert "compiles inside the window: 0" in line(ctx, "chunk-gap share")
    # every prompt passes dense_len, so selections were made and counted
    c = ctx.spans.counters
    assert 0.0 < c["kv_selected_page_frac"] < 1.0
    assert 0.0 < c["chunk_gap_share_pct"] < 100.0
    assert c["state_bytes"] == 6 * 3 * 4 * 16 * 16 * 4     # layers slots H Dh Dh f32
    assert c["sparse_pages_per_decode_call"] > 0
    # one chunk a query block less than topk pages: 2 heads x (16 x 4 - 8 x 3)
    assert c["sparse_pages_per_chunk_call"] == 2 * (16 * 4 - 8 * 3)
    sel = line(ctx, "check served_logit_gap").split("differ in ")[1].split()
    assert int(sel[2]) > 0 and int(sel[0]) <= int(sel[2]) // 20


def test_both_controls_read_over_the_limit(monkeypatch):
    """On a sample taken by COUNT: the window is drained and every request
    of the schedule is compared, so a slow host (a toy window is half a
    second of wall clock) changes neither the sample nor the readings."""
    from benchmark import generator as tg
    from benchmark.runners import serve, serve_chat, serve_long

    offer = serve.offer
    monkeypatch.setattr(serve, "offer",
                        lambda *a, **kw: offer(*a, **dict(kw, drain=True)))
    monkeypatch.setattr(
        serve, "sample_finished",
        lambda done, seed, n: serve_chat.first_finished(done, float("inf")))
    ctx = context(7)
    out = serve_long.run(ctx, ctx.cell_file["check"]["controls"])
    limit = ctx.cell_file["check"]["limits"]["served_logit_gap"]
    assert out["correct"] is True
    assert out["check"]["tokens"] == sum(
        r["max_new_tokens"] for r in tg.serve_requests(ctx.traffic, 7, 0.5, 96))
    assert set(out["check"]["controls"]) == {"fp8", "noselect"}
    assert all(g > limit for g in out["check"]["controls"].values())


def test_a_program_that_skips_the_selection_is_not_correct(monkeypatch):
    """Leaving mathematics out inside the tolerance is not a speed-up: the
    program reads the first blocks in place of the best-scored."""
    import jax.numpy as jnp

    from deeperspeed_tpu.models import mixers

    real = mixers.select_blocks
    monkeypatch.setattr(
        mixers, "select_blocks",
        lambda b, q_block, sp: real(
            -jnp.broadcast_to(jnp.arange(b.shape[-1], dtype=b.dtype), b.shape),
            q_block, sp))
    ctx = context(11)
    out = brun.run_cell(ctx)
    assert out["correct"] is False and out["failed"] == 0
    assert "OVER" in line(ctx, "check served_logit_gap")


def test_warm_sends_one_prompt_beyond_dense_len():
    from benchmark.runners import serve, serve_long

    ctx = context(5)
    engine = serve.build_engine(ctx)
    n = serve_long.warm(engine, ctx.config, 96, 5)
    assert n == 64 + 16 + 5
    assert engine.decode_compile_count == 1
    assert engine._chunk_step._cache_size() == 1
    assert engine.metrics.prefill_chunks == -(-n // 16)


# ------------------------------------------------------------------ #
# the new metrics' readers, on hand-written events
# ------------------------------------------------------------------ #


def _run(ops, counters):
    events = ops + [tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")]
    spans = profiling.Spans()
    spans.counters.update(counters)
    return {"trace": profiling.traced_run(events, 1), "notes": [],
            "spans": spans, "device": {"kind": "TPU v5 lite"}}


def _op(name, start, dur):
    return tr.Ev(name, start, dur, "/device:TPU:0", tr.OPS_LINE)


def metric(name):
    return mf.Manifest().metric_file(name)


def test_sparse_roofline_tells_decode_calls_from_chunk_calls():
    spec = metric("sparse_attn_roofline")
    dec = "%paged_sparse_attn.3 = bf16[24,16,128]{2,1,0} custom-call(...)"
    chk = "%paged_sparse_attn.9 = bf16[512,16,128]{2,1,0} custom-call(...)"
    pages = {"sparse_pages_per_decode_call": 1000.0,
             "sparse_pages_per_chunk_call": 28416.0}
    # an operation that takes the kernel's result names it in its operands
    # and is no call (the first traced runs counted it: 148% and 713%)
    user = ("%bitcast_dynamic-update-slice_fusion.3 = bf16[4,512,16,128]{3,2,1,0} "
            "fusion(%paged_sparse_attn.9, %param.1)")
    run = _run([_op(dec, 1.0, 1e-4), _op(chk, 2.0, 2e-3), _op(chk, 3.0, 2e-3),
                _op(user, 2.5, 1e-6), _op(user, 3.5, 1e-6),
                _op("%fusion.1 = bf16[8]{0} fusion()", 4.0, 1.0)], pages)
    got = kernel_roofline_pct.read(run, spec["params"])
    calls = [ps.paged_sparse_call(1000.0, 24, 16, 128, 64, 2),
             ps.paged_sparse_call(28416.0, 512, 16, 128, 64, 2)]
    nbytes = calls[0]["bytes"] + 2 * calls[1]["bytes"]
    assert got == pytest.approx(100 * nbytes / 819e9 / 4.1e-3)
    assert "1 decode calls" in run["notes"][0] and "2 chunk calls" in run["notes"][0]


def test_lightning_roofline_and_a_trace_without_the_kernel():
    spec = metric("lightning_roofline")
    ev = "%lightning_chunk.2 = (f32[1024,4096]{1,0}, f32[32,128,128]{2,1,0}) custom-call(...)"
    run = _run([_op(ev, 1.0, 1e-3), _op(ev, 2.0, 1e-3)], {})
    c = ps.lightning_chunk_call(1024, 32, 128, 256, 2)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    assert kernel_roofline_pct.read(run, spec["params"]) == pytest.approx(
        100 * 2 * least / 2e-3)
    # the parent's program has no such kernel: nothing, and no error
    none = _run([_op("%fusion.1 = bf16[8]{0} fusion()", 1.0, 1.0)], {})
    assert kernel_roofline_pct.read(none, spec["params"]) is None
    assert kernel_roofline_pct.read(
        none, metric("sparse_attn_roofline")["params"]) is None


@pytest.mark.parametrize("name,key", [
    ("kv_selected_page_frac.sala", "kv_selected_page_frac"),
    ("chunk_gap_share_pct.sala", "chunk_gap_share_pct")])
def test_counter_metrics_read_the_runners_counters(name, key):
    spec = metric(name)
    spans = types.SimpleNamespace(counters={key: 0.25})
    assert counter.read({"spans": spans}, spec["params"]) == 0.25
    assert counter.read({"spans": types.SimpleNamespace(counters={})},
                        spec["params"]) is None


# ------------------------------------------------------------------ #
# counts against hand arithmetic
# ------------------------------------------------------------------ #


def test_paged_sparse_counts():
    # one row, one page of 64 keys of 128 entries, 16 query heads, bf16:
    # scores 16 x 64 x 128 x 2, output the same: 524,288 operations;
    # K and V pages 2 x 64 x 128 x 2 B = 32,768 B; the row's q and o
    # 2 x 16 x 128 x 2 B = 8,192, its float32 accumulator 8,192, its
    # maximum and sum 2 x 16 x 128 x 4 = 16,384
    c = ps.paged_sparse_call(1, 1, 16, 128, 64, 2)
    assert c["flops"] == 524_288
    assert c["bytes"] == 32_768 + 8_192 + 8_192 + 16_384
    # a decode call of the cell: 12 slots x 2 heads x 64 pages
    c = ps.paged_sparse_call(12 * 2 * 64, 24, 16, 128, 64, 2)
    assert c["bytes"] == 1536 * 32_768 + 24 * 32_768


def test_lightning_chunk_counts():
    # one head, one block of 256 positions of 128 entries: Q K^T and its
    # product with V 2 x (2 x 256 x 256 x 128) = 33,554,432; Q S and the
    # state's update 2 x (2 x 256 x 128 x 128) = 16,777,216
    c = ps.lightning_chunk_call(256, 1, 128, 256, 2)
    assert c["flops"] == 33_554_432 + 16_777_216
    # q, k, v in bf16 3 x 65,536 B, o in float32 131,072, the state twice 131,072
    assert c["bytes"] == 196_608 + 131_072 + 131_072
    full = ps.lightning_chunk_call(1024, 32, 128, 256, 2)
    assert full["flops"] == 32 * 4 * c["flops"]


def test_chunk_pages_read_by_hand_and_by_the_program():
    # a chunk of 1,024 tokens is 16 blocks of 64: the queries of its b-th
    # block have b + 1 of their 64 blocks inside the chunk
    by_hand = 2 * sum(64 * (64 - (b + 1)) for b in range(16))
    assert ps.chunk_pages_read(1024, 64, 64, 2) == by_hand == 113_664
    assert ps.chunk_pages_read(1024, 64, 64, 2, sparse_layers=4) == 454_656


# ------------------------------------------------------------------ #
# the configuration and the manifest
# ------------------------------------------------------------------ #


def test_the_cut_is_published_entries_9_to_24():
    cfg = mf.Manifest().config("minicpm-sala")
    pub = cfg["published_mixer_types"]
    assert len(pub) == cfg["num_hidden_layers"] == 32
    assert pub.count("minicpm4") == 8 and pub.count("lightning-attn") == 24
    assert cfg["mixer_types"] == pub[9:25] and cfg["num_layers"] == 16
    assert "".join("S" if m == "minicpm4" else "L"
                   for m in cfg["mixer_types"]) == "SLLLLLLSSLLLLSLL"
    # the published ratio 1 : 3 (ISSUE 27 calls this run the only one of 16
    # that has it; the runs from 7, 8 and 14 have it too: PERF.md section 4)
    runs = [i for i in range(17) if pub[i:i + 16].count("minicpm4") == 4]
    assert runs == [7, 8, 9, 14]
    assert set(cfg["reduced"]) == {"num_layers", "mixer_types"}
    for key in ("lightning_slopes", "lightning_activation",
                "lightning_output_norm", "mup_denominator", "sparse_config",
                "sparse_footprint", "causal_rule", "weights"):
        assert key in cfg["assumed"], key


def test_every_published_width_is_kept():
    cfg = mf.Manifest().config("minicpm-sala")
    published = {"hidden_size": 4096, "intermediate_size": 16384,
                 "head_dim": 128, "num_attention_heads": 32,
                 "num_key_value_heads": 2, "lightning_nh": 32,
                 "lightning_nkv": 32, "lightning_head_dim": 128,
                 "vocab_size": 73448, "max_position_embeddings": 524288,
                 "num_hidden_layers": 32, "scale_emb": 12, "scale_depth": 1.4,
                 "dim_model_base": 256, "mup_denominator": 32,
                 "rms_norm_eps": 1e-06, "rope_theta": 10000}
    assert {k: cfg[k] for k in published} == published


def test_manifest_holds_the_cell_and_its_metrics():
    data = mf.load_json(os.path.join(mf.ROOT, "BENCHMARK.json"))
    assert mf.validate(data) == []
    man = mf.Manifest()
    cell = man.cell(REAL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "minicpm-sala", "serve-longdoc")
    per = {m["name"] for m in man.metrics_for(REAL, "per_layer")}
    assert per == {"step_host_ms.serve", "decode_step_device_ms",
                   "prefill_share_pct", "device_idle_pct.serve",
                   "hbm_peak_gib.serve"}
    assert {m["name"] for m in man.metrics_for(REAL, "end_to_end")} == {
        "tpot_p95_ms", "setup_s"}
    # the five metrics this configuration brought are files the traced run
    # reads and prints (``also_read``); tests/bench pins BENCHMARK.json's
    # per_layer list, so their entries wait for a benchmark PR
    also = man.workload_file(REAL)["also_read"]
    assert also == ["prefill_chunk_device_ms.sala", "sparse_attn_roofline",
                    "lightning_roofline", "kv_selected_page_frac.sala",
                    "chunk_gap_share_pct.sala"]
    for name in also:
        spec = man.metric_file(name)
        assert callable(importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}").read)


def test_also_read_prints_each_metric_of_a_traced_run():
    """``read_also`` on hand-written events: the counters read, the
    kernels' shares read, the chunk program's median read."""
    from benchmark.runners import serve_long

    man = mf.Manifest()
    chk = "%paged_sparse_attn.9 = bf16[512,16,128]{2,1,0} custom-call(...)"
    lc = "%lightning_chunk.2 = (f32[1024,4096]{1,0}, f32[32,128,128]{2,1,0}) custom-call(...)"
    events = [_op(chk, 1.0, 2e-3), _op(lc, 2.0, 1e-3),
              tr.Ev("jit_ds_prefill_chunk(7)", 1.0, 0.11, "/device:TPU:0", "XLA Modules"),
              tr.Ev("jit_ds_prefill_chunk(7)", 2.0, 0.13, "/device:TPU:0", "XLA Modules"),
              tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")]
    spans = profiling.Spans()
    spans.counters.update({"kv_selected_page_frac": 0.25,
                           "chunk_gap_share_pct": 31.0,
                           "sparse_pages_per_chunk_call": 28416.0,
                           "sparse_pages_per_decode_call": 1500.0})
    said = []
    ctx = types.SimpleNamespace(
        spans=spans, device={"kind": "TPU v5 lite"}, notes=[], devices=[0],
        cell_file=man.workload_file(REAL), manifest=man, say=said.append,
        profiler=types.SimpleNamespace(events=lambda: events))
    got = serve_long.read_also(ctx, ctx.cell_file["also_read"])
    assert got["prefill_chunk_device_ms.sala"] == pytest.approx(120.0)
    assert got["kv_selected_page_frac.sala"] == 0.25
    assert got["chunk_gap_share_pct.sala"] == 31.0
    assert 0 < got["sparse_attn_roofline"] <= 100
    assert 0 < got["lightning_roofline"] <= 100
    assert len(said) == 5 and all(l.startswith("metric ") for l in said)


def test_the_cells_parameters_are_the_issues():
    man = mf.Manifest()
    w, t = man.workload_file(REAL), man.traffic("serve-longdoc")
    assert w["serving"] == {"num_slots": 12, "block_size": 64,
                            "num_blocks": 6241, "max_seq_len": 33280,
                            "max_new_tokens": 512, "prefill_chunk": 1024,
                            "prefill_token_budget": 1024}
    # PR 44: the median is the smallest, in steps of 2,048 from 18,432,
    # that keeps the 95th percentile of the gaps among the sparse chunks
    # on every seed (PERF.md section 4), the shortest prompt half of it
    p = t["prompt_tokens"]
    assert (p["dist"], p["sigma"], p["max"]) == ("lognormal", 0.5, 32768)
    assert p["median"] in range(18432, 32768, 2048)
    assert p["min"] == max(8448, p["median"] // 2)
    assert p["max"] + 512 <= w["serving"]["max_seq_len"]
    n = 40 * t["arrivals"]["rate_per_s"]
    assert abs(n - round(n)) < 1e-9 and "knee" in t["why"]
    assert t["output_tokens"] == {"dist": "lognormal", "median": 192,
                                  "sigma": 0.5, "min": 64, "max": 512}
    assert t["arrivals"]["stretches"] == 8 and t["first_token_cap_s"] == 20.0
    assert t["prompt_tokens"]["min"] > man.config("minicpm-sala")[
        "sparse_config"]["dense_len"]
    assert json.dumps(w["check"]["controls"]) == '["fp8", "noselect"]'


def test_every_seed_offers_the_same_prompts_and_most_chunks_are_sparse():
    """The seed orders the requests and draws their tokens; the work is
    the file's. And the work is the selected pages: a chunk at or beyond
    ``dense_len`` is what the cell exists for, so the set of prompts, and
    the median prompt, hold more of those than of the dense ones."""
    from benchmark import generator as tg

    man = mf.Manifest()
    t, cfg = man.traffic("serve-longdoc"), man.config("minicpm-sala")
    dense = cfg["sparse_config"]["dense_len"]
    chunk = man.workload_file(REAL)["serving"]["prefill_chunk"]
    n = round(40 * t["arrivals"]["rate_per_s"])
    dealt = {round(g, 6) for g in tg._gaps(t["arrivals"], n, 40.0)}
    offered = []
    for seed in (1, 4_400_000_101, 2**31 + 5):
        reqs = tg.serve_requests(t, seed, 40.0, cfg["vocab_size"])
        offered.append((sorted(len(r["prompt"]) for r in reqs),
                        sorted(r["max_new_tokens"] for r in reqs)))
        # the first request is due at 0: the gap dealt before it is not kept
        gaps = {round(b["due_s"] - a["due_s"], 6) for a, b in zip(reqs, reqs[1:])}
        assert len(gaps) == n - 1 and gaps < dealt
    assert offered[0] == offered[1] == offered[2]
    lengths = offered[0][0]
    assert len(lengths) == n
    assert min(lengths) > dense and max(lengths) <= 32768
    below = [min(-(-m // chunk), dense // chunk) for m in lengths]
    beyond = [-(-m // chunk) - b for m, b in zip(lengths, below)]
    assert sum(beyond) > sum(below)
    mid = len(lengths) // 2
    assert beyond[mid] > below[mid]

