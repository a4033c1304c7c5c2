"""Ouro's part of the benchmark at a toy size on the CPU: the
``serve_solve`` runner end to end (the check passes on the sound program;
the float8 control and the four references with one thing wrong come out
over a limit, a case each), the new per-layer metrics' readers on
hand-written events, the bytes of a looped decode step against a hand
count, and the configuration against the published row. The toy window is
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
from benchmark import peaks_ouro as po
from benchmark import profiling
from benchmark import run as brun
from benchmark import trace as tr
from benchmark.reducers import (counter, full_list_roofline_pct,
                                loop_pass_ms, looped_step_roofline_pct)
from benchmark.runners import serve_solve

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "toy-ouro.serve-solve"
REAL = "ouro-2.6b.serve-solve"
CONFIG = "ouro-2.6b"
ALSO = ["loop_pass_device_ms.ouro", "decode_weights_roofline.ouro",
        "paged_attn_roofline.ouro", "prefill_chunk_device_ms.ouro",
        "chunk_gap_share_pct.ouro", "kv_pages_per_slot.ouro",
        "exit_step_expected.ouro", "slot_occupancy_pct"]
CONTROLS = ["fp8", "threeloops", "sharedcache", "nosandwich", "normonce"]


def context(seed, seconds=1.0):
    man = mf.Manifest(os.path.join(DATA, "BENCHMARK.toy-ouro.json"),
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
    assert out["failed"] == 0 and out["correct"] is True
    assert out["attempted"] == round(ctx.traffic["arrivals"]["rate_per_s"])
    assert set(out["metrics"]) == {"tpot_p95_ms", "setup_s"}
    said = line(ctx, "chunk-gap share")
    assert "compiles inside the window: 0" in said
    assert "preemptions 0" in said and "decode " in said
    assert "3 passes over 3 layers, a pool 9 cache layers deep, 4608 B a " \
        "position" in line(ctx, "warmed")
    c = ctx.spans.counters
    assert 0.0 < c["chunk_gap_share_pct"] < 100.0
    # prompts of 5 to 60 and answers to 24 in pages of 4 rows
    assert 2.0 <= c["kv_pages_per_slot"] <= 21.0
    assert 1.0 < c["exit_step_expected"] < 3.0
    # K and V, 97 pages of 4 rows, 9 cache layers, 4 heads of 16, float32
    assert c["kv_pool_bytes"] == 2 * 97 * 4 * 9 * 4 * 16 * 4


@pytest.fixture(scope="module")
def drained():
    """The first requests of the schedule, drained: the same sample
    whatever else the machine runs; the controls read once for all their
    cases."""
    ctx = context(7)
    assert ctx.cell_file["check"]["controls"] == CONTROLS
    return ctx, serve_solve.run(ctx, CONTROLS, drain=True)


def test_the_drained_toy_cell_agrees_with_the_reference(drained):
    ctx, out = drained
    limits = ctx.cell_file["check"]["limits"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["check"]["widest_gap"] <= limits["served_logit_gap"]
    assert out["check"]["request_mean_gap"] \
        <= limits["served_logit_gap_request_mean"]
    assert out["check"]["tokens"] >= ctx.cell_file["check"]["min_served_tokens"]
    assert list(out["check"]["controls"]) == CONTROLS


@pytest.mark.parametrize("name", CONTROLS)
def test_a_control_is_told_from_the_sound_program(drained, name):
    """Each control (the reference in float8; with a pass fewer; with one
    cache for all passes; without the norms on the sublayers' outputs;
    with the final norm after the last pass alone) reads over one of the
    limits the cell uses."""
    ctx, out = drained
    limits = ctx.cell_file["check"]["limits"]
    assert out["check"]["controls"][name] > limits["served_logit_gap"] \
        or out["check"]["controls_request_mean"][name] \
        > limits["served_logit_gap_request_mean"]
    assert f"control[{name}]" in "".join(ctx.lines)


# ------------------------------------------------------------------ #
# the new metrics' readers, on hand-written events
# ------------------------------------------------------------------ #


def _op(name, start, dur, text=""):
    return tr.Ev(name, start, dur, "/device:TPU:0", tr.OPS_LINE, text)


def _module(name, start, dur):
    return tr.Ev(name, start, dur, "/device:TPU:0", "XLA Modules")


def _span(name, start, dur, **args):
    text = name + " " + " ".join(f"{k}:{v}" for k, v in args.items())
    return tr.Ev(name, start, dur, "/host:CPU", "python3", text)


def metric(name):
    return mf.Manifest().metric_file(name)


WINDOW = tr.Ev("bench/traced_window", 0.0, 10.0, "/host:CPU", "x")
OTHER = tr.Ev("%fusion.1 = bf16[8]{0} fusion()", 5.0, 1.0, "/device:TPU:0",
              tr.OPS_LINE)
STEP = "jit_ds_decode_step(11)"
LOOP = "%while.7 = (s32[], bf16[8,1,2048]{2,1,0}) while(%tuple.3)"
INNER = "%while.3 = (s32[], bf16[8,1,2048]{2,1,0}) while(%tuple.1)"
LIST = ("%paged_sparse_attn_slots.16 = bf16[8,16,128]{2,1,0} "
        "custom-call(s32[1] %l, s32[8] %n)")


def run_of(events):
    return {"trace": profiling.traced_run(events + [WINDOW], 1), "notes": [],
            "spans": profiling.Spans(), "device": {"kind": "TPU v5 lite"}}


def steps(n, pages=50, passes=4, live=6, scoped=True):
    """``n`` decode steps of 34 ms: the program, its loop over the passes
    (32 ms, the layers' loops inside it) and its host spans."""
    out = []
    for i in range(n):
        t = 1.0 + 0.05 * i
        out += [_module(STEP, t, 0.034),
                _op(LOOP, t + 0.0005, 0.032,
                    LOOP + (" jit(ds_decode_step)/ds.loop/while" if scoped
                            else "")),
                _span("serving/decode", t - 0.002, 0.001, n_active=live),
                _span("serving/decode/dispatch", t - 0.0015, 0.0004,
                      full_pages=pages, passes=passes)]
        out += [_op(INNER, t + 0.0005 + 0.008 * j, 0.0079,
                    INNER + (" jit(ds_decode_step)/ds.loop/while/body/while"
                             if scoped else "")) for j in range(4)]
    return out


def test_the_bytes_of_a_looped_step_against_a_hand_count():
    """4 x the stack's 2,466,643,968 parameters, the head once, 8 rows of
    the embedding; a page 64 positions of 1.5 MiB; a row written a live
    slot: with all four passes, or the share would read over 100%."""
    shape = metric("decode_weights_roofline.ouro")["params"]["shape"]
    assert po.layer_matmul_params(2048, 5632, 16, 16, 128) \
        == 4 * 2048 ** 2 + 3 * 2048 * 5632 == 51_380_224
    c = po.decode_step(50, 6, **shape)
    stack = 48 * (51_380_224 + 4 * 2048)
    assert stack == 2_466_643_968
    want = 2 * (4 * stack + 2048 * 49152 + 8 * 2048) \
        + 50 * 64 * 1_572_864 + 6 * 1_572_864
    assert c["bytes"] == want
    assert 19.9e9 < 2 * (4 * stack + 2048 * 49152) < 19.95e9
    # memory-bound: 8 slots' products are nothing beside the weights' bytes
    assert c["flops"] / 197e12 < 0.05 * c["bytes"] / 819e9
    # one pass's weights alone would be a quarter: the count holds four
    one = po.decode_step(50, 6, **{**shape, "passes": 1})
    assert c["bytes"] - one["bytes"] == 2 * 3 * stack \
        + (50 * 64 + 6) * 3 * 393_216


def test_the_looped_steps_roofline_reads_the_slices_own_pages():
    spec = metric("decode_weights_roofline.ouro")
    run = run_of(steps(3))
    got = looped_step_roofline_pct.read(run, spec["params"])
    c = po.decode_step(50, 6, **spec["params"]["shape"])
    assert got == pytest.approx(100 * c["bytes"] / 819e9 / 0.034)
    assert 70 < got < 100 and "memory-bound" in run["notes"][0]
    # a program that runs another count of passes than the file's: an error
    with pytest.raises(tr.TraceError, match="passes"):
        looped_step_roofline_pct.read(run_of(steps(2, passes=3)),
                                      spec["params"])
    # no program, or no count in the slice (the parent): no value, a note
    for events in ([OTHER], [OTHER] + [e for e in steps(2)
                                    if e.line == "XLA Modules"]):
        run = run_of(events)
        assert looped_step_roofline_pct.read(run, spec["params"]) is None
        assert "no value" in run["notes"][0]


def test_a_pass_runs_from_its_first_page_list_read_to_the_next_passes():
    spec = metric("loop_pass_device_ms.ouro")
    assert spec["params"]["layers"] == 48
    # two steps of 192 calls, 170 us apart (a pass 8.16 ms), and a third
    # the window cuts
    events = steps(2) + [_op(LIST, 1.0 + 0.05 * i + 0.0005 + 1.7e-4 * k, 4e-5)
                         for i in range(2) for k in range(192)] + [OTHER]
    run = run_of(events)
    got = loop_pass_ms.read(run, spec["params"])
    assert got == pytest.approx(48 * 0.17)
    assert "6 passes of 48 layers" in run["notes"][0]
    # a step that does not loop, or a slice without steps or calls: no value
    for events in (steps(2, passes=1) + [_op(LIST, 1.001, 4e-5)], [OTHER],
                   steps(2)):
        assert loop_pass_ms.read(run_of(events), spec["params"]) is None
    # another count of calls a step than the file's layers: an error
    with pytest.raises(tr.TraceError, match="48 layers"):
        loop_pass_ms.read(run_of(steps(1) + [_op(LIST, 1.001 + 1e-4 * k, 4e-5)
                                             for k in range(100)]),
                          spec["params"])


def test_the_page_lists_roofline_counts_all_192_cache_layers():
    spec = metric("paged_attn_roofline.ouro")
    assert spec["params"]["full_layers"] == 192
    # ONE step: 192 calls, its live slots listing 50 pages of 512 KiB a
    # cache layer
    ops = [_op(LIST, 1.0 + 1e-4 * i, 4e-5) for i in range(192)]
    span = _span("serving/decode/dispatch", 0.9, 1e-4, full_pages=50,
                 passes=4)
    run = run_of(ops + [span])
    got = full_list_roofline_pct.read(run, spec["params"])
    want = pm.slot_list_call(192 * 50, 8 * 192, 16, 16, 128, 64, 2)
    assert want["bytes"] > 192 * 50 * 2 ** 19
    assert got == pytest.approx(100 * want["bytes"] / 819e9 / (192 * 4e-5))
    assert 0 < got < 100 and "192 calls" in run["notes"][0]


@pytest.mark.parametrize("name,key", [
    ("chunk_gap_share_pct.ouro", "chunk_gap_share_pct"),
    ("kv_pages_per_slot.ouro", "kv_pages_per_slot"),
    ("exit_step_expected.ouro", "exit_step_expected")])
def test_counter_metrics_read_the_runners_counters(name, key):
    spec = metric(name)
    spans = types.SimpleNamespace(counters={key: 0.25})
    assert counter.read({"spans": spans}, spec["params"]) == 0.25
    assert counter.read({"spans": types.SimpleNamespace(counters={})},
                        spec["params"]) is None


def test_also_read_prints_each_metric_of_a_traced_run():
    from benchmark.runners import serve_long

    man = mf.Manifest()
    events = steps(2) + [_op(LIST, 1.0005 + 1.6e-4 * i, 4e-5)
                         for i in range(192)] + [
        _module("jit_ds_prefill_chunk(7)", 3.0, 0.041),
        _module("jit_ds_prefill_chunk(7)", 4.0, 0.043), WINDOW]
    spans = profiling.Spans()
    spans.counters.update({"chunk_gap_share_pct": 2.0, "slot_occupancy": 0.7,
                           "kv_pages_per_slot": 7.5,
                           "exit_step_expected": 2.2})
    said = []
    ctx = types.SimpleNamespace(
        spans=spans, device={"kind": "TPU v5 lite"}, notes=[], devices=[0],
        cell_file=man.workload_file(REAL), manifest=man, say=said.append,
        profiler=types.SimpleNamespace(events=lambda: events))
    assert ctx.cell_file["also_read"] == ALSO
    got = serve_long.read_also(ctx, ALSO)
    assert got["loop_pass_device_ms.ouro"] == pytest.approx(48 * 0.16)
    assert got["prefill_chunk_device_ms.ouro"] == pytest.approx(42.0)
    assert got["chunk_gap_share_pct.ouro"] == 2.0
    assert got["exit_step_expected.ouro"] == 2.2
    assert got["slot_occupancy_pct"] == 70.0
    for name in ("decode_weights_roofline.ouro", "paged_attn_roofline.ouro"):
        assert 0 < got[name] < 100, name
    assert len(said) == len(ALSO) and all(l.startswith("metric ") for l in said)
    # a slice that holds none of it (the parent's): every reader returns
    # no value and none raises, bar the program's own time, which says so
    ctx.profiler = types.SimpleNamespace(events=lambda: [OTHER, WINDOW])
    ctx.spans = profiling.Spans()
    rest = [n for n in ALSO if n != "prefill_chunk_device_ms.ouro"]
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
        return next(json.loads(l) for l in f if '"Ouro-2.6B"' in l)


def test_every_published_key_is_in_the_file_and_nothing_is_cut():
    row = published()
    cfg = mf.Manifest().config(CONFIG)
    assert {k for k in row["config"] if cfg[k] != row["config"][k]} == set()
    assert cfg["reduced"] == [] and cfg["source"] == row["source_url"]
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["vocab_size"]) == (48, 4, 49152)


def test_the_file_states_what_it_assumed():
    cfg = mf.Manifest().config(CONFIG)
    assert cfg["family"] == "ouro" and cfg["early_exit_threshold"] == 1
    for key in ("drawn", "sandwich_norms", "norm_between_passes",
                "cache_per_pass", "exit_gate", "rotary", "weights",
                "arithmetic", "unused_keys"):
        assert key in cfg["assumed"], key
    for control, key in (("nosandwich", "sandwich_norms"),
                         ("normonce", "norm_between_passes"),
                         ("sharedcache", "cache_per_pass"),
                         ("threeloops", "exit_gate")):
        assert control in cfg["assumed"][key]
    assert "1,572,864" in cfg["deployment"] and "nothing cut" in cfg["deployment"]


def test_the_parameter_count_is_the_whole_models():
    from benchmark.refs import ouro as ref

    cfg = mf.Manifest().config(CONFIG)
    leaves = jax.tree.leaves(ref.leaf_specs(cfg),
                             is_leaf=lambda s: hasattr(s, "shape"))
    n = 0
    for s in leaves:
        k = 1
        for d in s.shape:
            k *= d
        n += k
    assert n == cfg["parameters"] == 2_667_974_657
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert n == 48 * layer + 2 * 49152 * 2048 + 2048 + 2049


def test_the_program_is_handed_the_published_sizes():
    from benchmark.adapters import ouro as adapter

    cfg = adapter.model_config(mf.Manifest().config(CONFIG))
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size) == (48, 2048, 16, 16, 128, 5632,
                                             49152)
    assert cfg.loop_steps == 4 and cfg.cache_layers("full_attn") == 192
    assert cfg.layer_kinds == ("full_attn",) * 48
    assert cfg.gqa.sandwich and not cfg.gqa.qk_norm and cfg.gqa.rotary
    assert not cfg.gqa.out_gate and cfg.gqa.full_rope.theta == 1e6
    assert cfg.gqa.full_rope.factor == 1.0 and not cfg.moe_num_experts
    assert cfg.layernorm_eps == 1e-6 and not cfg.tie_embeddings
    assert cfg.max_seq == 65536 and cfg.fp32_logits


def test_the_manifest_holds_the_cell_its_configuration_and_its_files():
    """Found BY NAME, wherever they stand and whatever stands beside them."""
    data = mf.load_json(os.path.join(mf.ROOT, "BENCHMARK.json"))
    assert mf.validate(data) == []
    man = mf.Manifest()
    cell = man.cell(REAL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "serve-solve")
    entry = next(c for c in data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
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
    for sub in ("adapters/ouro.py", "refs/ouro.py", "peaks_ouro.py",
                "runners/serve_solve.py", "traffic/serve-solve.json",
                f"workloads/{REAL}.json"):
        assert os.path.exists(os.path.join(mf.BENCH_DIR, sub)), sub


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(mf.BENCH_DIR, "refs", "ouro.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] \
        + [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
           for a in n.names]
    assert not any("deeperspeed" in n for n in names)


def test_the_cells_parameters_are_the_issues():
    man = mf.Manifest()
    w, t = man.workload_file(REAL), man.traffic("serve-solve")
    assert w["serving"] == {"num_slots": 8, "block_size": 64,
                            "num_blocks": 73, "max_seq_len": 1280,
                            "max_new_tokens": 768, "prefill_chunk": 256,
                            "prefill_token_budget": 256}
    assert w["weights_dtype"] == "bfloat16" and w["runner"] == "serve_solve"
    assert t["kind"] == "serve_solve" and t["temperature"] == 0.0
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 160,
                                  "sigma": 0.6, "min": 48, "max": 512}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 320,
                                  "sigma": 0.6, "min": 96, "max": 768}
    assert t["arrivals"]["process"] == "poisson"
    assert t["arrivals"]["stretches"] == 8
    # a whole number of requests in the window, and the file says where
    # the rate comes from
    n = t["arrivals"]["rate_per_s"] * 40
    assert abs(n - round(n)) < 1e-9 and "knee" in t["why"]
    # one order of arrivals for every seed, and the file says why
    assert isinstance(t["arrivals"]["deal"], int) and "deal" in t["why"]
    # the longest request fits a slot; 72 usable pages are 4,608 positions
    assert 512 + 768 <= w["serving"]["max_seq_len"]
    assert (w["serving"]["num_blocks"] - 1) * 64 == 4608
    assert w["check"]["controls"] == CONTROLS
    assert w["check"]["min_served_tokens"] >= 400
    limits = w["check"]["limits"]
    assert set(limits) == {"served_logit_gap", "served_logit_gap_request_mean"}
    assert 0 < limits["served_logit_gap_request_mean"] \
        < limits["served_logit_gap"] < 10
    assert len(w["check"]["why"]) > 200 and len(t["why"]) > 200
