"""``benchmark/populations.py``, the tool that counts a serving cell's
token gaps by what ran between their two tokens: a hand-made record of
steps gives the shares by hand, the watched toy engines (chunked prompts,
bucketed prefills) account for every gap the runner measures, and no
cell's run knows the tool. Counts only, on the CPU."""

import ast
import glob
import os

import jax
import pytest

from benchmark import generator as tg
from benchmark import manifest as mf
from benchmark import populations as pop
from benchmark.runners import serve

import test_bench_runners as runners
import test_bench_sala as sala

DENSE_LEN = 8192


def step(t, tokens, chunks=(), prefills=0):
    return {"t": t, "tokens": tokens, "chunks": list(chunks),
            "prefills": prefills}


def test_a_hand_made_record_gives_the_shares_by_hand():
    steps = [
        step(0.000, {"a": 1}),
        # dispatched in this call, so it runs in the gap that BEGINS here
        step(0.015, {"a": 2, "c": 1}, [(7168, False)]),
        step(0.105, {"a": 3, "c": 1}, [(8192, False)]),
        # a prompt's short tail and the next prompt's first chunk
        step(0.213, {"a": 4, "c": 2}, [(9216, False), (0, False)]),
        step(0.400, {"a": 5}),
        # a bucketed prefill, waited out inside the call: the gap that ENDS here
        step(0.420, {"a": 6, "b": 1}, prefills=1),
        # a last chunk with nothing in flight, waited out as well
        step(0.530, {"a": 7, "b": 2}, [(10240, True)]),
        step(0.545, {"a": 8, "b": 3}),
    ]
    gaps = pop.classify(steps, DENSE_LEN)
    by = {}
    for seconds, kind in gaps:
        by.setdefault(kind, []).append(round(seconds, 3))
    assert by == {
        "decode": [0.015, 0.015, 0.015],
        "chunk_below": [0.090],                  # offset 7,168
        "chunk_beyond": [0.108, 0.110, 0.110],   # offsets 8,192 and 10,240
        # one call's two chunks; c's tokens two calls apart, a chunk in each
        "two_chunks": [0.187, 0.198],
        "prefill": [0.020],
    }
    got = pop.shares(gaps)
    assert [got[k]["share_pct"] for k in pop.KINDS] == [30.0, 10.0, 30.0, 20.0, 10.0]
    assert got["chunk_below"]["median_ms"] == pytest.approx(90.0)
    # a model with no dense_len has chunks of one kind
    assert {k for _, k in pop.classify(steps)} == {
        "decode", "chunk_below", "two_chunks", "prefill"}
    assert pop.shares([])["decode"] == {"share_pct": 0.0, "median_ms": None}


def test_the_watched_toy_engine_accounts_for_every_gap_and_chunk():
    ctx = sala.context(5, seconds=1.0)
    engine = serve.build_engine(ctx)
    assert pop.warm_cell(ctx, engine, None) == 64 + 16 + 5
    chunks_before = engine.metrics.prefill_chunks
    watched = pop.Watched(engine)
    requests = tg.serve_requests(ctx.traffic, 5, 1.0, 96, tag="w")
    recs, _, _ = serve.offer(watched, requests, 1.0, 60.0, ctx.spans, drain=True)
    w = serve.reduce_window(recs, 1.0)
    assert w["failed"] == 0 and w["cut_by_close"] == 0
    seen = [c for s in watched.steps for c in s["chunks"]]
    assert len(seen) == engine.metrics.prefill_chunks - chunks_before
    # every prompt's chunks, each offset once, in 16-token steps from 0
    assert sorted(o for o, _ in seen) == sorted(
        o for r in requests for o in range(0, len(r["prompt"]), 16))
    assert sum(s["prefills"] for s in watched.steps) == 0
    gaps = pop.classify(watched.steps, ctx.config["sparse_config"]["dense_len"])
    assert len(gaps) == w["n_gaps"] == sum(r["max_new_tokens"] - 1 for r in requests)
    got = pop.shares(gaps)
    assert sum(v["share_pct"] for v in got.values()) == pytest.approx(100.0)
    # the toy's prompts are 66-180 tokens behind a dense_len of 64
    assert got["chunk_below"]["share_pct"] > 0 and got["chunk_beyond"]["share_pct"] > 0


def test_one_window_of_the_toy_cell_prints_what_the_tool_promises():
    ctx = sala.context(6, seconds=1.0)
    engine = serve.build_engine(ctx)
    pop.warm_cell(ctx, engine, None)
    rows = [pop.window(ctx, engine, seed, rate) for seed, rate in ((6, None), (7, 8.0))]
    assert [r["requests"] for r in rows] == [16, 8]
    for r in rows:
        assert r["failed"] == 0 and r["n_classified"] == r["n_gaps"] > 0
        assert sorted(r["top_ms"]) == list(range(88, 101))
        assert r["top_ms"][95] == pytest.approx(r["tpot_p95_ms"])
        assert set(r["kinds"]) == set(pop.KINDS)
    assert not engine.has_work()        # what the close cut was finished
    over = pop.extremes(rows)
    assert over["seeds"] == 2 and len(over["chunk_beyond_share_pct"]) == 2
    assert over["range_over_median"] >= 0.0


def test_a_bucketed_prefill_is_its_own_kind():
    ctx = runners.context("toy-neox.serve", 5, devices=jax.devices()[:1], seconds=0.5)
    engine = serve.build_engine(ctx)
    requests = tg.serve_requests(ctx.traffic, 5, 0.5, ctx.config["vocab_size"])
    pop.warm_cell(ctx, engine, requests)
    row = pop.window(ctx, engine, 5)
    assert row["requests"] == len(requests) and row["n_classified"] == row["n_gaps"]
    kinds = row["kinds"]
    assert kinds["prefill"]["share_pct"] > 0
    assert kinds["decode"]["share_pct"] + kinds["prefill"]["share_pct"] \
        == pytest.approx(100.0)


def test_the_tool_is_outside_every_cells_run():
    """It imports nothing a cell's run does not, and no file a cell is made
    of names it: parent and change run the same program under the same
    harness whatever happens to this tool."""
    with open(os.path.join(mf.BENCH_DIR, "populations.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported |= {f"{node.module}.{a.name}" for a in node.names}
    assert imported == {"argparse", "json", "os", "sys", "time",
                        "benchmark.generator", "benchmark.stats",
                        "benchmark.run", "benchmark.runners.serve",
                        "benchmark.runners.serve_long"}
    files = [os.path.join(mf.ROOT, "BENCHMARK.json")] + [
        os.path.join(mf.BENCH_DIR, name)
        for name in ("run.py", "generator.py", "stats.py", "manifest.py")]
    for sub in ("workloads", "traffic", "metrics", "runners", "reducers"):
        files += glob.glob(os.path.join(mf.BENCH_DIR, sub, "*.*"))
    assert len(files) > 100

    def names_it(path):
        with open(path) as f:
            return "populations" in f.read()

    assert [p for p in files if names_it(p)] == []
