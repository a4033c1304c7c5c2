"""The runners end to end, in-process, at a toy size on the CPU mesh (the
configurations live under tests/bench/data): they report counts, the
check passes on the sound program, and comes out NOT correct for the
control (the reference in 8-bit integers, put in the program's place) and
for a timed path broken underneath. No time or rate is asserted here."""

import os

import jax
import numpy as np
import pytest

from benchmark import control, device
from benchmark import manifest as mf
from benchmark import run as brun

DATA = os.path.join(os.path.dirname(__file__), "data")


def context(cell, seed, devices=None, seconds=0.3):
    man = mf.Manifest(os.path.join(DATA, "BENCHMARK.toy.json"),
                      extra_dirs=[mf.BENCH_DIR])
    devs = devices or jax.devices()
    lines = []
    ctx = brun.build_context(man, cell, seed, seconds, 0, devs, device.describe(devs),
                             lines.append)
    ctx.device["kind"] = "TPU v5 lite"    # the table of peaks has no CPU
    ctx.lines = lines
    return ctx


def checks(ctx):
    return {l.split()[1].rstrip(":"): float(l.split()[2])
            for l in ctx.lines if l.startswith("check ")}


@pytest.mark.parametrize("cell", ["toy-neox.train", "toy-bert.train"])
def test_training_cell_runs_and_agrees_with_its_reference(cell):
    ctx = context(cell, 3_000_000_019)
    out = brun.run_cell(ctx)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert out["device"]["count"] == len(jax.devices())
    got = checks(ctx)
    assert set(got) == {"loss_gap.step1", "loss_gap.step2", "first_grad_gap",
                        "moved_gap", "first_grad_diff"}
    assert any("compiles inside the window: 0" in l for l in ctx.lines)


def test_training_control_in_int8_is_not_correct():
    """The reference in 8-bit integer matmuls, in the program's place."""
    ctx = context("toy-neox.train", 7, devices=jax.devices()[:1])
    ctx.control_numerics = [ctx.cell_file["check"]["control_numerics"]]
    assert control.control_train(ctx)["correct"] is False
    got = {l.split()[2].rstrip(":"): float(l.split()[3])
           for l in ctx.lines if l.startswith("control[")}
    # the lower precision has to fail ONE of the cell's numbers: this one
    assert got["first_grad_diff"] > ctx.cell_file["check"]["limits"]["first_grad_diff"]


def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct(monkeypatch):
    """The program's step, broken underneath: it trains on the first half
    of the rows twice and never sees the second half."""
    import deeperspeed_tpu

    real = deeperspeed_tpu.initialize

    def initialize(**kw):
        engine, *rest = real(**kw)
        step = engine.train_batch

        def half(batch):
            h = batch.shape[0] // 2
            return step(np.concatenate([batch[:h], batch[:h]]))

        engine.train_batch = half
        return (engine, *rest)

    monkeypatch.setattr(deeperspeed_tpu, "initialize", initialize)
    ctx = context("toy-neox.train", 11)
    out = brun.run_cell(ctx)
    assert out["correct"] is False
    limits = ctx.cell_file["check"]["limits"]
    assert checks(ctx)["first_grad_gap"] > limits["first_grad_gap"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import deeperspeed_tpu

    real = deeperspeed_tpu.initialize

    def initialize(**kw):
        kw["config"] = dict(kw["config"])
        kw["config"]["optimizer"] = {
            "type": kw["config"]["optimizer"]["type"],
            "params": dict(kw["config"]["optimizer"]["params"], lr=0.0)}
        return real(**kw)

    monkeypatch.setattr(deeperspeed_tpu, "initialize", initialize)
    ctx = context("toy-neox.train", 12)
    out = brun.run_cell(ctx)
    assert out["correct"] is False
    assert checks(ctx)["moved_gap"] > ctx.cell_file["check"]["limits"]["moved_gap"]


def test_serving_cell_runs_and_a_changed_token_is_not_correct(monkeypatch):
    ctx = context("toy-neox.serve", 5, devices=jax.devices()[:1], seconds=0.5)
    out = brun.run_cell(ctx)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == round(0.5 * ctx.traffic["arrivals"]["rate_per_s"])
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "serve_tokens_per_s", "setup_s"}

    # the same cell with a token altered where it is produced
    from benchmark.runners import serve

    ctx = context("toy-neox.serve", 5, devices=jax.devices()[:1], seconds=0.5)
    build = serve.build_engine

    def broken(c):
        engine = build(c)
        step = engine.step

        def altered():
            done = step()
            for q in engine.sched.slots:
                if q is not None and len(q.generated) == 2:
                    q.generated[-1] = (q.generated[-1] + 1) % c.config["vocab_size"]
            return done

        engine.step = altered
        return engine

    monkeypatch.setattr(serve, "build_engine", broken)
    out = brun.run_cell(ctx)
    assert out["correct"] is False


def test_serving_control_in_fp8_is_not_correct():
    ctx = context("toy-neox.serve", 6, devices=jax.devices()[:1], seconds=0.5)
    ctx.control_numerics = [ctx.cell_file["check"]["control_numerics"]]
    assert control.control_serve(ctx)["correct"] is False


SHAPE = lambda rs: [(r["rid"], r["due_s"], len(r["prompt"]), r["max_new_tokens"])
                    for r in rs]


@pytest.mark.parametrize("deal", [None, 4400000451])
def test_schedule_follows_the_seed_or_the_mixs_one_deal(deal):
    """Without ``arrivals.deal`` the schedule is the generator's for the
    seed; with it every seed is offered the same lengths and gaps in the
    deal's ONE order and draws its own token ids."""
    from benchmark import generator as tg
    from benchmark.runners import serve

    mix = mf.Manifest().traffic("serve")
    mix = dict(mix, arrivals={k: v for k, v in mix["arrivals"].items() if k != "deal"})
    if deal is not None:
        mix["arrivals"]["deal"] = deal
    a = serve.schedule(mix, 3_100_000_411, 40.0, 50304)
    b = serve.schedule(mix, 7, 40.0, 50304)
    assert len(a) == len(b) == 48
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    tok = tg.rng_for(7, 2)     # the ids the generator would draw for the seed
    assert b[0]["prompt"] == tok.integers(0, 50304, len(b[0]["prompt"])).tolist()
    if deal is None:
        assert SHAPE(a) != SHAPE(b)
        assert b == tg.serve_requests(mix, 7, 40.0, 50304)
    else:
        assert SHAPE(a) == SHAPE(b) == SHAPE(tg.serve_requests(mix, deal, 40.0, 50304))
        assert b == serve.schedule(mix, 7, 40.0, 50304)    # same seed, same inputs


def test_the_neox_serving_cell_deals_one_order_and_says_why():
    man = mf.Manifest()
    mix = man.traffic("serve")
    assert mix["arrivals"]["deal"] == 4400000451 and mix["arrivals"]["rate_per_s"] == 1.2
    assert "arrivals.deal" in mix["why"] and "ONE order" in man.cell("neox-1.3b.serve")["why"]
    assert man.workload_file("neox-1.3b.serve")["runner"] == "serve"
