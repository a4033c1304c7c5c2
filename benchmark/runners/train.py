"""A training cell: ``deepspeed.initialize`` + ``engine.train_batch``.

Set-up builds ONE engine, drives it from the seed through its first three
optimizer steps through the same call and feed as the window (the first
two are what the reference follows; they also compile and warm), and hands
that engine to the window. After the window the engine is freed and the
float32 reference follows the same two steps from the same weights and
rows.
"""

import gc
import math
import time

import numpy as np

from .. import device, peaks, generator as tg
from ..refs import init as rinit
from ..refs import layerwise as lw
from ..refs.numerics import Numerics

FIRST_STEPS = 3      # steps of set-up; the reference follows the first two
POOL = 6             # distinct global batches the window cycles through
TRACE_SKIP, TRACE_STEPS = 1, 3


def reference_numbers(ctx, batches, numerics="f32") -> dict:
    """Losses of the first two steps, per-leaf norm of the first gradient
    as the optimizer gets it, per-leaf norm of the parameters' change
    after the two, by the plain reference in ``numerics``."""
    import jax
    import jax.numpy as jnp

    cfg, cell = ctx.config, ctx.cell_file
    ref = ctx.adapter.reference
    specs = ref.leaf_specs(cfg)
    wdtype = jnp.dtype(cell["weights_dtype"])
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          rinit.init_tree(ctx.seed, specs, wdtype))
    mesh = None
    if len(ctx.devices) > 1:
        mesh = jax.sharding.Mesh(np.array(ctx.devices), ("data",))
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        params = jax.device_put(params, rep)
    chk = cell["check"]
    model = ref.make(cfg, Numerics(numerics))
    trainer = lw.Layerwise(model, cfg["num_hidden_layers"],
                           chk["reference_rows_per_block"] * len(ctx.devices), mesh)
    opt = dict(cell["trainer"]["optimizer"]["params"],
               type=cell["trainer"]["optimizer"]["type"])
    out = lw.two_steps(trainer, params, batches[:2], opt,
                       cell["trainer"].get("gradient_clipping", 0.0),
                       chk["stash_first_gradient_on_host"], ctx.say, wdtype,
                       lw.sample_index(ctx.seed, params))
    moved = rinit.moved_norms(ctx.seed, specs, wdtype, out.pop("params"))
    return {"losses": out["losses"], "first_grad_norms": out["first_grad_norms"],
            "first_grad_samples": out["first_grad_samples"], "moved_norms": moved,
            "leaf_sizes": {jax.tree_util.keystr(p): int(np.prod(sp.shape))
                           for p, sp in jax.tree_util.tree_flatten_with_path(specs)[0]}}


def compare(prog: dict, ref: dict, limits: dict, say) -> bool:
    """Every number compared, beside its limit. All must hold."""
    rows = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        rows.append((f"loss_gap.step{i + 1}", abs(a - b) / abs(b),
                     limits["loss_gap"], f"program {a:.6f} reference {b:.6f}"))
    for key, name in (("first_grad_norms", "first_grad_gap"),
                      ("moved_norms", "moved_gap")):
        w = lw.worst_leaf_gap(prog[key], ref[key])
        rows.append((name, w["gap"], limits[name],
                     f"worst leaf {w['leaf']}: program {w['program']:.6g} "
                     f"reference {w['reference']:.6g}"))
    w = lw.worst_leaf_difference(prog["first_grad_samples"],
                                 ref["first_grad_samples"], ref["leaf_sizes"])
    rows.append(("first_grad_diff", w["gap"], limits["first_grad_diff"],
                 f"worst leaf {w['leaf']} (reference norm {w['reference']:.6g})"))
    ok = True
    for name, value, limit, note in rows:
        good = math.isfinite(value) and value <= limit
        ok &= good
        say(f"check {name}: {value:.6g} (limit {limit:g}) "
            f"{'ok' if good else 'OVER'}; {note}")
    return ok


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import deeperspeed_tpu as deepspeed

    cfg, cell, mix = ctx.config, ctx.cell_file, ctx.traffic
    adapter, ref, say = ctx.adapter, ctx.adapter.reference, ctx.say
    dp = len(ctx.devices)
    seq, micro, gas = mix["seq"], mix["micro_batch"], mix["accumulation_steps"]
    rows = micro * gas * dp
    tokens_per_step = rows * seq
    wdtype = jnp.dtype(cell["weights_dtype"])
    specs = ref.leaf_specs(cfg)

    ds_cfg = dict(cell["trainer"], train_micro_batch_size_per_gpu=micro,
                  gradient_accumulation_steps=gas, steps_per_print=10**9)
    params = rinit.init_tree(ctx.seed, specs, wdtype)
    engine = deepspeed.initialize(model=adapter.train_loss_fn(cfg, seq),
                                  model_parameters=params, config=ds_cfg)[0]
    del params
    if engine.data_parallel_size != dp:
        raise RuntimeError(f"engine runs dp={engine.data_parallel_size}, "
                           f"the cell has {dp} chip(s)")
    batches = tg.train_batches(mix, ctx.seed, 2 + POOL, rows, cfg["vocab_size"])
    spans = ctx.spans

    def step(batch):
        """The window's own call and feed; ends when the device has."""
        with spans.span("train_step"):
            with spans.span("train_dispatch"):
                loss = engine.train_batch(adapter.feed(batch))
            return float(jax.device_get(loss))

    # ---- set-up: the first steps, which the reference follows -------- #
    b1 = cell["trainer"]["optimizer"]["params"]["betas"][0]
    prog = {"losses": [step(batches[0])]}
    prog["first_grad_norms"] = {
        k: v / (1.0 - b1)
        for k, v in lw.leaf_norms(engine.state.opt_state.exp_avg).items()}
    prog["first_grad_samples"] = lw.sample_leaves(
        engine.state.opt_state.exp_avg,
        lw.sample_index(ctx.seed, engine.state.opt_state.exp_avg), 1.0 / (1.0 - b1))
    prog["losses"].append(step(batches[1]))
    master = engine.state.master
    prog["moved_norms"] = rinit.moved_norms(
        ctx.seed, specs, wdtype, engine.state.params if master is None else master)
    for b in batches[2:FIRST_STEPS]:
        step(b)
    say(f"first steps: losses {prog['losses']}; "
        f"in use {device.bytes_in_use(ctx.devices) / 2**30:.2f} GiB")
    lowered = device.LoweringCounter.get()
    compiles = lowered.count
    for k in ("train_step", "train_dispatch"):
        spans.durations[k].clear()

    # ---- the window -------------------------------------------------- #
    pool = batches[2:]
    prof = ctx.profiler
    losses, n = [], 0
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_start
    while True:
        if prof is not None and n == TRACE_SKIP:
            prof.start()
        losses.append(step(pool[n % len(pool)]))
        n += 1
        if prof is not None and prof.active and n == TRACE_SKIP + TRACE_STEPS:
            prof.stop()
        elapsed = time.perf_counter() - t_open
        if elapsed >= ctx.seconds and not (prof is not None and prof.active):
            break
    rate = n * tokens_per_step / elapsed / dp
    peak = device.memory_peak_bytes(ctx.devices)
    compiled_inside = lowered.count - compiles
    spans.counters["hbm_peak_bytes"] = peak

    pk = peaks.peaks_for(ctx.device["kind"])
    fpt = peaks.train_flops_per_token(adapter.matmul_params(cfg),
                                      cfg["num_hidden_layers"], cfg["hidden_size"],
                                      seq, adapter.CAUSAL)
    st = spans.durations["train_step"]
    say(f"window: {n} steps of {tokens_per_step} tokens in {elapsed:.3f} s; "
        f"step median {1e3 * float(np.median(st)):.2f} ms "
        f"min {1e3 * min(st):.2f} max {1e3 * max(st):.2f}; "
        f"{rate:.1f} tokens/s/chip = {100 * rate * fpt / pk['flops_per_s']:.2f}% "
        f"MFU ({fpt / 1e9:.3f} GFLOP/token); compiles inside the window: "
        f"{compiled_inside} (must be 0); peak {peak / 2**30:.2f} GiB")

    # ---- free the program, then the reference ------------------------- #
    del engine, master
    gc.collect()
    jax.clear_caches()
    say(f"program freed: {device.bytes_in_use(ctx.devices) / 2**30:.2f} GiB in use")
    t_ref = time.perf_counter()
    ref_numbers = reference_numbers(ctx, batches)
    correct = compare(prog, ref_numbers, cell["check"]["limits"], say)
    say(f"reference took {time.perf_counter() - t_ref:.1f} s")
    finite = all(math.isfinite(x) for x in losses)
    say(f"window losses finite: {finite}; first {losses[0]:.4f} last {losses[-1]:.4f}")

    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    shape = {"batch": micro, "heads": cfg["num_attention_heads"], "seq": seq,
             "head_dim": hd}
    return {
        "correct": bool(correct and finite and compiled_inside == 0),
        "attempted": n, "failed": sum(1 for x in losses if not math.isfinite(x)),
        "end_to_end": {"train_tokens_per_s_per_chip": rate, "setup_s": setup_s},
        "memory_peak_bytes": peak,
        "attention": {"flops": dict(shape, causal=adapter.CAUSAL),
                      "bytes": dict(shape, itemsize=2)},
    }
