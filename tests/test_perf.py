"""Perf doctor tests: compiled-cost index capture on CPU jits, the
device-memory watermark lane (graceful ``{}``-on-CPU fallback, spans
carrying hbm args), the flight-recorded near-OOM post-mortem payload,
the program's peak table against the benchmark's, and the engine/serving
integration (train-batch and decode spans carrying ``mfu``/``hbm_peak``
on CPU, strict-valid trace, decode still one-compile with the perf layer
on)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.monitor import (
    CompiledCostIndex,
    MemWatch,
    Tracer,
    aggregate_memory_stats,
    device_memory_stats,
    get_monitor,
    init_monitor,
    set_tracer,
    shutdown_monitor,
    validate_events,
)
from deeperspeed_tpu.monitor import flight as flight_mod
from deeperspeed_tpu.monitor.perf import (
    extract_cost_analysis,
    extract_memory_analysis,
    platform_peaks,
)
from deeperspeed_tpu.runtime.utils import memory_status
from deeperspeed_tpu.utils.timer import SynchronizedWallClockTimer


@pytest.fixture(autouse=True)
def _clean_global_monitor():
    """Telemetry state is process-global; leave no tracer/monitor behind."""
    yield
    shutdown_monitor(save=False)
    set_tracer(None)


# ------------------------------------------------------------------ #
# cost extraction + index
# ------------------------------------------------------------------ #


def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_extract_cost_analysis_real_jit():
    c = _compiled(lambda x: (x @ x).sum(), jnp.ones((32, 32)))
    ca = extract_cost_analysis(c)
    assert set(ca) == {"flops", "bytes_accessed", "optimal_seconds"}
    assert ca["flops"] > 0  # 32^3-ish matmul definitely counts flops
    assert ca["bytes_accessed"] > 0


def test_extract_cost_analysis_degenerate_shapes():
    class Fake:
        def __init__(self, ret):
            self._ret = ret

        def cost_analysis(self):
            if isinstance(self._ret, Exception):
                raise self._ret
            return self._ret

    zero = {"flops": 0.0, "bytes_accessed": 0.0, "optimal_seconds": 0.0}
    assert extract_cost_analysis(Fake(None)) == zero
    assert extract_cost_analysis(Fake([])) == zero
    assert extract_cost_analysis(Fake("bogus")) == zero
    assert extract_cost_analysis(Fake(RuntimeError("no model"))) == zero
    # list-of-dicts (what this CPU backend actually returns) + partial keys
    got = extract_cost_analysis(Fake([{"flops": 7.0}]))
    assert got["flops"] == 7.0 and got["bytes_accessed"] == 0.0
    # negative sentinel values are clamped, non-numeric ignored
    got = extract_cost_analysis(Fake({"flops": -1.0, "bytes accessed": "x"}))
    assert got["flops"] == 0.0 and got["bytes_accessed"] == 0.0


def test_extract_memory_analysis_real_jit():
    c = _compiled(lambda x: (x @ x).sum(), jnp.ones((32, 32)))
    ma = extract_memory_analysis(c)
    if ma:  # backend exposes it (this jaxlib's CPU does)
        assert ma["peak_bytes"] == (ma.get("argument_bytes", 0.0)
                                    + ma.get("output_bytes", 0.0)
                                    + ma.get("temp_bytes", 0.0)
                                    - ma.get("alias_bytes", 0.0))


def test_cost_index_capture_and_cache():
    tr = Tracer(ring_size=256)
    set_tracer(tr)
    ci = CompiledCostIndex()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((16, 16))
    f(x)  # warm first, so the cache size is stable across observes
    rec = ci.observe("t/f", f, (x,))
    assert rec.error is None and rec.flops > 0
    assert rec.captures == 1
    # warm path: same cache size -> no re-capture
    f(x)
    rec2 = ci.observe("t/f", f, (x,))
    assert rec2.captures == 1
    # a perf/compiled instant landed with the registered schema args
    evs = [e for e in tr.events() if e["name"] == "perf/compiled"]
    assert len(evs) == 1
    assert evs[0]["args"]["entry"] == "t/f"
    assert not validate_events(tr.events(), strict=True)


def test_cost_index_recapture_on_recompile():
    ci = CompiledCostIndex()
    f = jax.jit(lambda x: (x * 2).sum())
    a = jnp.ones((8,))
    f(a)
    ci.observe("t/g", f, (a,))
    b = jnp.ones((16,))  # new shape -> jit cache grows
    f(b)
    rec = ci.observe("t/g", f, (b,))
    assert rec.captures == 2


def test_cost_index_observe_never_raises():
    ci = CompiledCostIndex()
    rec = ci.observe("t/broken", object(), ())  # no .lower at all
    assert rec.error is not None
    assert ci.summary()["t/broken"]["error"]


def test_cost_index_donated_args_abstractified():
    """Capture must work from the caller's (possibly donated) arrays."""
    ci = CompiledCostIndex()
    f = jax.jit(lambda s, x: (s + x, x.sum()), donate_argnums=(0,))
    s, x = jnp.ones((8,)), jnp.ones((8,))
    out, _ = f(s, x)  # s is now deleted
    rec = ci.observe("t/donate", f, (s, x))
    assert rec.error is None


def test_platform_peaks_keyed_by_device_kind_unknown_raises():
    """One table, keyed by device_kind; a device that is not in it is an
    error, never a v5e default."""
    import types

    dev = lambda kind, platform="tpu": types.SimpleNamespace(
        device_kind=kind, platform=platform)
    assert platform_peaks(dev("TPU v5 lite"))["source"] == "v5 lite"
    assert platform_peaks(dev("TPU v5 lite"))["peak_tflops"] == 197.0
    assert platform_peaks(dev("TPU v4"))["source"] == "v4"
    assert platform_peaks(dev("cpu", "cpu"))["source"] == "cpu"
    with pytest.raises(ValueError, match="TPU v9"):
        platform_peaks(dev("TPU v9"))
    with pytest.raises(ValueError, match="no peak-table row"):
        platform_peaks(dev("NVIDIA H100", "gpu"))


def test_program_and_benchmark_peak_tables_agree():
    """benchmark/peaks.py is deliberately independent of the program, so
    there are two tables: for every device the benchmark knows, the row
    the program matches gives the same bf16 FLOP/s, HBM bytes/s and HBM
    capacity. The interconnect is left out: the program says 160 GB/s,
    the benchmark 200 GB/s (1,600 Gbit/s), and no cell crosses chips yet
    (ROADMAP B4 decides)."""
    import types

    from benchmark import peaks as bench_peaks

    assert "TPU v5 lite" in bench_peaks.PEAKS
    for kind, theirs in bench_peaks.PEAKS.items():
        ours = platform_peaks(types.SimpleNamespace(device_kind=kind,
                                                    platform="tpu"))
        assert ours["peak_tflops"] * 1e12 == theirs["flops_per_s"], kind
        assert ours["peak_gbps"] * 1e9 == theirs["bytes_per_s"], kind
        assert ours["hbm_gib"] * 2**30 == theirs["hbm_bytes"], kind


def test_step_stats_mfu_and_verdict():
    ci = CompiledCostIndex()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    ci.observe("t/mm", f, (x,))
    stats = ci.step_stats("t/mm", wall_s=1.0)
    assert stats is not None
    peak = platform_peaks()["peak_tflops"] * 1e12
    rec = ci.get("t/mm")
    assert stats["mfu"] == pytest.approx(
        rec.flops / (peak * ci.local_devices))
    # a 64^3 matmul over a full second is overwhelmingly overhead; the
    # verdict names collectives on a multi-device mesh, the host on one
    expect = "comm-bound" if ci.local_devices > 1 else "host-bound"
    assert stats["verdict"] == expect
    assert ci.step_stats("t/mm", wall_s=0.0) is None
    assert ci.step_stats("t/missing", wall_s=1.0) is None


def test_trace_metadata_carries_cost_table(tmp_path):
    tr = Tracer(ring_size=64)
    set_tracer(tr)
    ci = CompiledCostIndex()
    ci.observe("t/meta", jax.jit(lambda x: x + 1), (jnp.ones((4,)),))
    path = tr.save(str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)
    assert "t/meta" in doc["otherData"]["perf"]


# ------------------------------------------------------------------ #
# memwatch
# ------------------------------------------------------------------ #


def test_memory_stats_cpu_fallback():
    # CPU backend has no allocator ledger: the normalized readers return
    # {} and every legacy shim keeps its historical shape
    assert device_memory_stats() == {}
    assert aggregate_memory_stats() == {}
    assert memory_status() == {"bytes_in_use": 0, "peak_bytes_in_use": 0}
    assert SynchronizedWallClockTimer.memory_usage().startswith("Memory:")


def test_memwatch_watermark_lane():
    tr = Tracer(ring_size=128)
    set_tracer(tr)
    mw = MemWatch()
    with tr.span("engine/forward", lane="engine") as sp:
        mw.annotate(sp, "forward")
    evs = tr.events()
    marks = [e for e in evs if e["name"] == "mem/watermark"]
    assert len(marks) == 1 and marks[0]["args"]["phase"] == "forward"
    spans = [e for e in evs if e["name"] == "engine/forward"]
    assert spans[0]["args"]["hbm_peak"] == 0  # zeros on CPU, key present
    assert not validate_events(evs, strict=True)


def test_memwatch_postmortem_through_flight(tmp_path):
    fpath = str(tmp_path / "f.bin")
    fl = flight_mod.FlightRecorder(fpath, capacity=64)
    tr = Tracer(ring_size=128, flight=fl)
    set_tracer(tr)
    # a live buffer the dump must see — big enough to stay in the
    # top-k cut even when earlier suite modules left arrays alive
    x = jnp.ones((1024, 1024))
    mw = MemWatch(top_k=4)
    payload = mw.post_mortem("test oom")
    assert payload["live_buffers"] >= 1
    assert any(b["shape"] == "1024x1024" for b in payload["buffers"])
    for b in payload["buffers"]:
        assert set(b) == {"shape", "dtype", "nbytes", "sharding"}
    fl.flush()
    # the dump rode the tracer's inline flight sink: recoverable from
    # disk as a SIGKILLed process would leave it
    snap = flight_mod.recover(fpath)
    names = [e["name"] for e in snap.events]
    assert "mem/postmortem" in names and "mem/buffer" in names
    buf = next(e for e in snap.events if e["name"] == "mem/buffer")
    assert buf["args"]["nbytes"] > 0
    assert mw.postmortems == 1
    del x


def test_memwatch_near_oom_trip(monkeypatch):
    tr = Tracer(ring_size=64)
    set_tracer(tr)
    mw = MemWatch(near_oom_fraction=0.9)
    fake = {"bytes_in_use": 95, "peak_bytes_in_use": 99, "bytes_limit": 100}
    monkeypatch.setattr("deeperspeed_tpu.monitor.memwatch."
                        "aggregate_memory_stats", lambda: fake)
    mw.sample("step")
    assert mw.postmortems == 1
    mw.sample("step")  # still high: disarmed, no second dump
    assert mw.postmortems == 1
    fake = {"bytes_in_use": 10, "peak_bytes_in_use": 99, "bytes_limit": 100}
    monkeypatch.setattr("deeperspeed_tpu.monitor.memwatch."
                        "aggregate_memory_stats", lambda: fake)
    mw.sample("step")  # usage fell: re-arms
    fake = {"bytes_in_use": 95, "peak_bytes_in_use": 99, "bytes_limit": 100}
    monkeypatch.setattr("deeperspeed_tpu.monitor.memwatch."
                        "aggregate_memory_stats", lambda: fake)
    mw.sample("step")
    assert mw.postmortems == 2


def test_memwatch_bad_fraction():
    with pytest.raises(ValueError):
        MemWatch(near_oom_fraction=0.0)


# ------------------------------------------------------------------ #
# engine + serving integration (the acceptance criterion)
# ------------------------------------------------------------------ #


def _loss_fn(params, batch):
    x, y = batch
    return (((x @ params["w"]) - y) ** 2).mean()


def test_engine_train_batch_carries_mfu_and_hbm(tmp_path):
    trace = str(tmp_path / "t.json")
    engine, *_ = deepspeed.initialize(
        model=_loss_fn, model_parameters={"w": jnp.zeros((8, 2))},
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "monitor": {"trace_path": trace, "perf": True},
        })
    x = np.ones((8, 8), np.float32)
    y = np.zeros((8, 2), np.float32)
    for _ in range(3):
        engine.train_batch((x, y))
    mon = get_monitor()
    summary = mon.cost_index.summary()
    assert summary["engine/train_step"]["flops"] > 0
    evs = mon.tracer.events()
    tb = [e for e in evs if e["name"] == "engine/train_batch"]
    assert tb and {"mfu", "verdict", "hbm_peak"} <= set(tb[-1]["args"])
    steps = [e for e in evs if e["name"] == "perf/step"]
    assert steps and steps[-1]["args"]["entry"] == "engine/train_step"
    # MFU gauge exported
    assert any("perf_mfu" in line
               for line in mon.registry.render().splitlines())
    shutdown_monitor(save=True)
    assert not __import__("deeperspeed_tpu.monitor.validate",
                          fromlist=["validate_file"]).validate_file(
                              trace, strict=True)


def test_engine_imperative_path_captures_cost():
    engine, *_ = deepspeed.initialize(
        model=_loss_fn, model_parameters={"w": jnp.zeros((8, 2))},
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "monitor": {"perf": True},
        })
    x = np.ones((8, 8), np.float32)
    y = np.zeros((8, 2), np.float32)
    loss = engine.forward((x, y))
    engine.backward(loss)
    engine.step()
    summary = get_monitor().cost_index.summary()
    assert summary["engine/forward_grad"]["flops"] > 0
    assert "engine/apply_update" in summary


def test_engine_perf_off_no_cost_index():
    engine, *_ = deepspeed.initialize(
        model=_loss_fn, model_parameters={"w": jnp.zeros((8, 2))},
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "monitor": {"trace_enabled": True},
        })
    assert get_monitor().cost_index is None
    x = np.ones((8, 8), np.float32)
    y = np.zeros((8, 2), np.float32)
    engine.train_batch((x, y))  # default path untouched
    evs = get_monitor().tracer.events()
    tb = [e for e in evs if e["name"] == "engine/train_batch"]
    assert "mfu" not in tb[-1].get("args", {})


def test_serving_decode_carries_mfu_stays_one_compile():
    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
    from deeperspeed_tpu.serving import ServingEngine
    from deeperspeed_tpu.serving.config import ServingConfig

    mon = init_monitor({"perf": True})
    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=2, d_model=32,
                    max_seq=64, remat=False, dtype=jnp.float32,
                    attn_impl="xla")
    init_fn, _, _, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(0))
    scfg = ServingConfig(num_slots=2, block_size=4, num_blocks=32,
                        max_seq_len=48)
    eng = ServingEngine(cfg, params, scfg)
    rid = eng.submit([5, 6, 7, 8], max_new_tokens=3)
    for _ in range(16):
        eng.step()
        if eng.get(rid).state == "finished":
            break
    assert eng.get(rid).state == "finished"
    # cost capture must NOT add decode compiles (AOT lowering is outside
    # the jit cache) — the one-compile invariant the serving tests
    # key on
    assert eng.decode_compile_count == 1
    summary = mon.cost_index.summary()
    assert summary["serving/decode_step"]["flops"] > 0
    assert any(k.startswith("serving/prefill_step[b") for k in summary)
    evs = mon.tracer.events()
    dec = [e for e in evs if e["name"] == "serving/decode"]
    assert {"mfu", "hbm_peak"} <= set(dec[-1]["args"])
    assert not validate_events(evs, strict=True)
