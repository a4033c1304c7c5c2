"""End-to-end engine tests on the 8-device CPU mesh (parity with reference
tests/unit/test_fp16.py + test_checkpointing.py basics)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeperspeed_tpu as ds
from tests.simple_model import (
    RandomDataset,
    base_config,
    init_linear_stack,
    linear_stack_loss,
)

DIMS = [16, 32, 16]


def make_engine(zero_stage=0, precision=None, gas=1, lr=1e-2, optimizer="Adam", **extra):
    params = init_linear_stack(jax.random.PRNGKey(0), DIMS)
    cfg = base_config(
        micro_batch=4,
        gas=gas,
        lr=lr,
        precision=precision,
        zero_stage=zero_stage,
        optimizer=optimizer,
        **extra,
    )
    engine, _, _, _ = ds.initialize(
        model=linear_stack_loss, model_parameters=params, config=cfg
    )
    return engine


_DATASET = RandomDataset(512, DIMS[0], DIMS[-1], seed=0)


def global_batch(engine, n_micro=1, seed=0):
    """A deterministic slice of the shared dataset (seed picks the offset)."""
    size = (
        engine.train_micro_batch_size_per_gpu()
        * engine.data_parallel_size
        * n_micro
    )
    start = (seed * size) % (len(_DATASET) - size + 1)
    idx = np.arange(start, start + size)
    x = np.stack([_DATASET[i][0] for i in idx])
    y = np.stack([_DATASET[i][1] for i in idx])
    return (x, y)


def train_steps(engine, steps=10, seed=0):
    gas = engine.gradient_accumulation_steps()
    losses = []
    for s in range(steps):
        batch = global_batch(engine, n_micro=gas, seed=seed + s)
        loss = engine.train_batch(batch)
        losses.append(float(jax.device_get(loss)))
    return losses


def test_zero3_consolidated_state_dict():
    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    engine, _, _, _ = ds.initialize(
        model=loss_fn, model_parameters={"w": jnp.ones((8, 2))},
        config_params={"train_batch_size": 8,
                       "zero_optimization": {"stage": 3},
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
    )
    sd = engine.zero3_consolidated_fp16_state_dict()
    assert isinstance(sd["w"], np.ndarray)
    assert sd["w"].shape == (8, 2)  # full, not the 1/8 shard
    np.testing.assert_allclose(sd["w"], 1.0)
    assert engine.module_state_dict()["w"].shape == (8, 2)


def test_wall_clock_breakdown_timers():
    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    engine, _, _, _ = ds.initialize(
        model=loss_fn, model_parameters={"w": jnp.zeros((4, 1))},
        config_params={"train_batch_size": 8,
                       "wall_clock_breakdown": True,
                       "steps_per_print": 2,
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
    )
    x = np.random.RandomState(0).randn(8, 4).astype(np.float32)
    y = np.random.RandomState(1).randn(8, 1).astype(np.float32)
    batch = (jnp.asarray(x), jnp.asarray(y))
    for _ in range(2):
        engine.train_batch(batch=batch)
    assert "train_batch" in engine.timers.timers
    # imperative path populates the micro timers too
    loss = engine(batch)
    engine.backward(loss)
    engine.step()
    assert "forward_microstep" in engine.timers.timers
    assert "step_microstep" in engine.timers.timers


def test_train_loss_decreases():
    engine = make_engine()
    losses = train_steps(engine, steps=20, seed=42)
    assert losses[-1] < losses[0] * 0.5, losses


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_match_stage0(stage):
    """All ZeRO stages must produce numerically equivalent training."""
    ref = make_engine(zero_stage=0)
    ref_losses = train_steps(ref, steps=5, seed=7)
    eng = make_engine(zero_stage=stage)
    losses = train_steps(eng, steps=5, seed=7)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    # final params identical too
    p_ref = jax.device_get(ref.state.params)
    p_new = jax.device_get(eng.state.params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        p_ref,
        p_new,
    )


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_state_is_sharded(stage):
    engine = make_engine(zero_stage=stage, precision="bf16")
    # the largest master leaf must be sharded over the data axis
    w = engine.state.master["layer_0"]["w"]
    shardings = {s for s in w.sharding.spec}
    assert "data" in shardings
    if stage >= 3:
        wp = engine.state.params["layer_0"]["w"]
        assert "data" in set(wp.sharding.spec)


def test_bf16_training():
    engine = make_engine(precision="bf16", zero_stage=2)
    losses = train_steps(engine, steps=20, seed=3)
    assert losses[-1] < losses[0] * 0.6
    assert engine.state.params["layer_0"]["w"].dtype == jnp.bfloat16
    assert engine.state.master["layer_0"]["w"].dtype == jnp.float32


def test_gradient_accumulation_equivalence():
    """gas=2 over a batch must equal gas=1 with doubled micro batch (both see
    the same samples in one optimizer step)."""
    params = init_linear_stack(jax.random.PRNGKey(0), DIMS)
    cfg_gas = base_config(micro_batch=4, gas=2, lr=1e-2)
    cfg_big = base_config(micro_batch=8, gas=1, lr=1e-2)
    e_gas, _, _, _ = ds.initialize(
        model=linear_stack_loss, model_parameters=params, config=cfg_gas
    )
    e_big, _, _, _ = ds.initialize(
        model=linear_stack_loss, model_parameters=params, config=cfg_big
    )
    for s in range(3):
        batch = global_batch(e_big, n_micro=1, seed=100 + s)  # 64 samples
        e_gas.train_batch(batch)
        e_big.train_batch(batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            jax.device_get(a), jax.device_get(b), rtol=1e-4, atol=1e-6
        ),
        e_gas.state.params,
        e_big.state.params,
    )


def test_forward_backward_step_api():
    engine = make_engine(gas=2)
    losses = []
    for s in range(8):
        batch = global_batch(engine, n_micro=1, seed=200 + s)
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    assert engine.global_steps == 4  # gas=2 -> an optimizer step every 2 micros
    assert losses[-1] < losses[0]


def test_eval_mode_no_update():
    engine = make_engine()
    p0 = jax.device_get(engine.state.params["layer_0"]["w"])
    engine.eval()
    batch = global_batch(engine)
    loss = engine(batch)
    assert np.isfinite(float(jax.device_get(loss)))
    p1 = jax.device_get(engine.state.params["layer_0"]["w"])
    np.testing.assert_array_equal(p0, p1)


def test_lamb_optimizer():
    engine = make_engine(optimizer="Lamb", lr=2e-2)
    losses = train_steps(engine, steps=30, seed=5)
    assert losses[-1] < losses[0] * 0.7


def test_sgd_optimizer():
    engine = make_engine(optimizer="SGD", lr=5e-2)
    losses = train_steps(engine, steps=30, seed=5)
    assert losses[-1] < losses[0] * 0.9


def test_scheduler_steps():
    engine = make_engine(
        scheduler={
            "type": "WarmupLR",
            "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01, "warmup_num_steps": 10},
        }
    )
    lr0 = engine.get_lr()[0]
    train_steps(engine, steps=5)
    lr5 = engine.get_lr()[0]
    assert lr5 > lr0


def test_checkpoint_roundtrip(tmp_path):
    engine = make_engine(zero_stage=2, precision="bf16")
    train_steps(engine, steps=5, seed=11)
    engine.save_checkpoint(str(tmp_path), client_state={"note": "hello"})

    # fresh engine, load, continue — states must match
    engine2 = make_engine(zero_stage=2, precision="bf16")
    path, client = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    assert client["note"] == "hello"
    assert engine2.global_steps == engine.global_steps
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(jax.device_get(a), jax.device_get(b)),
        engine.state.params,
        engine2.state.params,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(jax.device_get(a), jax.device_get(b)),
        engine.state.opt_state.exp_avg,
        engine2.state.opt_state.exp_avg,
    )
    # training continues identically
    l1 = train_steps(engine, steps=3, seed=12)
    l2 = train_steps(engine2, steps=3, seed=12)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_checkpoint_latest_tag(tmp_path):
    engine = make_engine()
    train_steps(engine, steps=2)
    engine.save_checkpoint(str(tmp_path), tag="tag_a")
    engine.save_checkpoint(str(tmp_path), tag="tag_b")
    from deeperspeed_tpu.checkpoint import read_latest

    assert read_latest(str(tmp_path)) == "tag_b"


def test_zero_to_fp32_consolidation(tmp_path):
    engine = make_engine(zero_stage=2, precision="bf16")
    train_steps(engine, steps=3)
    engine.save_checkpoint(str(tmp_path), tag="final")
    from deeperspeed_tpu.checkpoint import consolidate_fp32_state

    fp32 = consolidate_fp32_state(str(tmp_path / "final"))
    ref = jax.device_get(engine.state.master)
    got = np.asarray(jax.tree.leaves(fp32)[0])
    want = np.asarray(jax.tree.leaves(ref)[0])
    np.testing.assert_allclose(got, want)


def test_onebit_adam_optimizer():
    engine = make_engine(optimizer="OneBitAdam", lr=1e-2)
    losses = train_steps(engine, steps=20, seed=9)
    assert losses[-1] < losses[0] * 0.6


def test_onebit_adam_compression_phase():
    """After freeze_step the variance freezes and momentum is 1-bit
    compressed; training must still make progress."""
    params = init_linear_stack(jax.random.PRNGKey(0), DIMS)
    cfg = base_config(micro_batch=4, lr=5e-3)
    cfg["optimizer"] = {
        "type": "OneBitAdam",
        "params": {"lr": 5e-3, "freeze_step": 3},
    }
    engine, _, _, _ = ds.initialize(
        model=linear_stack_loss, model_parameters=params, config=cfg
    )
    losses = train_steps(engine, steps=25, seed=9)
    assert losses[-1] < losses[0]
    v_before = jax.device_get(engine.state.opt_state.exp_avg_sq)
    train_steps(engine, steps=2, seed=50)
    v_after = jax.device_get(engine.state.opt_state.exp_avg_sq)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, b), v_before, v_after
    )


class TestMasterlessBf16:
    """Memory-lean bf16 mode (bf16.master_weights=false): no fp32 master,
    bf16-stored optimizer moments, bf16 grads — 4 bytes/param of state, the
    mode that fits billion-param models on one chip (the cell neox-1.3b.train)."""

    CFG = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 2,
        "bf16": {"enabled": True, "master_weights": False},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "gradient_clipping": 1.0,
    }

    @staticmethod
    def _model():
        def init(key):
            k1, k2 = jax.random.split(key)
            return {"w1": jax.random.normal(k1, (16, 32)) * 0.3,
                    "w2": jax.random.normal(k2, (32, 1)) * 0.3}

        def loss_fn(params, batch):
            x, y = batch
            h = jnp.tanh(x @ params["w1"].astype(jnp.bfloat16))
            out = h @ params["w2"].astype(jnp.bfloat16)
            return jnp.mean(
                (out - y.astype(jnp.bfloat16)).astype(jnp.float32) ** 2
            )

        return init, loss_fn

    def test_state_dtypes_and_convergence(self):
        init, loss_fn = self._model()
        eng, _, _, _ = ds.initialize(
            model=loss_fn, model_parameters=init(jax.random.PRNGKey(0)),
            config=dict(self.CFG),
        )
        assert eng.state.master is None
        assert eng.state.params["w1"].dtype == jnp.bfloat16
        assert eng.state.opt_state.exp_avg["w1"].dtype == jnp.bfloat16
        rng = np.random.default_rng(0)
        W = rng.normal(size=(16, 1)).astype(np.float32)
        losses = []
        for _ in range(40):
            X = rng.normal(size=(8, 16)).astype(np.float32)
            losses.append(float(jax.device_get(eng.train_batch((X, X @ W)))))
        assert losses[-1] < losses[0] / 3

    def test_checkpoint_round_trip_without_master(self, tmp_path):
        init, loss_fn = self._model()
        eng, _, _, _ = ds.initialize(
            model=loss_fn, model_parameters=init(jax.random.PRNGKey(0)),
            config=dict(self.CFG),
        )
        rng = np.random.default_rng(0)
        W = rng.normal(size=(16, 1)).astype(np.float32)
        for _ in range(4):
            X = rng.normal(size=(8, 16)).astype(np.float32)
            eng.train_batch((X, X @ W))
        eng.save_checkpoint(str(tmp_path))
        eng2, _, _, _ = ds.initialize(
            model=loss_fn, model_parameters=init(jax.random.PRNGKey(1)),
            config=dict(self.CFG),
        )
        path, _ = eng2.load_checkpoint(str(tmp_path))
        assert path is not None
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(eng.state.params["w1"])).view(np.uint16),
            np.asarray(jax.device_get(eng2.state.params["w1"])).view(np.uint16),
        )

    def test_fp16_masterless_rejected(self):
        init, loss_fn = self._model()
        with pytest.raises(ValueError, match="master"):
            ds.initialize(
                model=loss_fn, model_parameters=init(jax.random.PRNGKey(0)),
                config={"train_micro_batch_size_per_gpu": 4,
                        "fp16": {"enabled": True, "master_weights": False}},
            )


class TestReferenceAccessors:
    """Reference engine accessor parity (engine.py:256-1315 surface)."""

    def _engine(self):
        eng, _, _, _ = ds.initialize(
            model=lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
            model_parameters={"w": jnp.ones((4, 1), jnp.float32)},
            config={"train_batch_size": 16,
                    "train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "Adam",
                                  "params": {"lr": 1e-2, "betas": [0.9, 0.98]}}},
        )
        return eng

    def test_batch_info_and_params(self):
        eng = self._engine()
        assert eng.get_batch_info() == (16, 2, 1)
        assert eng.get_mom() == [[0.9, 0.98]]
        assert eng.optimizer_name().lower() == "adam"
        assert eng.scheduler_name() is None
        assert eng.elasticity_enabled() is False
        assert eng.sparse_gradients_enabled() is False
        assert eng.get_pld_theta() is None

    def test_set_lr(self):
        eng = self._engine()
        eng.set_lr(5e-3)
        assert eng.get_lr() == [5e-3]

    def test_save_fp16_model(self, tmp_path):
        eng = self._engine()
        path = eng.save_fp16_model(str(tmp_path))
        assert os.path.exists(path)

    def test_set_lr_with_scheduler(self):
        eng, _, _, _ = ds.initialize(
            model=lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
            model_parameters={"w": jnp.ones((4, 1), jnp.float32)},
            config={"train_batch_size": 32,
                    "train_micro_batch_size_per_gpu": 4,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "scheduler": {"type": "WarmupLR",
                                  "params": {"warmup_max_lr": 1e-2,
                                             "warmup_num_steps": 100}}},
        )
        eng.set_lr(5e-3)
        assert eng.get_lr() == [5e-3]  # pin holds before the next step
        X = np.ones((32, 4), np.float32)
        Y = np.zeros((32, 1), np.float32)
        eng.train_batch((X, Y))
        # the scheduler reclaims the lr at its step, like torch param_groups
        assert eng.get_lr() != [5e-3]

@pytest.mark.parametrize("stage", [1, 2])
def test_masterless_composes_with_zero(stage):
    """Masterless bf16 + ZeRO: moments shard over the data axis while
    the bf16 params stay per the param specs — training converges."""
    init, loss_fn = TestMasterlessBf16._model()
    eng, _, _, _ = ds.initialize(
        model=loss_fn, model_parameters=init(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True, "master_weights": False},
                "zero_optimization": {"stage": stage},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "gradient_clipping": 1.0},
    )
    assert eng.state.master is None
    rng = np.random.default_rng(0)
    W = rng.normal(size=(16, 1)).astype(np.float32)
    losses = []
    for _ in range(25):
        X = rng.normal(size=(16, 16)).astype(np.float32)
        losses.append(float(jax.device_get(eng.train_batch((X, X @ W)))))
    assert losses[-1] < losses[0] / 2, losses


def test_masterless_bf16_fp32_grad_accumulation():
    """bf16.grad_accum_dtype=fp32 must change what the bf16 carry rounds
    away: accumulate one large microbatch grad (1.0) followed by seven tiny
    ones (0.002, below bf16's ulp at 1.0) — the bf16 carry stays at 1.0,
    the fp32 carry reaches 1.014 and rounds ONCE on the final cast."""
    import deeperspeed_tpu as ds

    def make(gad):
        # single-leaf linear loss: dL/dw = mean over batch elements of x
        params = {"w": jnp.zeros((4,), jnp.float32)}

        def loss(p, batch):
            return jnp.mean(p["w"] * batch)

        bf16 = {"enabled": True, "master_weights": False}
        if gad:
            bf16["grad_accum_dtype"] = gad
        engine, _, _, _ = ds.initialize(
            model=loss, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 8,
                    "optimizer": {"type": "Adam",
                                  "params": {"lr": 1e-2,
                                             "betas": [0.9, 0.95]}},
                    "bf16": bf16},
        )
        return engine

    eng32, eng16 = make("fp32"), make(None)
    assert eng32._grad_accum_dtype == jnp.float32
    assert eng16._grad_accum_dtype == jnp.bfloat16
    assert eng32._grad_dtype == jnp.bfloat16

    dp = eng32.data_parallel_size
    rows = np.full((8 * dp, 4), 0.002, np.float32)
    rows[:dp] = 1.0  # microbatch 0 large, the rest tiny
    batch = jnp.asarray(rows)

    def accumulated(eng):
        _, grads = eng._batch_grads(
            eng.state, batch, jax.random.PRNGKey(0), 8)
        return float(np.asarray(grads["w"], np.float32)[0])

    g32, g16 = accumulated(eng32), accumulated(eng16)
    # per-microbatch grad = x/4: large mb -> 0.25, tiny mbs -> 0.0005 each
    # (below bf16's ulp/2 at 0.25). bf16 carry: every tiny add rounds back
    # to 0.25. fp32 carry: 0.2535, rounded ONCE to bf16 on the final cast.
    assert abs(g16 - 0.25) < 1e-7, g16
    assert g32 > 0.2525, g32
