"""Default program suite: every jitted entry point the repo ships,
built at toy scale so the CLI can audit the real lowered programs
without hardware.

Three engines cover the jit surface:

* a ZeRO-3 train engine on the canonical ``dp × fsdp`` mesh — the
  fused ``engine/train_step`` plus the imperative pair
  (``engine/forward_grad``, ``engine/apply_update``); this is where
  donation, fp64, and the ZeRO-3 gather-leak checks bite,
* a comm engine (int8 bucketed collectives on the legacy data mesh) —
  the fused comm train step with its shard_map reduction buckets plus
  one standalone per-bucket reducer (``comm/reduce[b0]``); this is
  where the collective-axis checks see real named collectives,
* a serving engine — one prefill bucket and the donated decode step.

Multi-device engines are skipped gracefully on a 1-device host (the
``__main__`` CLI forces 8 virtual CPU devices before jax imports, so
the full suite runs there; ``scripts/tpu_smoke.py`` re-runs the same
suite against real-TPU lowerings).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from .hlo import ProgramSpec

__all__ = ["default_program_suite", "audit_default_programs"]


def _param_bytes(tree) -> Tuple[int, int]:
    import jax
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "nbytes")]
    if not leaves:
        return 0, 0
    return sum(int(x.nbytes) for x in leaves), max(int(x.nbytes)
                                                  for x in leaves)


def _train_specs(notes: List[str]) -> List[ProgramSpec]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deeperspeed_tpu as deepspeed

    n_dev = jax.device_count()
    multi = n_dev >= 2 and n_dev % 2 == 0

    def _loss(p, batch):
        h = jnp.tanh(batch @ p["w1"])
        return jnp.mean((h @ p["w2"]) ** 2)

    params = {"w1": jnp.zeros((64, 128), jnp.float32),
              "w2": jnp.zeros((128, 32), jnp.float32)}
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    if multi:
        cfg["zero_optimization"] = {"stage": 3}
        cfg["mesh"] = {"dp": 2, "fsdp": -1}
        zero_stage = 3
    else:
        notes.append("train: single-device host — ZeRO-3 mesh audit "
                     "degraded to an unsharded engine")
        zero_stage = 0
    engine, _, _, _ = deepspeed.initialize(
        model=_loss, model_parameters=params, config_params=cfg)

    raw = np.ones((8, 64), np.float32)
    engine.train_batch(batch=raw)  # commit sharding + build every fn
    batch = engine._pack_pld(engine._place_batch(raw))
    rng = engine._rng_args()
    lr = np.float32(engine._current_lr())
    total, largest = _param_bytes(engine.state.params)

    specs = [ProgramSpec(
        name="engine/train_step", fn=engine._train_batch_fn(),
        args=(engine.state, batch, lr, rng), mesh=engine.mesh,
        zero_stage=zero_stage, hot=True,
        param_bytes_total=total, param_bytes_largest=largest)]
    specs.append(ProgramSpec(
        name="engine/forward_grad", fn=engine._forward_grad_fn(),
        args=(engine.state, batch, rng), mesh=engine.mesh,
        zero_stage=zero_stage, hot=True,
        param_bytes_total=total, param_bytes_largest=largest))
    grads = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
        engine.state.params)
    specs.append(ProgramSpec(
        name="engine/apply_update", fn=engine._apply_update_fn(),
        args=(engine.state, grads, lr, np.float32(1.0)),
        mesh=engine.mesh, zero_stage=zero_stage, hot=True,
        param_bytes_total=total, param_bytes_largest=largest))
    return specs


def _comm_specs(notes: List[str]) -> List[ProgramSpec]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deeperspeed_tpu as deepspeed

    if jax.device_count() < 2:
        notes.append("comm: single-device host — bucketed-collective "
                     "audit skipped")
        return []

    def _loss(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2)

    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "comm": {"mode": "int8", "bucket_mb": 0.001, "block": 128},
    }
    engine, _, _, _ = deepspeed.initialize(
        model=_loss, model_parameters={"w": jnp.zeros((64, 32),
                                                      jnp.float32)},
        config_params=cfg)

    raw = np.ones((8, 64), np.float32)
    engine.train_batch(batch=raw)  # builds the bucket plan + comm state
    batch = engine._pack_pld(engine._place_batch(raw))
    rng = engine._rng_args()
    lr = np.float32(engine._current_lr())
    total, largest = _param_bytes(engine.state.params)

    specs = [ProgramSpec(
        name="engine/train_step[comm]", fn=engine._train_batch_fn(),
        args=(engine.state, engine._comm_state, batch, lr, rng),
        mesh=engine.mesh, hot=True,
        param_bytes_total=total, param_bytes_largest=largest)]
    comm = engine.comm
    if comm is not None and getattr(comm, "n_buckets", 0) > 0:
        # the standalone reducer takes per-device LOCAL gradient stacks
        # (leading axis = data-parallel world), exactly what the
        # unfused backward() hands it
        ndev = int(np.prod(engine.mesh.devices.shape))
        from jax.sharding import NamedSharding, PartitionSpec as P
        ax = engine.mesh.axis_names[0]

        def _stack(p):
            sh = NamedSharding(engine.mesh,
                               P(ax, *([None] * len(p.shape))))
            return jax.device_put(
                jnp.zeros((ndev,) + tuple(p.shape), p.dtype), sh)

        stacked = jax.tree_util.tree_leaves(
            jax.tree.map(_stack, engine.state.params))
        b = comm.plan.buckets[0]
        specs.append(ProgramSpec(
            name="comm/reduce[b0]", fn=comm._bucket_reduce_fn(0),
            args=([stacked[i] for i in b.leaf_ids],
                  engine._comm_state[0]),
            mesh=engine.mesh, hot=True))
    return specs


def _serving_specs(notes: List[str]) -> List[ProgramSpec]:
    import jax
    import jax.numpy as jnp

    from ..models.gpt import GPTConfig, make_gpt
    from ..serving import ServingConfig, ServingEngine, idle_slots

    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=2, d_model=32,
                    max_seq=64, remat=False, dtype=jnp.float32,
                    attn_impl="xla")
    init_fn, _, _, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(0))
    scfg = ServingConfig(num_slots=2, block_size=4, num_blocks=32,
                         max_seq_len=48)
    eng = ServingEngine(cfg, params, scfg)

    bucket = eng.scfg.bucket_for(9)
    toks = jnp.zeros((1, bucket), jnp.int32)
    specs = [ProgramSpec(
        name=f"serving/prefill_step[b{bucket}]", fn=eng._prefill_step,
        args=(eng.params, toks), hot=False)]

    dargs = (eng.params, eng.kv.k, eng.kv.v, jnp.asarray(
        idle_slots(scfg.num_slots, scfg.blocks_per_slot)), eng._prev)
    specs.append(ProgramSpec(
        name="serving/decode_step", fn=eng._decode_step, args=dargs,
        hot=True))
    return specs


def default_program_suite(notes: Optional[List[str]] = None
                          ) -> List[ProgramSpec]:
    """Build every auditable entry point; ``notes`` collects coverage
    degradations (e.g. single-device hosts) so nothing is silently
    skipped."""
    if notes is None:
        notes = []
    specs: List[ProgramSpec] = []
    specs.extend(_train_specs(notes))
    specs.extend(_comm_specs(notes))
    specs.extend(_serving_specs(notes))
    return specs


def audit_default_programs(notes: Optional[List[str]] = None):
    from .hlo import audit_programs
    return audit_programs(default_program_suite(notes))
