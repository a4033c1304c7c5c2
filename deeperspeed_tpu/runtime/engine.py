"""The training engine.

Capability parity with /root/reference/deepspeed/runtime/engine.py
(`DeepSpeedEngine` :102): wraps a user model with mixed precision, ZeRO
sharding, gradient accumulation, loss scaling, gradient clipping, LR
scheduling, throughput/wall-clock instrumentation, and checkpoint
save/load — re-architected for XLA:

  * the hot path is ONE jitted train step (`train_batch`) that scans over
    gradient-accumulation microbatches and applies the optimizer at the
    boundary; collectives are derived from sharding constraints (see
    zero/partition.py) instead of backward hooks + bucketed NCCL calls
    (reference engine.py:1023-1453).
  * the reference's imperative `forward()/backward()/step()` triple is kept:
    forward computes loss+grads fused, backward banks the grads, step applies
    the update at the accumulation boundary.

Model contract: a callable `loss_fn(params, batch)` or
`loss_fn(params, batch, rng)` returning a scalar loss (optionally
`(loss, aux)`), plus an initial params pytree — the JAX analog of passing an
nn.Module whose forward returns the loss.
"""

import inspect
import os
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint.serialization import (
    SHARDED_STATE_DIR,
    CheckpointEngine,
    load_sharded_tree,
    load_sharded_tree_raw,
    model_state_filename,
    optim_state_filename,
    read_latest,
    save_sharded_tree,
    sharded_tree_top_keys,
    to_host,
    validate_tag_across_processes,
    write_latest,
)
from ..ops import kernel_config
from ..ops.adam import DeepSpeedCPUAdam, FusedAdam
from ..ops.lamb import FusedLamb
from ..ops.sgd import SGD
from ..monitor import (
    get_monitor,
    init_monitor,
    install_compile_listener,
    trace_instant,
    trace_span,
)
from ..resilience.manifest import resolve_load_tag
from ..parallel.topology import DATA_AXIS  # noqa: F401 — re-exported for callers
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from . import lr_schedules
from .accessors import ConfigAccessorsMixin, make_summary_writer
from .config import TrainingConfig
from .dataloader import DeepSpeedDataLoader
from .fp16.loss_scaler import LossScaleState, create_loss_scaler
from .zero import partition
from .. import sharding

FORWARD_MICRO_TIMER = "forward_microstep"
BACKWARD_MICRO_TIMER = "backward_microstep"
STEP_MICRO_TIMER = "step_microstep"

ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
SGD_OPTIMIZER = "sgd"
CPU_ADAM_OPTIMIZER = "cpuadam"


class EngineState(NamedTuple):
    """All device-side training state; one pytree so jit can donate it."""

    step: jnp.ndarray  # i32 global (optimizer) steps taken
    params: Any  # compute-dtype params
    master: Any  # fp32 master params (None when compute dtype is fp32)
    opt_state: Any
    scaler: LossScaleState
    skipped: jnp.ndarray  # i32 overflow-skipped steps


def _dtype_of(precision: str):
    return {
        "fp16": jnp.float16,
        "bfloat16": jnp.bfloat16,
        "fp32": jnp.float32,
    }[precision]


class Engine(ConfigAccessorsMixin):
    def __init__(
        self,
        model: Callable,
        params: Any,
        config: TrainingConfig,
        mesh=None,
        optimizer=None,
        lr_scheduler=None,
        training_data=None,
        collate_fn=None,
        param_specs: Any = None,
        rng: Optional[jax.Array] = None,
        mpu=None,
        batch_axis_in_batch: int = 0,
    ):
        # the compile account by program name (monitor.compile_account)
        # is kept whether or not a monitor is, from before the first
        # array is placed: a dict update per compile, nothing per step
        install_compile_listener()
        self._config = config
        self.loss_fn = model
        self.module = model  # reference-compatible alias
        self.mpu = mpu
        # multi-host: a "distributed" block brings jax.distributed up
        # BEFORE the mesh is built, so MeshConfig layouts resolve over
        # the global (process-spanning) device list. Idempotent — a
        # launcher that already called init_distributed is adopted.
        dist_cfg = (config.distributed_config()
                    if hasattr(config, "distributed_config") else None)
        if dist_cfg is not None:
            from ..distributed import bootstrap as _dist_bootstrap

            _dist_bootstrap.bootstrap(dist_cfg)
        if mesh is None:
            mesh_cfg = (config.mesh_config()
                        if hasattr(config, "mesh_config") else None)
            mesh = (sharding.from_config(mesh_cfg)
                    if mesh_cfg is not None else _default_mesh())
        self.mesh = mesh
        # the batch dim (and the grad mean) spans all batch axes — dp AND
        # fsdp on a canonical mesh, the legacy data axis otherwise
        self.batch_axes = sharding.batch_axes(self.mesh)
        self.data_parallel_size = sharding.data_parallel_size(self.mesh)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        # per-dispatch rng derivation happens INSIDE the jitted step
        # (fold_in(base, ticket)); a host-side jax.random.split per call
        # would cost a full extra device dispatch on the hot path
        self._rng_tick = 0

        self._takes_rng = _loss_fn_takes_rng(model)
        # PLD (reference engine.py:972 passes pld.get_state() kwargs into the
        # module forward; here theta rides along as a traced scalar)
        self.progressive_layer_drop = None
        if config.pld_enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop

            pld_params = config.pld_params or {}
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_params.get("theta", 0.5),
                gamma=pld_params.get("gamma", 0.001),
            )
        self._takes_pld = _loss_fn_takes_pld(model)
        # batch-size warmup scheduler (fork bs_schedules.py). The engine
        # tracks the schedule and exposes current_batch_size(); the data
        # pipeline reads it — on TPU the array SHAPES stay fixed (no
        # retrace) and the loader masks/subsets rows.
        self.batch_size_scheduler = None
        if config.batch_scheduler_enabled:
            from .bs_schedules import BatchSizeScheduler

            known = ("final_batch_size", "min_batch_size_multiplier",
                     "warmup_num_steps", "num_intervals",
                     "last_batch_iteration")
            bs_params = {k: v for k, v in config.batch_scheduler_params.items()
                         if k in known}
            unknown = set(config.batch_scheduler_params) - set(known) - {"enabled"}
            if unknown:
                raise ValueError(
                    f"batch_scheduler config has unknown keys {sorted(unknown)}; "
                    f"valid keys: {list(known)}"
                )
            bs_params.setdefault("final_batch_size", config.train_batch_size)
            self.batch_size_scheduler = BatchSizeScheduler(**bs_params)
            # honor a configured resume point; default starts at step 0
            self.batch_size_scheduler.step(
                max(bs_params.get("last_batch_iteration", 0), 0)
            )
        self._compute_dtype = _dtype_of(config.precision)
        # masterless bf16 (memory-lean mode, config bf16.master_weights=false):
        # the optimizer updates bf16 params in place with bf16-stored moments
        # and bf16 grads — 4 bytes/param of optimizer+grad state instead of 16
        self._use_master = (self._compute_dtype != jnp.float32
                            and config.master_weights)
        self._grad_dtype = (jnp.float32 if (self._use_master
                            or self._compute_dtype == jnp.float32)
                            else self._compute_dtype)
        # accumulation carry across gas microbatches (see constants.py:
        # BFLOAT16_GRAD_ACCUM_DTYPE); None follows the grad storage dtype
        gad = config.grad_accum_dtype
        self._grad_accum_dtype = (
            jnp.float32 if gad in ("fp32", "float32")
            else jnp.bfloat16 if gad in ("bf16", "bfloat16")
            else self._grad_dtype
        )
        self.zero_stage = config.zero_optimization_stage

        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_micro_batch_size_per_gpu
            * config.gradient_accumulation_steps,
            num_workers=self.data_parallel_size,
            steps_per_output=config.steps_per_print,
        )

        # tensorboard monitor (reference engine.py:163; writer on the first
        # process only, as the reference gates on global rank 0)
        self.summary_writer = make_summary_writer(config)

        # unified telemetry (monitor/ package): a "monitor" config block
        # installs the process-global tracer/watchdog/metrics endpoint;
        # absent one, an already-installed monitor (init_monitor) is
        # adopted so manual setups and config-driven ones compose
        if config.monitor_config() is not None:
            self.monitor = init_monitor(config.monitor_config())
        else:
            self.monitor = get_monitor()
        if self.monitor is not None:
            # anchors the run's trace lane: run id + which incarnation
            # this process is (the supervisor bumps it every relaunch)
            rc = self.monitor.run_context
            trace_instant("run/start", lane="run", run_id=rc.run_id or "",
                          role=rc.role, incarnation=rc.incarnation)
            # the mesh was resolved before the monitor existed (it feeds
            # world-size derivation), so announce the layout here — this
            # is the mesh/build event post-hoc layout debugging joins on
            trace_instant("mesh/build", lane="mesh",
                          axes={k: int(v)
                                for k, v in dict(self.mesh.shape).items()},
                          devices=int(self.mesh.devices.size))
        # fused Pallas kernels: the "kernels" config block selects the
        # fused elementwise/optimizer/super-tile kernels. Applied
        # process-globally (ops/kernel_config.py) because the consumers
        # are free functions deep inside model code; must land before
        # _configure_basic_optimizer so FusedAdam sees the mode.
        if getattr(config, "kernels_params", None):
            kernel_config.configure(**config.kernels_params)

        # resilience (resilience/ package): a "resilience" config block
        # installs the process-global manager (async two-phase-commit
        # saves, preemption guard, fault injection); absent one, an
        # already-installed manager is adopted like the monitor above
        from ..resilience import get_resilience_manager, init_resilience

        if config.resilience_config() is not None:
            self._resilience = init_resilience(config.resilience_config())
        else:
            self._resilience = get_resilience_manager()
        if self._resilience is not None:
            # supervisor-restarted child: count it + record reason/world
            self._resilience.note_restart_context()

        # lifecycle (lifecycle/ package): a "lifecycle" block arms the
        # live re-mesh signal handler and the weight-version publisher
        # as resilience step-boundary hooks; the publisher needs a
        # checkpoint dir, so wiring waits for the first known save dir
        # when resilience.save_dir is unset
        self._lifecycle = None
        lc_cfg = config.lifecycle_config()
        if lc_cfg is not None:
            from ..lifecycle.controller import LifecycleController

            ckpt_dir = (self._resilience.save_dir
                        if self._resilience is not None else None)
            if ckpt_dir is not None:
                self._lifecycle = LifecycleController(
                    ckpt_dir, cfg=lc_cfg).attach(self)
            else:
                # no checkpoint dir to publish from: still honor the
                # re-mesh half so pool shrinks work checkpoint-free
                from ..lifecycle.remesh import RemeshHook

                hook = RemeshHook(lc_cfg)
                if lc_cfg.remesh_enabled:
                    hook.install()
                if self._resilience is not None:
                    self._resilience.attach_lifecycle(hook)
                self._lifecycle = hook

        # the fused train step legitimately traces twice: the initial
        # state is an uncommitted single-device array, the step's output
        # commits to a NamedSharding over the mesh, and the second call
        # specializes to it. The first watchdog observation is therefore
        # skipped so the warm baseline locks on the steady-state cache.
        self._wd_warmup_left = 1

        # fork extras (reference engine.py:139,227): gradient stashing and
        # layer-output capture
        self.store_gradients = False
        self.store_gradients_cpu = False
        self.stored_gradients = None
        self._layer_collector = None

        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self._mode = "train"
        self._stashed = None  # (loss, grads) pending backward()
        self._grad_acc = None  # banked grads between backward() and step()
        self._acc_count = 0
        self._pending_metrics = None
        self._lr_override = None  # set_lr pin; cleared by scheduler steps

        self._loss_scaler = create_loss_scaler(
            config.precision,
            static_loss_scale=config.loss_scale,
            dynamic_args=config.dynamic_loss_scale_args,
        )

        self.optimizer = optimizer or self._configure_basic_optimizer()
        self.lr_scheduler = lr_scheduler or self._configure_lr_scheduler()
        self._client_lr = _optimizer_base_lr(self.optimizer, config)

        # ZeRO-Offload / ZeRO-Infinity: optimizer state leaves the device
        # (reference stage2.py cpu_offload / stage3 offload_optimizer).
        self._offload = None
        off_cfg = config.zero_config.offload_optimizer
        if off_cfg.enabled:
            if not isinstance(self.optimizer, DeepSpeedCPUAdam):
                # host steps always run on the cpu_adam kernel, whatever the
                # configured optimizer name (reference forces DeepSpeedCPUAdam
                # under cpu_offload, engine.py:713-724)
                self.optimizer = DeepSpeedCPUAdam(
                    lr=getattr(self.optimizer, "lr", 1e-3),
                    betas=getattr(self.optimizer, "betas", (0.9, 0.999)),
                    eps=getattr(self.optimizer, "eps", 1e-8),
                    weight_decay=getattr(self.optimizer, "weight_decay", 0.0),
                    adam_w_mode=getattr(self.optimizer, "adam_w_mode", True),
                    bias_correction=getattr(self.optimizer, "bias_correction", True),
                )
            self._offload_cfg = off_cfg

        # ---- sharding specs ----
        tp_specs = param_specs
        if tp_specs is None:
            tp_specs = jax.tree.map(lambda p: P(), params)
        self._tp_specs = tp_specs
        self.param_specs = partition.tree_specs(
            params, tp_specs, self.zero_stage, self.mesh, "param"
        )
        self.master_specs = partition.tree_specs(
            params, tp_specs, self.zero_stage, self.mesh, "master"
        )
        self.grad_specs = partition.tree_specs(
            params, tp_specs, self.zero_stage, self.mesh, "grad"
        )

        self.state = self._init_state(params)

        # comm (runtime/comm/ package): a "comm" config block swaps the
        # monolithic XLA-scheduled grad all-reduce for the bucketed
        # GradReducer — explicit per-bucket collectives over the data
        # axis with quantized wire formats; error-feedback residuals live
        # in _comm_state (outside EngineState, threaded through the fused
        # step and checkpointed alongside the optimizer state)
        # canonical-slot reduction (elasticity.canonical_shards): restructure
        # the fused-step gradient reduction as C world-size-independent slots
        # combined by a graph-fixed pairwise tree, so the loss curve is
        # bit-identical across every admissible elastic world size. Resolved
        # before the GradReducer below so comm residuals adopt the same
        # (C, ...) world-free layout.
        self.canonical_shards = 0
        _canon = int(getattr(config, "elastic_canonical_shards", 0) or 0)
        if _canon:
            rows = (self.train_micro_batch_size_per_gpu()
                    * self.data_parallel_size
                    * self.gradient_accumulation_steps())
            if rows % _canon != 0:
                raise ValueError(
                    f"elasticity.canonical_shards={_canon} must divide the "
                    f"global batch rows ({rows})")
            if _canon % self.data_parallel_size != 0:
                raise ValueError(
                    f"elasticity.canonical_shards={_canon} must be a "
                    f"multiple of every admissible data-parallel size "
                    f"(current: {self.data_parallel_size})")
            self.canonical_shards = _canon

        self.comm = None
        self._comm_state = None
        self._comm_acc_reduced = None  # per-cycle backward() routing flag
        self._comm_overlap = None      # OverlapScheduler when overlap is on
        if config.comm_config() is not None:
            # The reducer places through the mesh's named batch axes, so
            # ZeRO>=2 and non-data-axis meshes are no longer excluded:
            # under ZeRO>=2 the reducer's replicated means are immediately
            # re-constrained to the sharded grad specs (GSPMD slices them
            # — reduce-scatter semantics preserved), and tp/sp axes simply
            # aren't part of the reduction tuple. Only offload still owns
            # the grad path exclusively.
            if getattr(self, "_offload_cfg", None) is not None:
                logger.warning(
                    "comm block ignored (keeping the monolithic XLA "
                    "reduction): optimizer offload owns the grad path")
            else:
                from .comm.reducer import GradReducer

                self.comm = GradReducer(
                    config.comm_config(), self.mesh,
                    axis_name=self.batch_axes,
                    registry=(self.monitor.registry
                              if self.monitor is not None else None),
                    canonical=self.canonical_shards)
                self.comm.build_plan(params)
                self._comm_state = self.comm.init_state()
                # backward-overlap scheduling (comm/overlap.py): fused
                # path emits per-bucket shard_maps so XLA hides early
                # buckets under backward; imperative path dispatches
                # async and drains at the step() boundary
                from .comm import overlap as comm_overlap

                if comm_overlap.resolve_overlap(
                        config.comm_config(), world=self.comm.world,
                        canonical=self.canonical_shards):
                    self._comm_overlap = comm_overlap.OverlapScheduler()

        # datapipe (datapipe/ package): a "datapipe" config block swaps
        # the sync dataloader pull for the streaming/prefetching host
        # pipeline — memory-mapped shards or initialize(training_data=),
        # async device staging, checkpointable DataState (carried in
        # _host_checkpoint_payload, restored by load_checkpoint)
        self.datapipe = None
        if config.datapipe_config() is not None:
            from ..datapipe import build_datapipe

            self.datapipe = build_datapipe(
                config.datapipe_config(),
                dataset=training_data,
                global_rows=(self.train_micro_batch_size_per_gpu()
                             * self.data_parallel_size
                             * self.gradient_accumulation_steps()),
                place_fn=self._place_batch,
                bs_schedule=(self.batch_size_scheduler.schedule
                             if self.batch_size_scheduler is not None
                             else None),
                collate_fn=collate_fn,
            )

        # dataloader (legacy sync path; the datapipe owns the data when
        # its block is configured)
        self.training_dataloader = None
        if training_data is not None and self.datapipe is None:
            self.training_dataloader = self.deepspeed_io(
                training_data, collate_fn=collate_fn
            )

        self._compiled = {}
        log_dist(
            f"engine ready: precision={config.precision} zero_stage={self.zero_stage} "
            f"mesh={dict(self.mesh.shape)} dp={self.data_parallel_size}",
            ranks=[0],
        )

    # ------------------------------------------------------------------ #
    # construction helpers
    # ------------------------------------------------------------------ #

    def _configure_basic_optimizer(self):
        """Build the optimizer named in the config (reference engine.py:702)."""
        name = (self._config.optimizer_name or "adam").lower()
        params = dict(self._config.optimizer_params or {})
        params.pop("torch_adam", None)
        betas = tuple(params.pop("betas", (0.9, 0.999)))
        lr = params.pop("lr", 1e-3)
        eps = params.pop("eps", 1e-8)
        wd = params.pop("weight_decay", 0.0)
        if name in (ADAM_OPTIMIZER, ADAMW_OPTIMIZER):
            if name == ADAMW_OPTIMIZER:
                # AdamW always runs decoupled weight decay (reference forces it)
                params.pop("adam_w_mode", None)
                adam_w_mode = True
            else:
                adam_w_mode = params.pop("adam_w_mode", True)
            bias_corr = params.pop("bias_correction", True)
            return FusedAdam(
                lr=lr,
                betas=betas,
                eps=eps,
                weight_decay=wd,
                adam_w_mode=bool(adam_w_mode),
                bias_correction=bias_corr,
                # bf16 first moment in masterless mode (same condition as
                # the grad dtype — both fp32 exactly when a master exists)
                state_dtype=self._grad_dtype,
            )
        if name == CPU_ADAM_OPTIMIZER:
            return DeepSpeedCPUAdam(lr=lr, betas=betas, eps=eps, weight_decay=wd)
        if name == LAMB_OPTIMIZER:
            return FusedLamb(
                lr=lr,
                betas=betas,
                eps=eps,
                weight_decay=wd,
                max_coeff=params.pop("max_coeff", 10.0),
                min_coeff=params.pop("min_coeff", 0.01),
            )
        if name in (ONEBIT_ADAM_OPTIMIZER, ONEBIT_LAMB_OPTIMIZER):
            from ..runtime.comm.onebit import OnebitAdam, OnebitLamb

            cls = OnebitAdam if name == ONEBIT_ADAM_OPTIMIZER else OnebitLamb
            return cls(
                lr=lr,
                betas=betas,
                eps=eps,
                weight_decay=wd,
                freeze_step=params.pop("freeze_step", 100000),
            )
        if name == SGD_OPTIMIZER:
            return SGD(
                lr=lr,
                momentum=params.pop("momentum", 0.0),
                weight_decay=wd,
                nesterov=params.pop("nesterov", False),
            )
        raise ValueError(f"unknown optimizer '{name}'")

    def _configure_lr_scheduler(self):
        if self._config.scheduler_name:
            return lr_schedules.get_scheduler(
                self._config.scheduler_name, self._config.scheduler_params or {}
            )
        return None

    def _init_state(self, params) -> EngineState:
        mesh = self.mesh

        def place(tree, specs, dtype=None):
            def leaf(x, s):
                # copy=True: the engine owns (and later donates) its state, so
                # it must never alias caller-provided arrays
                sh = NamedSharding(mesh, s)
                if (jax.process_count() > 1
                        and not sh.is_fully_addressable
                        and getattr(x, "is_fully_addressable", True)):
                    # collective-free global placement (every process holds
                    # the same init value); device_put would broadcast each
                    # leaf for a cross-process equality assert
                    arr = np.array(jax.device_get(x),
                                   dtype=dtype or x.dtype, copy=True)
                    return jax.make_array_from_callback(
                        arr.shape, sh, lambda idx: arr[idx])
                arr = jnp.array(x, dtype=dtype or x.dtype, copy=True)
                return jax.device_put(arr, sh)

            return jax.tree.map(leaf, tree, specs)

        params_c = place(params, self.param_specs, self._compute_dtype)

        if getattr(self, "_offload_cfg", None) is not None:
            # master + moments live off-device; device state is params-only.
            # The offload optimizer keys its host chunks off the ADDRESSABLE
            # shards of the master-sharded placement, so each process owns
            # exactly its 1/dp slice (ZeRO-Infinity per-rank swapping).
            from .offload.offload_optimizer import HostOffloadOptimizer

            self._offload = HostOffloadOptimizer(
                place(params, self.master_specs, jnp.float32),
                self.optimizer,
                device=self._offload_cfg.device,
                compute_dtype=np.dtype(self._compute_dtype),
                aio_config=self._config.aio_config,
                swap_folder=self._offload_cfg.nvme_path,
                pipeline=bool(
                    self._offload_cfg.pipeline_read or self._offload_cfg.pipeline_write
                ),
            )
            return EngineState(
                step=jnp.zeros((), jnp.int32),
                params=params_c,
                master=None,
                opt_state=(),
                scaler=self._loss_scaler.init(),
                skipped=jnp.zeros((), jnp.int32),
            )

        master = (place(params, self.master_specs, jnp.float32)
                  if self._use_master else None)
        opt_src = master if self._use_master else params_c
        def ds_init_opt_state(src):
            return self.optimizer.init(src)

        opt_state = jax.jit(
            ds_init_opt_state,
            out_shardings=_opt_state_shardings(
                self.optimizer, opt_src, mesh, self.master_specs
            ),
        )(opt_src)
        return EngineState(
            step=jnp.zeros((), jnp.int32),
            params=params_c,
            master=master,
            opt_state=opt_state,
            scaler=self._loss_scaler.init(),
            skipped=jnp.zeros((), jnp.int32),
        )

    # ------------------------------------------------------------------ #
    # reference-API accessors
    # ------------------------------------------------------------------ #

    def current_batch_size(self):
        """Scheduled effective batch size (== train_batch_size unless a
        batch_scheduler block is configured)."""
        if self.batch_size_scheduler is not None:
            return self.batch_size_scheduler.current_batch_size
        return self._config.train_batch_size

    def get_global_grad_norm(self):
        if self._pending_metrics is None:
            return 0.0
        return float(jax.device_get(self._pending_metrics["grad_norm"]))

    @property
    def skipped_steps(self):
        """Overflow-skipped optimizer steps (device counter, fetched lazily)."""
        return int(jax.device_get(self.state.skipped))

    def loss_scale(self):
        return float(jax.device_get(self.state.scaler.loss_scale))

    def train(self, mode=True):
        self._mode = "train" if mode else "eval"

    def eval(self):
        self._mode = "eval"

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def save_fp16_model(self, save_dir, save_filename="model_fp16.msgpack"):
        """Save consolidated compute-dtype weights only (reference
        engine.py:1882 — gathers ZeRO-3 shards first)."""
        from ..checkpoint.serialization import save_tree

        os.makedirs(save_dir, exist_ok=True)
        host = self._zero3_consolidated_fp16_state_dict()
        path = os.path.join(save_dir, save_filename)
        save_tree(path, host)
        log_dist(f"saved fp16 model weights to {path}", ranks=[0])
        return path

    # ------------------------------------------------------------------ #
    # data placement
    # ------------------------------------------------------------------ #

    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, shuffle=False):
        batch_size = batch_size or (
            self.train_micro_batch_size_per_gpu() * self.data_parallel_size
        )
        return DeepSpeedDataLoader(
            dataset, batch_size=batch_size, collate_fn=collate_fn, shuffle=shuffle
        )

    def _place_batch(self, batch):
        """Shard a host batch over the mesh's batch axes (leading dim) —
        routed through sharding.place_batch, the same staging the serving
        engine and datapipe use. Multi-host: each process contributes its
        local slice via jax.make_array_from_process_local_data."""
        return sharding.place_batch(self.mesh, batch)

    # ------------------------------------------------------------------ #
    # jitted computations
    # ------------------------------------------------------------------ #

    def _pld_active(self) -> bool:
        return self.progressive_layer_drop is not None and self._takes_pld

    def _pack_pld(self, batch, theta: float = None):
        """Attach the PLD keep-probability to the batch pytree so it enters
        the jitted step as a traced scalar (no retrace as theta decays)."""
        if not self._pld_active():
            return batch
        if theta is None:
            theta = self.progressive_layer_drop.get_theta()
        return (batch, np.float32(theta))

    def _call_loss(self, params, batch, rng, scale):
        kwargs = {}
        if self._pld_active():
            batch, theta = batch
            kwargs["pld_theta"] = theta
        # the model's Pallas kernels need to know the mesh they are
        # traced under (XLA cannot partition a Mosaic kernel)
        with kernel_config.mesh_scope(self.mesh):
            out = (
                self.loss_fn(params, batch, rng, **kwargs)
                if self._takes_rng
                else self.loss_fn(params, batch, **kwargs)
            )
        loss, aux = out if isinstance(out, tuple) else (out, None)
        return (loss.astype(jnp.float32) * scale), loss

    def _micro_grads(self, params, mb, rng, scale):
        """One microbatch fused forward+backward on the scaled loss."""
        (scaled, loss), grads = jax.value_and_grad(self._call_loss, has_aux=True)(
            params, mb, rng, scale
        )
        del scaled
        grads = jax.tree.map(lambda g: g.astype(self._grad_dtype), grads)
        return loss, grads

    def _rng_args(self):
        """(base_key, ticket) passed into the jitted step; the key is a jit
        ARGUMENT (not a closure constant) so reassigning engine.rng between
        steps takes effect without a retrace."""
        i = self._rng_tick
        self._rng_tick += 1
        return (self.rng, i)

    @staticmethod
    def _fold_rng(rng):
        """Traced: derive this dispatch's key from (base_key, ticket)."""
        key, idx = rng
        return jax.random.fold_in(key, idx)

    def _get_compiled(self, name, builder):
        if name not in self._compiled:
            self._compiled[name] = builder()
        return self._compiled[name]

    def _forward_grad_fn(self):
        """jitted (state, batch, rng) -> (loss, grads) for ONE microbatch.

        Under comm the grads come back as the LOCAL per-device stack
        ((world, *shape), sharded P(data)) with no collective in the
        program — backward()/step() decide when the reducer runs."""

        def build():
            if self.comm is not None:
                def ds_forward_grad(state, batch, rng):
                    rng = self._fold_rng(rng)
                    return self._batch_grads_local(state, batch, rng, 1)

                return jax.jit(ds_forward_grad)

            def ds_forward_grad(state, batch, rng):
                rng = self._fold_rng(rng)
                loss, grads = self._micro_grads(
                    state.params, batch, rng, state.scaler.loss_scale
                )
                grads = partition.constrain(grads, self.grad_specs, self.mesh)
                return loss, grads

            return jax.jit(ds_forward_grad)

        return self._get_compiled("forward_grad", build)

    def _forward_only_fn(self):
        def build():
            def ds_forward_only(state, batch, rng):
                rng = self._fold_rng(rng)
                _, loss = self._call_loss(state.params, batch, rng, jnp.float32(1.0))
                return loss

            return jax.jit(ds_forward_only)

        return self._get_compiled("forward_only", build)

    def _apply_update_fn(self):
        """jitted (state, grads, lr, gas) -> (new_state, metrics)."""

        def build():
            def ds_apply_update(state, grads, lr, gas):
                return self._apply_update_body(state, grads, lr, gas)

            return jax.jit(ds_apply_update, donate_argnums=(0,))

        return self._get_compiled("apply_update", build)

    def _batch_grads(self, state, batch, rng, gas):
        """Traced: scan over gas microbatches; returns (mean loss, summed
        scaled grads)."""
        scale = state.scaler.loss_scale
        if gas == 1:
            loss, grads = self._micro_grads(state.params, batch, rng, scale)
            grads = partition.constrain(grads, self.grad_specs, self.mesh)
            return loss, grads

        # the PLD theta scalar rides outside the microbatch reshape
        theta = None
        if self._pld_active():
            batch, theta = batch

        def resh(x):
            return jnp.reshape(x, (gas, x.shape[0] // gas) + x.shape[1:])

        batch_g = jax.tree.map(resh, batch)
        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, self._grad_accum_dtype), state.params
        )
        zero_g = partition.constrain(zero_g, self.grad_specs, self.mesh)

        def body(carry, mb):
            acc, loss_sum, i = carry
            if theta is not None:
                mb = (mb, theta)
            loss, grads = self._micro_grads(
                state.params, mb, jax.random.fold_in(rng, i), scale
            )
            grads = partition.constrain(grads, self.grad_specs, self.mesh)
            with jax.named_scope("ds.accum"):
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), acc, grads)
                acc = partition.constrain(acc, self.grad_specs, self.mesh)
            return (acc, loss_sum + loss, i + 1), None

        (grads, loss_sum, _), _ = jax.lax.scan(
            body, (zero_g, jnp.float32(0.0), jnp.int32(0)), batch_g
        )
        grads = jax.tree.map(
            lambda g: g.astype(self._grad_dtype), grads
        )
        return loss_sum / gas, grads

    def _batch_grads_local(self, state, batch, rng, gas):
        """Traced: per-device LOCAL grads over gas microbatches — no
        implicit GSPMD reduction; the comm GradReducer owns the
        collective. shard_map over the data axis computes each device's
        grads of its local-mean loss and returns them stacked
        ``(world, *shape)`` (sharded ``P(data)``); averaging the stack
        over the axis reproduces the global-mean-gradient semantics of
        :meth:`_batch_grads`. Returns (global mean loss, stacked grads)."""
        from jax import shard_map

        scale = state.scaler.loss_scale
        theta = None
        if self._pld_active():
            batch, theta = batch

        def body(params, scale_, batch_, rng_):
            def one(mb, key):
                if theta is not None:
                    mb = (mb, theta)
                # this body is one shard: kernels run on local rows
                with kernel_config.mesh_scope(None):
                    return self._micro_grads(params, mb, key, scale_)

            if gas == 1:
                loss, grads = one(batch_, rng_)
            else:
                def resh(x):
                    return jnp.reshape(
                        x, (gas, x.shape[0] // gas) + x.shape[1:])

                batch_g = jax.tree.map(resh, batch_)
                zero_g = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, self._grad_accum_dtype),
                    params)

                def mb_body(carry, mb):
                    acc, loss_sum, i = carry
                    mb_loss, grads = one(mb, jax.random.fold_in(rng_, i))
                    with jax.named_scope("ds.accum"):
                        acc = jax.tree.map(
                            lambda a, g: a + g.astype(a.dtype), acc, grads)
                    return (acc, loss_sum + mb_loss, i + 1), None

                (grads, loss_sum, _), _ = jax.lax.scan(
                    mb_body, (zero_g, jnp.float32(0.0), jnp.int32(0)),
                    batch_g)
                loss = loss_sum / gas
            loss = jax.lax.pmean(loss, self.batch_axes)
            grads = jax.tree.map(
                lambda g: g.astype(self._grad_dtype)[None], grads)
            return loss, grads

        # one batch-axis entry covering all batch axes (dp+fsdp on a
        # canonical mesh, data on a legacy one)
        ax = (self.batch_axes if len(self.batch_axes) > 1
              else self.batch_axes[0])
        dspec = P(ax)
        in_specs = (
            jax.tree.map(lambda _: P(), state.params),
            P(),
            jax.tree.map(lambda x: P() if jnp.ndim(x) == 0 else dspec,
                         batch),
            P(),
        )
        out_specs = (P(), jax.tree.map(lambda _: dspec, state.params))
        fn = shard_map(body, mesh=self.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        return fn(state.params, scale, batch, rng)

    def _batch_grads_canonical(self, state, batch, rng, C):
        """Traced: world-size-invariant grads via C canonical slots.

        The global batch (R rows) is reshaped to ``(C, R/C, ...)`` and each
        slot's loss/grads are computed by one ``jax.vmap`` lane with a
        per-SLOT rng (``fold_in(rng, slot)`` — not per gas microbatch, so
        the stream is independent of how gas/micro split across world
        sizes). The slot axis is sharding-constrained over the data axis;
        because C is fixed by config, the program (and therefore every
        reduction grouping) is identical on any device count. Returns
        ``(slot_losses (C,), slot grads stacked (C, *shape))`` — callers
        combine slots with :func:`pairwise_slot_sum`, a graph-fixed
        pairwise tree, never a GSPMD mean.
        """
        scale = state.scaler.loss_scale
        theta = None
        if self._pld_active():
            batch, theta = batch

        def resh(x):
            return jnp.reshape(x, (C, x.shape[0] // C) + x.shape[1:])

        batch_c = jax.tree.map(resh, batch)
        slot_sharding = jax.sharding.NamedSharding(
            self.mesh, sharding.batch_spec(self.mesh, 1))
        batch_c = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, slot_sharding),
            batch_c)

        def one(mb, idx):
            if theta is not None:
                mb = (mb, theta)
            key = jax.random.fold_in(rng, idx)
            return self._micro_grads(state.params, mb, key, scale)

        losses, slot_grads = jax.vmap(one, in_axes=(0, 0))(
            batch_c, jnp.arange(C))
        slot_grads = jax.tree.map(
            lambda g: jax.lax.with_sharding_constraint(g, slot_sharding),
            slot_grads)
        return losses, slot_grads

    def _train_batch_fn(self):
        """Fully fused jitted step: scan over gas microbatches + update."""

        def build():
            gas = self.gradient_accumulation_steps()
            C = self.canonical_shards

            if C:
                # canonical path: slots subsume the gas microbatches (one
                # vmap lane per slot; the scaled-grad divisor is C inside
                # the slot mean, so the update body unscales with gas=1).
                # Slot means go through exact_slot_mean — an explicit
                # all_gather + local pairwise tree — because inside the
                # jit GSPMD may lower a sliced-add tree over the sharded
                # slot axis to a native all-reduce whose accumulation
                # order tracks the device->process topology (one ulp
                # between gloo and shared-memory, enough to fork the
                # loss curve across process layouts).
                from .comm.reducer import exact_slot_mean

                if self.comm is not None:
                    def ds_train_step(state, comm_state, batch, lr, rng):
                        rng = self._fold_rng(rng)
                        losses, slots = self._batch_grads_canonical(
                            state, batch, rng, C)
                        loss = exact_slot_mean(
                            losses, self.mesh, self.batch_axes, C)
                        grads, new_comm = self.comm.reduce_canonical(
                            slots, comm_state)
                        grads = jax.tree.map(
                            lambda g: g.astype(self._grad_dtype), grads)
                        grads = partition.constrain(
                            grads, self.grad_specs, self.mesh)
                        new_state, metrics = self._apply_update_body(
                            state, grads, lr, 1)
                        metrics["loss"] = loss
                        return new_state, new_comm, metrics

                    return jax.jit(ds_train_step, donate_argnums=(0, 1))

                def ds_train_step(state, batch, lr, rng):
                    rng = self._fold_rng(rng)
                    losses, slots = self._batch_grads_canonical(
                        state, batch, rng, C)
                    loss = exact_slot_mean(
                        losses, self.mesh, self.batch_axes, C)
                    grads = jax.tree.map(
                        lambda g: g.astype(self._grad_dtype),
                        exact_slot_mean(slots, self.mesh,
                                        self.batch_axes, C))
                    grads = partition.constrain(
                        grads, self.grad_specs, self.mesh)
                    new_state, metrics = self._apply_update_body(
                        state, grads, lr, 1)
                    metrics["loss"] = loss
                    return new_state, metrics

                return jax.jit(ds_train_step, donate_argnums=(0,))

            if self.comm is not None:
                # comm path: local grads via shard_map, explicit bucketed
                # reduction, then the shared update body. The comm state
                # (error-feedback residuals) threads through the jit with
                # donation like the engine state.
                def ds_train_step(state, comm_state, batch, lr, rng):
                    rng = self._fold_rng(rng)
                    loss, local = self._batch_grads_local(
                        state, batch, rng, gas)
                    grads, new_comm = self.comm.reduce_stacked(
                        local, comm_state,
                        per_bucket=self._comm_overlap is not None)
                    grads = jax.tree.map(
                        lambda g: g.astype(self._grad_dtype), grads)
                    grads = partition.constrain(
                        grads, self.grad_specs, self.mesh)
                    new_state, metrics = self._apply_update_body(
                        state, grads, lr, gas)
                    metrics["loss"] = loss
                    return new_state, new_comm, metrics

                return jax.jit(ds_train_step, donate_argnums=(0, 1))

            def ds_train_step(state, batch, lr, rng):
                rng = self._fold_rng(rng)
                loss, grads = self._batch_grads(state, batch, rng, gas)
                new_state, metrics = self._apply_update_body(state, grads, lr, gas)
                metrics["loss"] = loss
                return new_state, metrics

            return jax.jit(ds_train_step, donate_argnums=(0,))

        return self._get_compiled("train_batch", build)

    def _offload_grads_fn(self):
        """Device half of the offloaded step: grads unscaled + clipped on
        device, constrained to the MASTER sharding (reduce-scattered under
        ZeRO>=1) so each process fetches only its addressable shards."""

        def build():
            gas = self.gradient_accumulation_steps()
            clip = float(self._config.gradient_clipping or 0.0)

            def ds_offload_grads(state, batch, rng):
                rng = self._fold_rng(rng)
                loss, grads = self._batch_grads(state, batch, rng, gas)
                grads, gnorm, finite = self._postprocess_grads(
                    state, grads, jnp.float32(gas), clip
                )
                grads = partition.constrain(
                    grads, self.master_specs, self.mesh
                )
                return loss, grads, gnorm, finite

            return jax.jit(ds_offload_grads)

        return self._get_compiled("offload_grads", build)

    @staticmethod
    def _postprocess_grads(state, grads, gas, clip):
        """Traced: unscale by loss_scale*gas, global-norm clip, overflow flag.

        One reduction pass + one fused multiply pass over the grads (HBM-bound
        at 125M+ params, so passes matter): the overflow check rides on the
        squared-norm reduction — any inf/nan grad makes the norm non-finite —
        and unscale+clip collapse into a single scale factor. A non-finite
        coef can NaN the scaled grads, but in exactly that case finite=False
        and the update is discarded wholesale (the `keep` select in
        _apply_update_body), matching the reference's skip-step
        (runtime/engine.py:1184-1192 + CheckOverflow, runtime/utils.py)."""
        with jax.named_scope("ds.update/clip"):
            inv = 1.0 / (state.scaler.loss_scale * gas)
            raw_sq = jnp.sum(
                jnp.stack([jnp.sum(g.astype(jnp.float32) ** 2)
                           for g in jax.tree.leaves(grads)])
            )
            gnorm = jnp.sqrt(raw_sq) * inv  # norm of the UNSCALED grads
            finite = jnp.isfinite(gnorm)
            coef = inv
            if clip > 0:
                coef = coef * jnp.minimum(1.0, clip / (gnorm + 1e-6))
            grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * coef).astype(g.dtype),
                grads
            )
        return grads, gnorm, finite

    def _offload_post_fn(self):
        """jitted (state, grads, gas) -> (grads, gnorm, finite) for the
        imperative forward/backward/step path under offload."""

        def build():
            clip = float(self._config.gradient_clipping or 0.0)

            def ds_offload_post(state, grads, gas):
                grads, gnorm, finite = self._postprocess_grads(
                    state, grads, gas, clip
                )
                grads = partition.constrain(
                    grads, self.master_specs, self.mesh
                )
                return grads, gnorm, finite

            return jax.jit(ds_offload_post)

        return self._get_compiled("offload_post", build)

    def _offload_reshard_fn(self):
        """jitted identity: master-sharded compute-dtype params -> the param
        sharding (the ZeRO all-gather, compiled; multi-process safe)."""

        def build():
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.param_specs
            )
            cdt = self._compute_dtype

            def ds_offload_reshard(t):
                return jax.tree.map(lambda x: x.astype(cdt), t)

            return jax.jit(ds_offload_reshard, out_shardings=shardings)

        return self._get_compiled("offload_reshard", build)

    def _resolve_offload_sd(self, ck, optim_states, model_states):
        """This rank's offload state dict for load_checkpoint.

        Fast path (same topology): only this rank's own file is read — the
        main optim file for rank 0, its zero_pp_rank file otherwise. Only
        when the saved chunks do not match this run's layout (mesh change)
        is the merged all-rank view built, bounded by the process count
        recorded at save time so stale higher-rank files from an older
        save into the same tag are ignored."""
        import json as _json

        def _meta(d):
            m = d.get("chunk_meta")
            return _json.loads(m) if isinstance(m, (str, bytes)) else (m or {})

        own = optim_states.get("offload")
        if jax.process_count() > 1 and jax.process_index() != 0:
            rf = optim_state_filename(jax.process_index())
            own = ck.load(rf).get("offload") if ck.exists(rf) else None
        if own is not None and self._offload.chunks_match(own):
            return own

        # topology changed (or own file missing): merge every rank file
        # present on disk (gap-tolerant — discovered by listing, not by
        # scanning until the first hole), bounded by the process count
        # recorded at save time so stale files from an older, larger save
        # into the same tag are ignored
        import re

        saved_procs = int(model_states.get("process_count", 0))
        ranks = sorted(
            int(m.group(1))
            for f in os.listdir(ck.ckpt_dir)
            if (m := re.match(r"zero_pp_rank_(\d+)_mp_rank_\d+_optim_states",
                              f))
        )
        if saved_procs:
            ranks = [r for r in ranks if r < saved_procs]
        merged = None
        for r in ranks:
            if jax.process_count() > 1 and r == jax.process_index():
                rank_sd = own  # already loaded above
            elif r == 0:
                rank_sd = optim_states.get("offload")  # the main file
            else:
                rank_sd = ck.load(optim_state_filename(r)).get("offload")
            if not rank_sd:
                continue
            if merged is None:
                merged = dict(rank_sd)
            else:
                merged["states"] = {**merged["states"], **rank_sd["states"]}
                merged["chunk_meta"] = {**_meta(merged), **_meta(rank_sd)}
        if merged is None and jax.process_count() > 1:
            logger.warning(
                "no offload state found in checkpoint; optimizer moments "
                "reset"
            )
        return merged

    def _to_master_sharded(self, params):
        """jitted identity: any params placement -> fp32 master sharding
        (scatter each process its chunks)."""

        def build():
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.master_specs
            )

            def ds_to_master(t):
                return jax.tree.map(lambda x: x.astype(jnp.float32), t)

            return jax.jit(ds_to_master, out_shardings=shardings)

        return self._get_compiled("offload_to_master", build)(params)

    def _offload_apply(self, grads_device, gnorm, finite, loss):
        """Host half of the offloaded step: per-shard CPU Adam on this
        process's chunks + reassembly/all-gather of the fresh params."""
        overflow = not bool(jax.device_get(finite))
        state = self.state
        if overflow:
            state = state._replace(skipped=state.skipped + 1)
        else:
            params_m = self._offload.step(grads_device, lr=self._current_lr())
            params = self._offload_reshard_fn()(params_m)
            state = state._replace(params=params, step=state.step + 1)
        metrics = {
            "overflow": jnp.asarray(overflow),
            "grad_norm": gnorm,
            "loss_scale": state.scaler.loss_scale,
            "loss": loss,
        }
        state = state._replace(
            scaler=self._loss_scaler.update(state.scaler, jnp.asarray(overflow))
        )
        self.state = state
        return metrics

    def _apply_update_body(self, state, grads, lr, gas):
        """Non-jitted body shared between the fused and imperative paths."""
        # delegate to the same math as _apply_update_fn but inline (traced)
        clip = float(self._config.gradient_clipping or 0.0)
        opt = self.optimizer
        scaler = self._loss_scaler

        grads, gnorm, finite = self._postprocess_grads(state, grads, gas, clip)
        overflow = ~finite

        target = state.master if self._use_master else state.params
        # with the fused Pallas Adam active, the fp32->compute-dtype
        # master-weight cast rides inside the optimizer kernel (one HBM
        # pass) instead of a separate full-param cast here
        # (the optimizer's Pallas route needs the mesh it is traced under)
        with kernel_config.mesh_scope(self.mesh), \
                jax.named_scope("ds.update/optimizer"):
            fused_cast = (self._use_master
                          and getattr(opt, "pallas_active", lambda: False)())
            if fused_cast:
                new_target, new_opt, new_cast = opt.update(
                    grads, state.opt_state, target, lr,
                    cast_dtype=self._compute_dtype)
            else:
                new_target, new_opt = opt.update(
                    grads, state.opt_state, target, lr)
        keep = lambda new, old: jax.tree.map(
            lambda n, o: jnp.where(overflow, o, n), new, old
        )
        new_target = keep(new_target, target)
        new_opt = keep(new_opt, state.opt_state)
        if not self._use_master:
            new_params = partition.constrain(new_target, self.param_specs, self.mesh)
            new_master = None
        else:
            new_master = partition.constrain(new_target, self.master_specs, self.mesh)
            if fused_cast:
                # overflow keep-select vs the old compute-dtype params —
                # identical to casting keep(master): params == cast(master)
                # is the steady-state invariant
                cast = keep(new_cast, state.params)
            else:
                cast = jax.tree.map(
                    lambda m: m.astype(self._compute_dtype), new_master)
            new_params = partition.constrain(
                cast, self.param_specs, self.mesh)
        new_state = EngineState(
            step=state.step + jnp.where(overflow, 0, 1),
            params=new_params,
            master=new_master,
            opt_state=new_opt,
            scaler=scaler.update(state.scaler, overflow),
            skipped=state.skipped + jnp.where(overflow, 1, 0),
        )
        return new_state, {
            "overflow": overflow,
            "grad_norm": gnorm,
            "loss_scale": state.scaler.loss_scale,
        }

    # ------------------------------------------------------------------ #
    # public training API
    # ------------------------------------------------------------------ #

    def __call__(self, batch):
        return self.forward(batch)

    def forward(self, batch):
        """Compute loss on one microbatch. In train mode the backward is fused
        in (grads stashed for `backward()`); in eval mode loss only."""
        batch = self._place_batch(batch)
        rng = self._rng_args()
        if self._mode != "train":
            return self._forward_only_fn()(self.state, self._pack_pld(batch, 1.0), rng)
        batch = self._pack_pld(batch)
        if self._layer_collector is not None and self._acc_count == 0:
            self._layer_collector.clear()  # fresh capture per accumulation cycle
        fpc = self._config.flops_profiler_config
        if fpc.enabled and not getattr(self, "_flops_profiled", False):
            self._profile_args = (batch, rng)
        wall = self._config.wall_clock_breakdown
        if wall:
            self._timer_start(FORWARD_MICRO_TIMER)
        with trace_span("engine/forward", lane="engine",
                        micro_step=self.micro_steps) as _sp:
            fwd_fn = self._forward_grad_fn()
            loss, grads = fwd_fn(self.state, batch, rng)
            mon = self.monitor
            if mon is not None:
                if mon.cost_index is not None:
                    # imperative-path cost capture: AOT re-lower against
                    # abstract avals, so the jit cache (and the
                    # watchdog's view of it) is untouched
                    mon.cost_index.observe("engine/forward_grad", fwd_fn,
                                           (self.state, batch, rng))
                if mon.memwatch is not None:
                    mon.memwatch.annotate(_sp, "forward")
        if wall:
            # forward+backward are fused in this fn; the split is the
            # imperative API's, the timing is the fused step's
            self.timers(FORWARD_MICRO_TIMER).stop(sync_with=loss)
        self._stashed = (loss, grads)
        return loss

    def backward(self, loss=None, allreduce_gradients=True):
        """Bank the stashed grads (reference engine.py:1040).

        Without a "comm" block the collective schedule is decided by XLA
        from the grad sharding constraints (the grads arriving here are
        already globally reduced, so ``allreduce_gradients`` has nothing
        left to route and is accepted for API compatibility). With the
        comm GradReducer active, the stashed grads are per-device LOCAL
        stacks and the flag is honored: True reduces this microbatch's
        bucket stack now (reference default), False banks the local sum
        and defers the reduction to the accumulation boundary in
        ``step()`` — one collective per cycle instead of one per
        microbatch. The two routings may not be mixed within a cycle."""
        assert self._stashed is not None, "backward() requires a prior forward()"
        stashed_loss, grads = self._stashed
        self._last_micro_loss = stashed_loss  # for step()-path monitoring
        self._stashed = None
        with trace_span("engine/backward", lane="engine",
                        micro_step=self.micro_steps) as _bwd_sp:
            if self.comm is not None:
                reduce_now = bool(allreduce_gradients)
                if self._grad_acc is None:
                    self._comm_acc_reduced = reduce_now
                elif self._comm_acc_reduced != reduce_now:
                    raise RuntimeError(
                        "backward(allreduce_gradients=...) must not change "
                        "within one accumulation cycle: the bank holds "
                        + ("reduced" if self._comm_acc_reduced else "local")
                        + " gradients")
                if reduce_now:
                    overlap = self._comm_overlap is not None
                    grads, self._comm_state = self.comm.reduce_dispatch(
                        grads, self._comm_state, overlap=overlap)
                    if overlap:
                        # collectives stay in flight; step() drains at
                        # the accumulation boundary
                        self._comm_overlap.note(
                            (grads, self._comm_state), self.comm.n_buckets)
            if self._grad_acc is None:
                # bank the carry in the configured accumulation dtype (see
                # grad_accum_dtype) so the imperative path matches
                # train_batch
                self._grad_acc = jax.tree.map(
                    lambda g: g.astype(self._grad_accum_dtype), grads
                )
            else:
                self._grad_acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), self._grad_acc, grads
                )
            if (self.monitor is not None
                    and self.monitor.memwatch is not None):
                self.monitor.memwatch.annotate(_bwd_sp, "backward")
        self._acc_count += 1
        return loss

    def step(self):
        """Apply the optimizer at the grad-accumulation boundary (reference
        engine.py:1201; micro_steps increments here like engine.py:1286, so
        is_gradient_accumulation_boundary() reads True after the last
        microbatch's backward())."""
        wall = self._config.wall_clock_breakdown
        if wall:
            self._timer_start(STEP_MICRO_TIMER)
        gas = self.gradient_accumulation_steps()
        if self._acc_count >= gas:
            banked = self._grad_acc
            if self.comm is not None and not self._comm_acc_reduced:
                # deferred routing (backward(allreduce_gradients=False)):
                # the bank holds the SUM of local grad stacks; one bucketed
                # reduction at the boundary covers the whole cycle
                overlap = self._comm_overlap is not None
                banked, self._comm_state = self.comm.reduce_dispatch(
                    banked, self._comm_state, overlap=overlap)
                if overlap:
                    # async even here: buckets pipeline against each
                    # other and the optimizer dispatch below
                    self._comm_overlap.note(
                        (banked, self._comm_state), self.comm.n_buckets)
            if self._comm_overlap is not None:
                # accumulation boundary: wait for every in-flight bucket
                # under the comm/overlap_window span (the only comm time
                # the overlap schedule leaves exposed)
                self._comm_overlap.drain()
            # hand the optimizer grads in the storage dtype (the fused path
            # casts its scan carry back the same way)
            banked = jax.tree.map(
                lambda g: g.astype(self._grad_dtype), banked
            )
            with trace_span("engine/step", lane="engine",
                            step=self.global_steps) as _step_sp:
                mon = self.monitor
                if self._offload is not None:
                    grads, gnorm, finite = self._offload_post_fn()(
                        self.state, banked, np.float32(self._acc_count)
                    )
                    metrics = self._offload_apply(grads, gnorm, finite, None)
                else:
                    lr = np.float32(self._current_lr())
                    # the imperative path banked unscaled-by-gas grads;
                    # scale in fn
                    upd_fn = self._apply_update_fn()
                    if mon is not None and mon.cost_index is not None:
                        mon.cost_index.observe(
                            "engine/apply_update", upd_fn,
                            (self.state, banked, lr,
                             np.float32(self._acc_count)))
                    new_state, metrics = upd_fn(
                        self.state, banked, lr, np.float32(self._acc_count)
                    )
                    self.state = new_state
                if mon is not None and mon.memwatch is not None:
                    mon.memwatch.annotate(_step_sp, "step")
            if self.store_gradients:
                self._store_grads(banked)
            self._grad_acc = None
            self._acc_count = 0
            self._comm_acc_reduced = None
            self._after_optimizer_step(metrics)
            if wall:
                self.timers(STEP_MICRO_TIMER).stop(
                    sync_with=metrics.get("grad_norm")
                )
                self.timers.log(
                    [FORWARD_MICRO_TIMER, STEP_MICRO_TIMER],
                    ranks=[0],
                )
            if getattr(self, "_profile_args", None) is not None:
                self._maybe_profile_flops(*self._profile_args)
        elif wall:
            self.timers(STEP_MICRO_TIMER).stop()
        self.micro_steps += 1

    def _end_of_step_resilience(self):
        """Step-boundary resilience hook: fault injection, preemption
        (urgent checkpoint + sentinel exit), interval autosaves. Shared
        by the fused train_batch path and the imperative step() path."""
        if self._resilience is not None:
            self._resilience.on_step_boundary(self)

    def _after_optimizer_step(self, metrics):
        """Bookkeeping after the jitted update. The blocking scalar fetch of
        the overflow flag only happens for a DYNAMIC loss scaler (fp16), where
        the host must know whether to step the lr scheduler; the bf16/fp32 hot
        path stays fully async (overflow still discards the update on device)."""
        self.global_steps += 1
        self.global_samples += self.current_batch_size()
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if self.batch_size_scheduler is not None:
            self.batch_size_scheduler.step(self.global_steps)
        if self.summary_writer is not None:
            # write the PREVIOUS step's scalars (its device values have
            # completed, so device_get doesn't stall the pipeline — keeps
            # the async hot-path guarantee below)
            self._tb_write_pending()
            tb_metrics = dict(metrics)
            micro_loss = getattr(self, "_last_micro_loss", None)
            if micro_loss is not None:
                tb_metrics.setdefault("_micro_loss", micro_loss)
            self._tb_pending = (tb_metrics, self._current_lr(),
                                self.global_samples)
        if self.monitor is not None:
            self.monitor.registry.counter(
                "train_steps_total", "optimizer steps taken").inc()
            self.monitor.registry.gauge(
                "train_global_samples", "samples consumed").set(
                    self.global_samples)
            ivl = self.monitor.config.tb_export_interval
            if ivl and self.global_steps % ivl == 0:
                self.monitor.export_tensorboard(self.summary_writer,
                                                self.global_samples)
        self._pending_metrics = metrics
        if self._loss_scaler.dynamic:
            overflow = bool(jax.device_get(metrics["overflow"]))
            if overflow:
                log_dist(
                    f"OVERFLOW! skipping step; loss scale -> {self.loss_scale()}",
                    ranks=[0],
                )
            elif self.lr_scheduler is not None:
                self.lr_scheduler.step()
                self._lr_override = None
        else:
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
                self._lr_override = None
        self._end_of_step_resilience()

    def train_batch(self, batch=None, data_iter=None):
        """Fused one-step API (the TPU-native hot path). Accepts either a full
        global batch (leading dim = gas * micro * dp) or pulls one from the
        engine dataloader / provided iterator."""
        wall = self._config.wall_clock_breakdown
        wd = self.monitor.watchdog if self.monitor is not None else None
        ci = self.monitor.cost_index if self.monitor is not None else None
        mw = self.monitor.memwatch if self.monitor is not None else None
        step_fn = step_args = None  # what the perf doctor re-lowers
        with trace_span("engine/train_batch", lane="engine",
                        step=self.global_steps) as _tb_sp:
            with trace_span("engine/train_batch/feed", lane="engine"):
                placed = False
                if batch is None:
                    if self.datapipe is not None and data_iter is None:
                        # the pipe hands over a full global batch, usually
                        # already staged on the mesh by the prefetch thread
                        batch, placed = self.datapipe.next_global_batch()
                    else:
                        it = data_iter or self._train_iter()
                        parts = [next(it) for _ in range(
                            self.gradient_accumulation_steps())]
                        batch = jax.tree.map(
                            lambda *xs: np.concatenate(xs, axis=0), *parts)
                if not placed:
                    batch = self._place_batch(batch)
                batch = self._pack_pld(batch)
                rng = self._rng_args()
                lr = np.float32(self._current_lr())
            if wall:
                self._timer_start("train_batch")
            self.tput_timer.start()
            if self._layer_collector is not None:
                self._layer_collector.clear()
            with trace_span("engine/train_batch/dispatch",
                            lane="engine") as _disp_sp:
                if self._offload is not None:
                    loss, grads, gnorm, finite = self._offload_grads_fn()(
                        self.state, batch, rng
                    )
                    metrics = self._offload_apply(grads, gnorm, finite, loss)
                elif self.store_gradients:
                    # unfused route so the grads are observable (reference
                    # engine.py:1156 clones p.grad at step time)
                    loss, grads = self._batch_grads_fn()(
                        self.state, batch, rng)
                    self._store_grads(grads)
                    new_state, metrics = self._apply_update_fn()(
                        self.state, grads, lr,
                        np.float32(self.gradient_accumulation_steps()),
                    )
                    metrics = dict(metrics, loss=loss)
                    self.state = new_state
                else:
                    fn = self._train_batch_fn()
                    if wd is not None:
                        wd.watch("engine/train_step", fn)
                    if self.comm is not None:
                        step_args = (self.state, self._comm_state, batch,
                                     lr, rng)
                        new_state, self._comm_state, metrics = fn(*step_args)
                        self.comm.record_reduction_counters()
                    else:
                        step_args = (self.state, batch, lr, rng)
                        new_state, metrics = fn(*step_args)
                    step_fn = fn
                    self.state = new_state
                if ci is not None and step_fn is not None:
                    # perf doctor is opt-in precisely because of this
                    # sync: per-step MFU needs the real wall time, so the
                    # step result is blocked on INSIDE the span (the
                    # default path stays fully async — ThroughputTimer
                    # only syncs on reporting steps)
                    jax.block_until_ready(metrics["loss"])
                    _wall = _disp_sp.elapsed_s()
                    ci.observe("engine/train_step", step_fn, step_args)
                    _stats = ci.note_step("engine/train_step", _wall)
                    if _stats is not None:
                        _tb_sp.note(mfu=round(_stats["mfu"], 6),
                                    tflops=round(_stats["tflops"], 4),
                                    verdict=_stats["verdict"])
            if mw is not None:
                mw.annotate(_tb_sp, "train_batch")
            if self._layer_collector is not None:
                # jax.debug.callback taps inside the layer scan are
                # silently dropped once the scan is linearized under grad,
                # so the train step itself can never surface them; replay
                # the same (packed) batch and rng through the forward-only
                # program, where the taps do fire — forward hooks observe
                # forward activations, matching the reference semantics
                self._forward_only_fn()(self.state, batch, rng)
            if wd is not None:
                # the train step must compile once (after sharding
                # commits, see __init__) and stay compiled; cache growth
                # past the warm baseline means a shape/dtype leaked into
                # the trace
                if self._wd_warmup_left:
                    self._wd_warmup_left -= 1
                else:
                    wd.observe(step=self.global_steps)
            with trace_span("engine/train_batch/after", lane="engine"):
                self.micro_steps += self.gradient_accumulation_steps()
                self._after_optimizer_step(metrics)
                self.tput_timer.stop(global_step=True,
                                     sync_with=metrics["loss"])
        if wall:
            self.timers("train_batch").stop(sync_with=metrics["loss"])
            self._wall_steps = getattr(self, "_wall_steps", 0) + 1
            spp = max(self._config.steps_per_print, 1)
            if self.global_steps % spp == 0:
                # normalize by the steps ACTUALLY accumulated (resume or
                # mixed imperative/fused use lands off the spp boundary)
                self.timers.log(["train_batch"],
                                normalizer=self._wall_steps, ranks=[0])
                self._wall_steps = 0
        self._maybe_profile_flops(batch, rng)
        return metrics["loss"]

    def _timer_start(self, name):
        """Start a phase timer, recovering from a previous run that died
        between start and stop (a crashed step must not poison the timer;
        completed intervals in the window are kept)."""
        self.timers(name).safe_start()

    # ------------------------------------------------------------------ #
    # fork extras: layer-output hooks + gradient stashing
    # ------------------------------------------------------------------ #

    def register_forward_hook(self, layers_to_hook="all",
                              layer_name_pattern=None):
        """Capture layer outputs tapped via utils.hooks.record_layer_output
        (reference engine.py:227 torch forward hooks). Forces a retrace so
        the taps lower into the compiled step."""
        from ..utils import hooks

        self._layer_collector = hooks.LayerOutputCollector(
            layers_to_hook, layer_name_pattern
        )
        hooks.set_active(self._layer_collector)
        self._compiled.clear()

    def remove_forward_hooks(self):
        from ..utils import hooks

        hooks.set_active(None)
        self._layer_collector = None
        self._compiled.clear()

    @property
    def layer_outputs(self):
        if self._layer_collector is None:
            return {}
        jax.effects_barrier()  # flush pending tap callbacks
        return self._layer_collector.layer_outputs

    def _store_grads(self, grads):
        if self.store_gradients_cpu:
            self.stored_gradients = jax.tree.map(
                lambda g: np.asarray(jax.device_get(g)), grads
            )
        else:
            self.stored_gradients = grads

    def _batch_grads_fn(self):
        """jitted (state, batch, rng) -> (loss, summed grads over gas)."""

        def build():
            gas = self.gradient_accumulation_steps()

            def ds_batch_grads(state, batch, rng):
                rng = self._fold_rng(rng)
                return self._batch_grads(state, batch, rng, gas)

            return jax.jit(ds_batch_grads)

        return self._get_compiled("batch_grads", build)

    def _tb_write_pending(self):
        """Emit the previous step's tensorboard scalars (now settled on
        device). Called on the next boundary and before checkpoints."""
        pending = getattr(self, "_tb_pending", None)
        if self.summary_writer is None or pending is None:
            return
        self._tb_pending = None
        metrics_prev, lr_prev, samples_prev = pending
        scalars = {"Train/Samples/lr": lr_prev}
        loss = metrics_prev.get("loss")
        if loss is None:  # imperative path: last microbatch's loss
            loss = metrics_prev.get("_micro_loss")
        if loss is not None:
            scalars["Train/Samples/train_loss"] = jax.device_get(loss)
        if self._loss_scaler.dynamic:
            scalars["Train/Samples/loss_scale"] = jax.device_get(
                metrics_prev["loss_scale"]
            )
        self.summary_writer.write_scalars(scalars, samples_prev)
        self.summary_writer.flush()

    def _maybe_profile_flops(self, batch, rng):
        """One-shot flops profile at profile_step (reference engine.py:966-1019
        triggers the profiler inside forward at that step)."""
        fpc = self._config.flops_profiler_config
        if not fpc.enabled or self.global_steps != fpc.profile_step:
            return
        self._flops_profiled = True  # one-shot: stop stashing batches
        self._profile_args = None
        if isinstance(rng, tuple):
            rng = self._fold_rng(rng)
        from ..profiling.flops_profiler import FlopsProfiler

        def fwd(params, batch, rng):
            return self._call_loss(params, batch, rng, jnp.float32(1.0))[1]

        prof = FlopsProfiler(fwd)
        prof.start_profile(self.state.params, batch, rng)
        # every process runs the device work; only the first writes/logs
        if jax.process_index() == 0:
            out = prof.print_model_profile(profile_step=self.global_steps,
                                           top_modules=fpc.top_modules)
            if fpc.output_file:
                with open(fpc.output_file, "w") as f:
                    f.write(out + "\n")
        prof.end_profile()

    def eval_batch(self, batch):
        batch = self._place_batch(batch)
        rng = self._rng_args()
        # PLD keeps every layer at eval (theta pinned to 1)
        return self._forward_only_fn()(self.state, self._pack_pld(batch, 1.0), rng)

    def _train_iter(self):
        if not hasattr(self, "_train_data_iter") or self._train_data_iter is None:
            assert self.training_dataloader is not None, "no training data"
            from .dataloader import RepeatingLoader

            self._train_data_iter = iter(RepeatingLoader(self.training_dataloader))
        return self._train_data_iter

    # ------------------------------------------------------------------ #
    # checkpointing (reference engine.py:1462-1817)
    # ------------------------------------------------------------------ #

    def _zero3_consolidated_fp16_state_dict(self):
        """Fully-gathered compute-dtype params as a host pytree (reference
        engine.py:1820 gathers the ZeRO-3 partitions into one fp16 state
        dict). Gathers LEAF BY LEAF so peak device memory is one full tensor
        above the sharded copy (the reference bounds it per-layer the same
        way) — never the whole replicated model at once."""
        flat, treedef = jax.tree_util.tree_flatten(self.state.params)
        rep = NamedSharding(self.mesh, P())
        out = []
        for leaf in flat:
            full = jax.device_put(leaf, rep)  # reshard, no trace/compile
            out.append(np.asarray(jax.device_get(full)))
            del full
        return jax.tree_util.tree_unflatten(treedef, out)

    # reference-compatible public name
    zero3_consolidated_fp16_state_dict = _zero3_consolidated_fp16_state_dict

    def module_state_dict(self):
        """Host copy of the (consolidated) model parameters."""
        return self._zero3_consolidated_fp16_state_dict()

    def _fully_replicate(self, tree):
        """All-gather a sharded pytree so each process holds a full copy."""
        reps = jax.tree.map(lambda _: NamedSharding(self.mesh, P()), tree)

        def ds_replicate(t):
            return t

        return jax.jit(ds_replicate, out_shardings=reps)(tree)

    def _global_rows(self) -> int:
        """Rows consumed per optimizer step (micro * dp * gas) — the unit
        the datapipe cursor advances by; constant across elastic world
        flips (elasticity co-designs micro/gas so the product holds)."""
        return (self.train_micro_batch_size_per_gpu()
                * self.data_parallel_size
                * self.gradient_accumulation_steps())

    def _host_checkpoint_payload(self, state=None, client_state=None,
                                 comm_state=None):
        """Blocking device->host snapshot of everything a legacy-layout
        checkpoint stores, keyed by destination filename. The resilience
        manager takes this at the step boundary and hands it to the
        background writer (the arrays are host numpy, so training can
        mutate device state while the write proceeds); the sync save
        path writes the same payload inline. ``comm_state`` overrides the
        live residuals with an already-replicated snapshot (the
        multi-process single-writer path must not device_get the sharded
        originals — their shards live on other hosts)."""
        if state is None:
            state = self.state
        if comm_state is None:
            comm_state = self._comm_state
        model_states = {
            "module": to_host(state.params),
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "micro_steps": self.micro_steps,
            "dp_world_size": self.data_parallel_size,
            "mp_world_size": int(self.mesh.shape.get("model", 1)),
            # rows per optimizer step at save time: the datapipe cursor
            # remap on an elastic (different-world) resume checks this to
            # certify the sample stream continues exactly
            "global_rows": self._global_rows(),
            # bounds the per-rank offload-file scan on load (stale files
            # from an older, larger save into the same tag are ignored)
            "process_count": jax.process_count(),
            "lr_scheduler": (
                self.lr_scheduler.state_dict() if self.lr_scheduler else {}
            ),
            "datapipe": (
                self.datapipe.state_dict() if self.datapipe is not None
                else {}
            ),
            "client_state": client_state or {},
        }
        optim_states = {
            "master": to_host(state.master) if state.master is not None else {},
            "opt_state": to_host(state.opt_state),
            "scaler": to_host(state.scaler._asdict()),
            "step": int(jax.device_get(state.step)),
            "zero_stage": self.zero_stage,
        }
        if self._offload is not None:
            # host/NVMe state is the source of truth under offload
            optim_states["offload"] = self._offload.state_dict()
        if self.comm is not None:
            # error-feedback residuals: quantized modes need them to
            # resume bit-identically (a dropped residual replays the
            # quantization error into the next update)
            optim_states["comm"] = to_host(comm_state)
            optim_states["comm_fingerprint"] = repr(
                self.comm.state_fingerprint())
            # layout descriptor for the elastic reshard path: a resume at
            # a different world size reshapes the residuals from this
            # instead of zeroing them
            optim_states["comm_plan"] = self.comm.plan_summary()
        return {
            model_state_filename(): model_states,
            optim_state_filename(): optim_states,
        }

    def _reshard_comm_residuals(self, saved_buckets, saved_plan) -> bool:
        """Elastic restore of comm residuals whose checkpointed shape bakes
        in a DIFFERENT world size: rebuild them for the running topology
        via resilience/reshard.py instead of zeroing. True on success."""
        from ..resilience.reshard import reshard_comm_residuals

        target_plan = self.comm.plan_summary()
        resharded = reshard_comm_residuals(
            saved_buckets, saved_plan, target_plan)
        if resharded is None:
            return False
        try:
            self._comm_state = jax.tree.map(
                lambda x, s: _device_put_global(x, s, np.float32),
                resharded, self.comm.state_shardings())
        except Exception as e:
            logger.warning(
                "placing resharded comm residuals failed (%s): error "
                "feedback restarts from zero", e)
            return False
        w_from = saved_plan.get("world") if isinstance(saved_plan, dict) \
            else None
        logger.info(
            "comm residuals resharded for the new topology (world %s -> "
            "%s)", w_from, target_plan["world"])
        trace_instant("resilience/comm_reshard", lane="resilience",
                      world_from=w_from, world_to=target_plan["world"])
        return True

    def _restore_comm_state(self, host_state, fingerprint, comm_plan=None):
        """Re-place checkpointed error-feedback residuals. Residuals from
        a different bucket layout / mode are useless (and misapplying them
        corrupts gradients) — a fingerprint mismatch first attempts the
        elastic world-size reshard (when a compatible ``comm_plan`` rode
        along), then keeps the fresh zeros."""
        if host_state is None:
            if any(True for _ in jax.tree.leaves(self._comm_state)):
                logger.warning(
                    "checkpoint carries no comm residuals: error feedback "
                    "restarts from zero (one step of re-accumulated "
                    "quantization error)")
            return
        if fingerprint != repr(self.comm.state_fingerprint()):
            if self._reshard_comm_residuals(host_state, comm_plan):
                return
            logger.warning(
                "checkpointed comm residuals were saved under a different "
                "bucket layout/mode/world (fingerprint mismatch): error "
                "feedback restarts from zero")
            return
        try:
            # msgpack round-trips the per-bucket list as an index-keyed dict
            if isinstance(host_state, dict):
                host_state = [host_state[k]
                              for k in sorted(host_state, key=int)]
            self._comm_state = jax.tree.map(
                lambda x, s: _device_put_global(x, s, np.float32),
                list(host_state), self.comm.state_shardings())
        except Exception as e:
            logger.warning(
                "comm residual restore failed (%s): error feedback "
                "restarts from zero", e)

    # ------------------------------------------------------------------ #
    # live re-mesh (lifecycle/)
    # ------------------------------------------------------------------ #

    def remesh(self, world_size: int, devices=None):
        """Flip the data-parallel topology IN PROCESS at a step boundary.

        The kill-free counterpart of the supervisor's elastic relaunch:
        instead of checkpoint → SIGKILL → re-exec → reshard-on-load, the
        running engine rebuilds the mesh over ``devices`` (default: the
        first ``world_size`` local devices — a pool *shrink*; growth past
        the process's fixed device count still needs a relaunch),
        re-places every ``EngineState`` leaf with ``jax.device_put`` onto
        the new specs, rebuilds the GradReducer plan and reshards its
        error-feedback residuals via ``resilience/reshard.py`` — all
        without a checkpoint round trip. With canonical-slot reduction
        (``elasticity.canonical_shards``) the loss curve continues
        bit-identically, exactly as a kill-restart resume would.

        Requires an ``elasticity`` block (it re-solves the micro/gas
        batch split at the new world size with the global batch — and
        therefore the datapipe row stream — invariant) and a clean
        accumulation boundary (no banked gradients in flight).
        """
        if world_size == self.data_parallel_size:
            return self.data_parallel_size
        if self._offload is not None:
            raise RuntimeError(
                "live re-mesh is not supported with optimizer offload "
                "(host-side state is keyed to the old placement)")
        if self._acc_count or self._stashed is not None:
            raise RuntimeError(
                "live re-mesh must happen at an optimizer-step boundary "
                "(gradients are banked mid-accumulation)")
        if not self._config.elasticity_enabled:
            raise RuntimeError(
                "live re-mesh needs an elasticity block: the batch "
                "triple must re-solve at the new world size with the "
                "global batch invariant")
        valid = self._config.elastic_valid_world_sizes or []
        if valid and world_size not in valid:
            raise ValueError(
                f"world_size {world_size} is not an admissible elastic "
                f"world size (valid: {sorted(valid)})")
        if devices is None:
            local = jax.devices()
            if world_size > len(local):
                raise ValueError(
                    f"cannot re-mesh to {world_size} devices in process: "
                    f"only {len(local)} exist (growth needs a relaunch)")
            devices = local[:world_size]

        old_world = self.data_parallel_size
        t0 = time.time()
        # the span COVERS the re-placement stall — the goodput ledger's
        # `remesh` bucket is carved from exactly this interval
        with trace_span("lifecycle/remesh", lane="lifecycle",
                        world_from=old_world, world_to=world_size):
            new_dp = self._remesh_apply(world_size, devices)
        stall_ms = (time.time() - t0) * 1000.0
        log_dist(
            f"live re-mesh: world {old_world} -> {new_dp} in "
            f"{stall_ms:.0f}ms (step {self.global_steps}, "
            f"mesh={dict(self.mesh.shape)})", ranks=[0])
        return new_dp

    def _remesh_apply(self, world_size: int, devices) -> int:
        import copy

        from . import constants as _c

        old_rows = self._global_rows()

        # ---- snapshots the new topology must inherit ----
        old_comm_host = old_comm_fp = old_comm_plan = None
        if self.comm is not None:
            old_comm_host = to_host(self._comm_state)
            old_comm_fp = repr(self.comm.state_fingerprint())
            old_comm_plan = self.comm.plan_summary()

        # ---- re-solve the config at the new world size ----
        # elasticity rewrote the batch triple into the param dict at
        # init; strip it so the re-parse re-derives micro/gas for the
        # new world (the global batch is pinned by the elasticity block)
        raw = copy.deepcopy(self._config._param_dict)
        for key in (_c.TRAIN_BATCH_SIZE, _c.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                    _c.GRADIENT_ACCUMULATION_STEPS):
            raw.pop(key, None)
        new_config = TrainingConfig(raw, world_size=world_size)

        # ---- the new mesh, over the surviving devices ----
        mesh_cfg = new_config.mesh_config()
        if mesh_cfg is not None:
            new_mesh = sharding.from_config(mesh_cfg, devices)
        else:
            from ..parallel.topology import build_mesh

            new_mesh = build_mesh({DATA_AXIS: len(devices)},
                                  devices=devices)
        new_dp = sharding.data_parallel_size(new_mesh)
        if new_dp != world_size:
            raise ValueError(
                f"the new mesh resolves to data-parallel size {new_dp}, "
                f"not the requested {world_size} — fix the mesh block's "
                "axis extents (use -1 to infer from the device count)")

        # ---- swap topology + config, rebuild specs ----
        self._config = new_config
        self.mesh = new_mesh
        self.batch_axes = sharding.batch_axes(new_mesh)
        self.data_parallel_size = new_dp
        params_tree = self.state.params
        self.param_specs = partition.tree_specs(
            params_tree, self._tp_specs, self.zero_stage, new_mesh, "param")
        self.master_specs = partition.tree_specs(
            params_tree, self._tp_specs, self.zero_stage, new_mesh, "master")
        self.grad_specs = partition.tree_specs(
            params_tree, self._tp_specs, self.zero_stage, new_mesh, "grad")
        if self._global_rows() != old_rows:
            raise RuntimeError(
                f"elastic re-solve changed the global batch rows "
                f"({old_rows} -> {self._global_rows()}); the datapipe "
                "stream would diverge — the elasticity block must pin "
                "one global batch across its world sizes")
        if self.canonical_shards and (
                self.canonical_shards % new_dp != 0):
            raise RuntimeError(
                f"elasticity.canonical_shards={self.canonical_shards} is "
                f"not a multiple of the new data-parallel size {new_dp}; "
                "bit-identical reduction cannot continue")

        # ---- re-place every device-state leaf onto the new mesh ----
        def put(tree, specs):
            return jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(new_mesh, s)),
                tree, specs)

        replicated = NamedSharding(new_mesh, P())

        def put_replicated(tree):
            return jax.tree.map(
                lambda x: jax.device_put(x, replicated), tree)

        state = self.state
        new_params = put(state.params, self.param_specs)
        new_master = (put(state.master, self.master_specs)
                      if state.master is not None else None)
        opt_src = new_master if self._use_master else new_params
        opt_shardings = _opt_state_shardings(
            self.optimizer, opt_src, new_mesh, self.master_specs)
        if opt_shardings is not None:
            new_opt = jax.tree.map(
                lambda x, s: jax.device_put(x, s),
                state.opt_state, opt_shardings)
        else:
            new_opt = put_replicated(state.opt_state)
        self.state = EngineState(
            step=jax.device_put(state.step, replicated),
            params=new_params,
            master=new_master,
            opt_state=new_opt,
            scaler=put_replicated(state.scaler),
            skipped=jax.device_put(state.skipped, replicated),
        )

        # ---- rebuild the reducer; reshard residuals in memory ----
        if self.comm is not None:
            from .comm import overlap as comm_overlap
            from .comm.reducer import GradReducer

            self.comm = GradReducer(
                new_config.comm_config(), new_mesh,
                axis_name=self.batch_axes,
                registry=(self.monitor.registry
                          if self.monitor is not None else None),
                canonical=self.canonical_shards)
            self.comm.build_plan(new_params)
            self._comm_state = self.comm.init_state()
            self._comm_acc_reduced = None
            # same math as the kill-restart load path: fingerprint match
            # restores directly, a world-size mismatch reshards the
            # error-feedback residuals onto the new plan
            self._restore_comm_state(
                old_comm_host, old_comm_fp, old_comm_plan)
            self._comm_overlap = (
                comm_overlap.OverlapScheduler()
                if comm_overlap.resolve_overlap(
                    new_config.comm_config(), world=self.comm.world,
                    canonical=self.canonical_shards)
                else None)

        # ---- restart data production against the new mesh ----
        # (drops any staged batches; the cursor is world-agnostic because
        # the global rows per step are invariant — remap_data_state at
        # equal rows is the identity)
        if self.datapipe is not None:
            self.datapipe.load_state_dict(self.datapipe.state_dict())

        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu()
            * self.gradient_accumulation_steps(),
            num_workers=self.data_parallel_size,
            steps_per_output=new_config.steps_per_print,
        )
        # every compiled entry closed over the old mesh/specs
        self._compiled = {}
        # the first step on the new topology recompiles + recommits; skip
        # one watchdog observation so the warm baseline re-locks
        self._wd_warmup_left = 1

        if self.monitor is not None:
            trace_instant("mesh/build", lane="mesh",
                          axes={k: int(v)
                                for k, v in dict(new_mesh.shape).items()},
                          devices=int(new_mesh.devices.size))
        return new_dp

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True):
        self._tb_write_pending()
        if tag is None:
            tag = f"global_step{self.global_steps}"
        tag = str(tag)
        if self._config.checkpoint_tag_validation_enabled:
            validate_tag_across_processes(
                tag, self._config.checkpoint_tag_validation_fail
            )
        if self._resilience is not None:
            self._resilience.note_save_dir(save_dir)
            if self._resilience.handles_save():
                return self._resilience.save_checkpoint(
                    self, save_dir, tag, client_state,
                    save_latest=save_latest)
        ck = CheckpointEngine(save_dir, tag)
        if self._config.checkpoint_sharded_io:
            if self._offload is None:
                return self._save_checkpoint_sharded(ck, save_dir, tag,
                                                     client_state, save_latest)
            logger.warning(
                "checkpoint.sharded_io ignored: host/NVMe offload keeps the "
                "optimizer state off-device, so the save uses the legacy "
                "(replicating) layout"
            )
        state = self.state
        comm_snapshot = None
        if jax.process_count() > 1:
            # single-writer layout: replicate device state so every process
            # holds an addressable full copy (a jitted identity with
            # replicated out_shardings = global all-gather), then only
            # process 0 writes. The scalable alternative is
            # checkpoint.sharded_io (orbax per-shard parallel write).
            state = self._fully_replicate(state)
            if self.comm is not None and jax.tree.leaves(self._comm_state):
                # error-feedback residuals are sharded P(axis, None) across
                # processes too — same replication, same single writer
                comm_snapshot = self._fully_replicate(self._comm_state)
            if self._offload is not None and jax.process_index() != 0:
                # under offload each process is the ONLY holder of its master
                # shards/moments: persist them per-rank (the analog of the
                # reference's per-dp-rank zero_pp_rank_* optimizer files)
                ck.save(
                    optim_state_filename(jax.process_index()),
                    {
                        "offload": self._offload.state_dict(),
                        "step": int(jax.device_get(state.step)),
                        "zero_stage": self.zero_stage,
                    },
                )
            if jax.process_index() != 0:
                return True
        for fname, tree in self._host_checkpoint_payload(
                state=state, client_state=client_state,
                comm_state=comm_snapshot).items():
            ck.save(fname, tree)
        if save_latest and jax.process_index() == 0:
            write_latest(save_dir, tag)
        # drop the recovery tool next to the shards (reference
        # engine.py:1800-1808 copies zero_to_fp32.py into the ckpt dir);
        # single writer — the open() is a plain truncate
        if jax.process_index() == 0:
            from ..checkpoint.zero_to_fp32 import write_recovery_stub

            write_recovery_stub(ck.ckpt_dir)
        log_dist(f"saved checkpoint {ck.ckpt_dir}", ranks=[0])
        return True

    def _save_checkpoint_sharded(self, ck, save_dir, tag, client_state,
                                 save_latest):
        """orbax per-shard parallel write: every process persists only its
        addressable shards — no replication gather. The scalable analog of
        the reference's per-DP-rank zero_pp_rank_* files."""
        state = self.state
        save_sharded_tree(ck.path(f"{SHARDED_STATE_DIR}/params"), state.params)
        optim_tree = {
            "opt_state": state.opt_state,
            "scaler": state.scaler._asdict(),
            "step": state.step,
            "skipped": state.skipped,
        }
        save_sharded_tree(ck.path(f"{SHARDED_STATE_DIR}/optim"), optim_tree)
        if state.master is not None:
            # masters in their own tree so zero_to_fp32 consolidation can
            # restore them WITHOUT reading the (2x bigger) Adam moments
            save_sharded_tree(ck.path(f"{SHARDED_STATE_DIR}/master"),
                              state.master)
        if self.comm is not None and jax.tree.leaves(self._comm_state):
            # error-feedback residuals, already sharded P(data, None)
            save_sharded_tree(ck.path(f"{SHARDED_STATE_DIR}/comm"),
                              {"buckets": self._comm_state})
        if jax.process_index() == 0:
            meta = {
                "sharded_io": True,
                "global_steps": self.global_steps,
                "global_samples": self.global_samples,
                "skipped_steps": self.skipped_steps,
                "micro_steps": self.micro_steps,
                "dp_world_size": self.data_parallel_size,
                "mp_world_size": int(self.mesh.shape.get("model", 1)),
                "global_rows": self._global_rows(),
                "zero_stage": self.zero_stage,
                "lr_scheduler": (
                    self.lr_scheduler.state_dict() if self.lr_scheduler else {}
                ),
                "datapipe": (
                    self.datapipe.state_dict() if self.datapipe is not None
                    else {}
                ),
                "client_state": client_state or {},
            }
            if self.comm is not None:
                meta["comm_fingerprint"] = repr(self.comm.state_fingerprint())
                meta["comm_plan"] = self.comm.plan_summary()
            ck.save(model_state_filename(), meta)
            from ..checkpoint.zero_to_fp32 import write_recovery_stub

            write_recovery_stub(ck.ckpt_dir)
            if save_latest:
                write_latest(save_dir, tag)
        log_dist(f"saved sharded checkpoint {ck.ckpt_dir}", ranks=[0])
        return True

    def _load_checkpoint_sharded(self, ck, load_module_only,
                                 load_optimizer_states,
                                 load_lr_scheduler_states):
        if not ck.exists(model_state_filename()):
            logger.warning("sharded checkpoint %s has no metadata (partial "
                           "save?); nothing loaded", ck.ckpt_dir)
            return None, {}
        meta = ck.load(model_state_filename())
        state = self.state
        # restore the skip counter from metadata up front; a successful
        # optimizer restore overwrites it with the device value
        state = state._replace(
            skipped=jnp.asarray(meta.get("skipped_steps", 0), jnp.int32)
        )
        params = load_sharded_tree(
            ck.path(f"{SHARDED_STATE_DIR}/params"), state.params
        )
        state = state._replace(params=params)
        if self._offload is not None:
            # sharded checkpoints carry no host/NVMe optimizer state; push
            # the restored params into the offload master so the next step
            # does not revert them (moments restart — warn loudly)
            self._offload.set_master_params(self._to_master_sharded(params))
            logger.warning(
                "sharded checkpoint loaded into an offload engine: params "
                "restored, optimizer moments reset (sharded_io saves no "
                "offload state)"
            )
        optim_dir = ck.path(f"{SHARDED_STATE_DIR}/optim")
        master_dir = ck.path(f"{SHARDED_STATE_DIR}/master")
        optim_restored = False
        master_restored = False
        if (not load_module_only and load_optimizer_states
                and self._offload is None and os.path.isdir(optim_dir)):
            target = {
                "opt_state": state.opt_state,
                "scaler": state.scaler._asdict(),
                "step": state.step,
                "skipped": state.skipped,
            }
            optim_keys = sharded_tree_top_keys(optim_dir)
            if (state.master is not None and not os.path.isdir(master_dir)
                    and (optim_keys is None or "master" in optim_keys)):
                # older sharded layout stored the master inside the optim
                # tree; a checkpoint with no master anywhere (fp32 saver)
                # must NOT get the key injected or the whole restore fails.
                # Unreadable manifest (None) falls back to attempting the
                # legacy shape.
                target["master"] = state.master
            restored = None
            try:
                restored = load_sharded_tree(optim_dir, target)
            except Exception as first_err:
                if "master" in target:
                    # the legacy-layout guess was wrong (checkpoint has no
                    # master tree): retry plain before giving anything up
                    target.pop("master")
                    try:
                        restored = load_sharded_tree(optim_dir, target)
                    except Exception as e:
                        logger.warning(
                            "sharded optimizer restore failed (%s); "
                            "params-only load — likely a zero-stage/"
                            "structure change since save", e
                        )
                else:
                    logger.warning(
                        "sharded optimizer restore failed (%s); params-only "
                        "load — likely a zero-stage/structure change since "
                        "save", first_err
                    )
            if restored is not None:
                master = restored.pop("master", None)
                if state.master is not None and os.path.isdir(master_dir):
                    try:
                        master = load_sharded_tree(master_dir, state.master)
                    except Exception as e:
                        logger.warning(
                            "sharded master restore failed (%s); master will "
                            "be re-derived from the restored params", e
                        )
                        master = None
                # scalars replicated over the mesh (the initial state's
                # scalar leaves may be uncommitted single-device arrays, so
                # their sharding is not a usable placement target)
                rep = NamedSharding(self.mesh, P())
                state = state._replace(
                    opt_state=restored["opt_state"],
                    scaler=LossScaleState(**{
                        k: jax.device_put(v, rep)
                        for k, v in restored["scaler"].items()
                    }),
                    step=jax.device_put(restored["step"], rep),
                    skipped=jax.device_put(restored["skipped"], rep),
                )
                if master is not None:
                    state = state._replace(master=master)
                    master_restored = True
                optim_restored = True
        comm_dir = ck.path(f"{SHARDED_STATE_DIR}/comm")
        if (self.comm is not None and not load_module_only
                and load_optimizer_states and os.path.isdir(comm_dir)):
            if meta.get("comm_fingerprint") == repr(
                    self.comm.state_fingerprint()):
                try:
                    restored_comm = load_sharded_tree(
                        comm_dir, {"buckets": self._comm_state})
                    self._comm_state = restored_comm["buckets"]
                except Exception as e:
                    logger.warning(
                        "sharded comm residual restore failed (%s): error "
                        "feedback restarts from zero", e)
            else:
                # the fingerprint bakes in the world size: on an elastic
                # resume the residual arrays have a DIFFERENT global shape
                # than the running reducer's, so they load raw (no
                # abstract target) and reshape via resilience/reshard.py
                resharded = False
                try:
                    raw = load_sharded_tree_raw(comm_dir)
                    resharded = self._reshard_comm_residuals(
                        raw.get("buckets") if isinstance(raw, dict)
                        else None,
                        meta.get("comm_plan"))
                except Exception as e:
                    logger.warning(
                        "raw comm residual read failed (%s)", e)
                if not resharded:
                    logger.warning(
                        "checkpointed comm residuals were saved under a "
                        "different bucket layout/mode/world (fingerprint "
                        "mismatch): error feedback restarts from zero")
        if state.master is not None and not master_restored:
            # no master came off disk (params-only load, or a checkpoint
            # saved without one): re-derive it from the restored params, or
            # the first optimizer step would revert them
            state = state._replace(
                master=partition.constrain(
                    jax.tree.map(lambda p: p.astype(jnp.float32), params),
                    self.master_specs, self.mesh,
                )
            )
        self.state = state
        self.global_steps = int(meta.get("global_steps", 0))
        if self.batch_size_scheduler is not None:
            self.batch_size_scheduler.step(self.global_steps)
        self.global_samples = int(meta.get("global_samples", 0))
        self.micro_steps = int(meta.get("micro_steps", 0))
        if self.datapipe is not None:
            if meta.get("datapipe"):
                from ..resilience.reshard import remap_data_state

                self.datapipe.load_state_dict(remap_data_state(
                    meta["datapipe"], meta.get("global_rows"),
                    self._global_rows()))
            else:
                logger.warning(
                    "checkpoint %s carries no datapipe state (saved "
                    "before the datapipe existed?): the input pipe "
                    "restarts from epoch 0 and will NOT replay the "
                    "original batch stream; seeding its curriculum step "
                    "from global_steps=%d so the seq-len/batch-size "
                    "schedules stay consistent", ck.ckpt_dir,
                    self.global_steps)
                self.datapipe.seed_step(self.global_steps)
        if (load_lr_scheduler_states and self.lr_scheduler is not None
                and meta.get("lr_scheduler")):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        log_dist(f"loaded sharded checkpoint {ck.ckpt_dir}", ranks=[0])
        return ck.ckpt_dir, meta.get("client_state", {})

    def load_checkpoint(
        self,
        load_dir,
        tag=None,
        load_module_only=False,
        load_optimizer_states=True,
        load_lr_scheduler_states=True,
    ):
        if tag is None:
            tag = read_latest(load_dir)
            if tag is None:
                logger.warning("no 'latest' file in %s; nothing loaded", load_dir)
                return None, {}
        # never load a torn/corrupt tag: committed tags verify against
        # their manifest, and an unloadable requested tag falls back to
        # the newest older valid one (a crash mid-save costs at most one
        # checkpoint interval, never the run)
        verify = (self._resilience.cfg.verify_on_load
                  if self._resilience is not None else True)
        requested = str(tag)
        tag, fell_back = resolve_load_tag(load_dir, requested,
                                          verify_checksums=verify)
        if tag is None:
            return None, {}
        if fell_back and self._resilience is not None:
            self._resilience.note_fallback(skipped_tag=requested)
        ck = CheckpointEngine(load_dir, str(tag))
        if os.path.isdir(ck.path(SHARDED_STATE_DIR)):
            loaded = self._load_checkpoint_sharded(
                ck, load_module_only, load_optimizer_states,
                load_lr_scheduler_states,
            )
            if loaded[0] is not None and self._resilience is not None:
                self._resilience.note_resumed(tag)
            return loaded
        if not ck.exists(model_state_filename()):
            logger.warning("checkpoint %s not found", ck.ckpt_dir)
            return None, {}

        model_states = ck.load(model_state_filename())
        params_host = model_states["module"]
        mesh = self.mesh

        def put(tree_host, specs, dtype):
            return jax.tree.map(
                lambda x, s: _device_put_global(
                    x, NamedSharding(mesh, s), dtype
                ),
                _retree(tree_host, self.state.params),
                specs,
            )

        new_params = put(params_host, self.param_specs, self._compute_dtype)
        state = self.state._replace(params=new_params)

        if not load_module_only and load_optimizer_states and ck.exists(
            optim_state_filename()
        ):
            optim_states = ck.load(optim_state_filename())
            off_sd = (self._resolve_offload_sd(ck, optim_states, model_states)
                      if self._offload is not None else None)
            if self._offload is not None and off_sd:
                self._offload.load_state_dict(off_sd)
                # refresh device params from the restored master copy
                fresh = self._offload.current_params()
                state = state._replace(
                    params=self._offload_reshard_fn()(fresh),
                    step=jnp.asarray(optim_states["step"], jnp.int32),
                )
            elif self._offload is not None:
                # no usable offload state: the host masters still hold the
                # INIT-time params and would revert the restored weights on
                # the next step — push the checkpoint params into them
                self._offload.set_master_params(
                    self._to_master_sharded(state.params))
                logger.warning(
                    "checkpoint carried no matching offload state: params "
                    "pushed into host masters, optimizer moments reset"
                )
            elif state.master is not None and optim_states.get("master"):
                master = jax.tree.map(
                    lambda x, s: _device_put_global(
                        x, NamedSharding(mesh, s), jnp.float32
                    ),
                    _retree(optim_states["master"], self.state.master),
                    self.master_specs,
                )
                state = state._replace(master=master)
            if self._offload is None:
                # device opt_state restore — for offload engines the host
                # chunks are the source of truth and the device opt_state
                # is (), which a non-offload checkpoint cannot populate
                opt_state = jax.tree.map(
                    lambda x, ref: _device_put_global(
                        x, ref.sharding, ref.dtype),
                    _retree(optim_states["opt_state"], self.state.opt_state),
                    self.state.opt_state,
                )
                state = state._replace(opt_state=opt_state)
            sc = optim_states["scaler"]
            scaler = LossScaleState(
                loss_scale=jnp.asarray(sc["loss_scale"], jnp.float32),
                good_steps=jnp.asarray(sc["good_steps"], jnp.int32),
                hysteresis=jnp.asarray(sc["hysteresis"], jnp.int32),
            )
            state = state._replace(
                scaler=scaler,
                step=jnp.asarray(optim_states["step"], jnp.int32),
            )
            if self.comm is not None:
                self._restore_comm_state(
                    optim_states.get("comm"),
                    optim_states.get("comm_fingerprint"),
                    optim_states.get("comm_plan"))

        state = state._replace(
            skipped=jnp.asarray(model_states.get("skipped_steps", 0), jnp.int32)
        )
        self.state = state
        self.global_steps = int(model_states.get("global_steps", 0))
        if self.batch_size_scheduler is not None:
            self.batch_size_scheduler.step(self.global_steps)
        self.global_samples = int(model_states.get("global_samples", 0))
        self.micro_steps = int(model_states.get("micro_steps", 0))
        if self.datapipe is not None:
            if model_states.get("datapipe"):
                from ..resilience.reshard import remap_data_state

                self.datapipe.load_state_dict(remap_data_state(
                    model_states["datapipe"],
                    model_states.get("global_rows"), self._global_rows()))
            else:
                logger.warning(
                    "checkpoint %s carries no datapipe state (saved "
                    "before the datapipe existed?): the input pipe "
                    "restarts from epoch 0 and will NOT replay the "
                    "original batch stream; seeding its curriculum step "
                    "from global_steps=%d so the seq-len/batch-size "
                    "schedules stay consistent", ck.ckpt_dir,
                    self.global_steps)
                self.datapipe.seed_step(self.global_steps)
        if (
            load_lr_scheduler_states
            and self.lr_scheduler is not None
            and model_states.get("lr_scheduler")
        ):
            self.lr_scheduler.load_state_dict(model_states["lr_scheduler"])
        log_dist(f"loaded checkpoint {ck.ckpt_dir}", ranks=[0])
        if self._resilience is not None:
            self._resilience.note_resumed(tag)
        return ck.ckpt_dir, model_states.get("client_state", {})


# ---------------------------------------------------------------------- #
# helpers
# ---------------------------------------------------------------------- #


def _default_mesh():
    # all devices on the legacy data axis (sharding.default_mesh mirrors
    # this exactly; kept as one call site so the behavior can't fork)
    return sharding.default_mesh()


def _loss_fn_takes_rng(fn) -> bool:
    try:
        sig = inspect.signature(fn)
        kinds = [p.kind for p in sig.parameters.values()]
        if inspect.Parameter.VAR_POSITIONAL in kinds:
            return True  # *args catches the rng
        return len([p for p in sig.parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                    and p.name != "pld_theta"]) >= 3
    except (TypeError, ValueError):
        return False


def _loss_fn_takes_pld(fn) -> bool:
    try:
        return "pld_theta" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def _optimizer_base_lr(opt, config):
    lr = getattr(opt, "lr", None)
    if lr is not None:
        return lr
    return (config.optimizer_params or {}).get("lr", 1e-3)


def _opt_state_shardings(opt, params, mesh, master_specs):
    """Shardings for optimizer state: moments mirror the master specs; scalars
    replicated."""
    state_shape = jax.eval_shape(opt.init, params)

    # moments have the same tree structure as params — map specs by structure
    def build(tree_shape):
        # NamedTuple states: map each field
        out = []
        for field in tree_shape._fields:
            val = getattr(tree_shape, field)
            if isinstance(val, jax.ShapeDtypeStruct):
                out.append(NamedSharding(mesh, P()))
            else:
                out.append(
                    jax.tree.map(lambda s: NamedSharding(mesh, s), master_specs)
                )
        return type(tree_shape)(*out)

    try:
        return build(state_shape)
    except Exception:
        return None


def _retree(host_tree, ref_tree):
    """Restore a msgpack-loaded dict tree to the reference pytree structure,
    matching dict keys / namedtuple field names (not flatten order)."""
    from flax import serialization

    return serialization.from_state_dict(ref_tree, host_tree)


def _device_put_global(x, sharding, dtype=None):
    """Place a host value onto a (possibly process-spanning) sharding.

    ``jax.device_put`` of a host array onto a non-addressable sharding
    broadcasts the FULL array for a cross-process equality assert —
    one collective per leaf, which is slow and desyncs against any
    concurrently-issued collective. ``make_array_from_callback`` builds
    the same global array purely from local shards, collective-free;
    every process passes the same host value (checkpoint loads do: all
    processes read the same files)."""
    arr = np.asarray(x, dtype)
    if jax.process_count() > 1 and not sharding.is_fully_addressable:
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])
    return jax.device_put(jnp.asarray(arr), sharding)


# ---------------------------------------------------------------------- #
# initialize()
# ---------------------------------------------------------------------- #


def initialize(
    args=None,
    model: Callable = None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    mpu=None,
    dist_init_required=None,
    collate_fn=None,
    config=None,
    config_params=None,
    mesh=None,
    param_specs=None,
    rng=None,
):
    """Build an Engine (reference deepspeed/__init__.py:52).

    Returns (engine, optimizer, training_dataloader, lr_scheduler).
    `model` is a loss callable `loss_fn(params, batch[, rng])`;
    `model_parameters` is the initial params pytree.
    """
    assert model is not None, "deepspeed.initialize requires a model"
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    assert config is not None, "a config (dict or json path) is required"

    # A "mesh" block in the config chooses the SPMD layout. It must be
    # built BEFORE TrainingConfig: the batch triple's world_size is
    # derived FROM the mesh, but the block lives inside the config — so
    # peek the raw dict here and hand every engine the built mesh.
    if mesh is None:
        mesh = _mesh_from_raw_config(config)

    from .pipe.module import PipelineModule

    # Streaming ZeRO-Infinity route (reference engine.py:803 one-flag
    # stage-3/Infinity entry): a model *config* (GPTConfig/BertConfig)
    # plus a config enabling streaming — an explicit "streaming" block or
    # zero stage 3 with offload_param.device cpu/nvme — constructs the
    # StreamedOffloadEngine (host-RAM/NVMe optimizer state, quantized
    # offload wire, optionally quantized device residency).
    from ..models.bert import BertConfig as _BertConfig
    from ..models.gpt import GPTConfig as _GPTConfig

    if isinstance(model, (_GPTConfig, _BertConfig)):
        # streaming world = the dp extent (single-controller; one device
        # unless a mesh with batch axes is given) — NOT jax.device_count,
        # which would mis-derive the batch triple on multi-device hosts
        world_size = (sharding.data_parallel_size(mesh)
                      if mesh is not None else 1)
        ds_config = (config if isinstance(config, TrainingConfig)
                     else TrainingConfig(config, world_size=world_size))
        if not ds_config.streaming_enabled:
            raise ValueError(
                "initialize() got a model config (GPTConfig/BertConfig) "
                "but the ds_config does not enable the streaming engine — "
                'add a "streaming" block or zero stage 3 with '
                "offload_param.device cpu/nvme, or pass a loss callable "
                "instead of a model config")
        from .offload.streaming import build_streamed_engine

        engine = build_streamed_engine(
            model, ds_config, host_params=model_parameters, mesh=mesh)
        return engine, engine.opt, None, None

    if isinstance(model, PipelineModule):
        # reference __init__.py:52 builds a PipelineEngine for PipelineModule
        from .pipe.engine import PipelineEngine

        world_size = _world_size_for_config(mesh)
        ds_config = config if isinstance(config, TrainingConfig) else TrainingConfig(
            config, world_size=world_size
        )
        engine = PipelineEngine(
            module=model,
            config=ds_config,
            mesh=mesh,
            optimizer=optimizer,
            lr_scheduler=lr_scheduler,
            training_data=training_data,
            rng=rng,
        )
        return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler

    assert model_parameters is not None, "model_parameters (params pytree) required"

    world_size = _world_size_for_config(mesh)
    ds_config = config if isinstance(config, TrainingConfig) else TrainingConfig(
        config, world_size=world_size
    )
    engine = Engine(
        model=model,
        params=model_parameters,
        config=ds_config,
        mesh=mesh,
        optimizer=optimizer,
        lr_scheduler=lr_scheduler,
        training_data=training_data,
        collate_fn=collate_fn,
        param_specs=param_specs,
        rng=rng,
        mpu=mpu,
    )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def _world_size_for_config(mesh) -> int:
    if mesh is not None:
        return sharding.data_parallel_size(mesh)
    n = len(jax.devices())
    return n


def _mesh_from_raw_config(config) -> Optional["jax.sharding.Mesh"]:
    """Build the mesh a config's ``"mesh"`` block describes (None when
    the block is absent or disabled). Accepts the same config forms as
    initialize(): a TrainingConfig, a dict, or a json path."""
    raw = config
    if isinstance(raw, TrainingConfig):
        mc = raw.mesh_config()
        return sharding.from_config(mc) if mc is not None else None
    if isinstance(raw, str):
        import json

        with open(raw) as f:
            raw = json.load(f)
    if not isinstance(raw, dict):
        return None
    block = raw.get("mesh")
    if not isinstance(block, dict):
        return None
    if block.get("enabled") is False:
        return None
    return sharding.from_config(block)
