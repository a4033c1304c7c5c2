"""Continuous-batching inference serving (the inference half of the
roadmap's north star).

``ServingEngine`` turns concurrent requests into efficient fixed-shape
decode batches over a slot pool backed by a paged KV cache;
``PipelineServingBridge`` exposes the same surface over
``PipelineEngine.inference_batch`` for pipelined models. On top of
single engines, the fleet layer (``FleetRouter`` over
``ThreadReplica``/``SubprocessReplica`` workers) adds admission
control, wall-clock deadlines, health-checked failover, and rolling
restarts. See docs/tutorials/serving.md for the walkthrough.
"""

from .config import RouterConfig, ServingConfig, SLOConfig
from .engine import (
    EngineDrainingError,
    PipelineServingBridge,
    ServingEngine,
    derive_request_seed,
    idle_slots,
    make_decode_step,
    pack_slots,
    request_sample_key,
    unpack_slots,
)
from .fleet import (
    ReplicaUnavailableError,
    SubprocessReplica,
    ThreadReplica,
    build_subprocess_fleet,
    build_thread_fleet,
)
from .kv_cache import BlockAllocator, PagedKVCache, blocks_needed
from .metrics import FleetMetrics, ServingMetrics, SLOTracker
from .router import FleetRouter, RouterRequest, ShedError
from .scheduler import (
    FINISH_EOS,
    FINISH_FAILED,
    FINISH_LENGTH,
    FINISH_RETRIED,
    FINISH_SHED,
    FINISH_TIMEOUT,
    Request,
    Scheduler,
)

__all__ = [
    "ServingConfig",
    "RouterConfig",
    "SLOConfig",
    "SLOTracker",
    "ServingEngine",
    "PipelineServingBridge",
    "EngineDrainingError",
    "make_decode_step",
    "pack_slots",
    "unpack_slots",
    "idle_slots",
    "derive_request_seed",
    "request_sample_key",
    "BlockAllocator",
    "PagedKVCache",
    "blocks_needed",
    "ServingMetrics",
    "FleetMetrics",
    "Scheduler",
    "Request",
    "FleetRouter",
    "RouterRequest",
    "ShedError",
    "ThreadReplica",
    "SubprocessReplica",
    "ReplicaUnavailableError",
    "build_thread_fleet",
    "build_subprocess_fleet",
    "FINISH_EOS",
    "FINISH_LENGTH",
    "FINISH_TIMEOUT",
    "FINISH_SHED",
    "FINISH_RETRIED",
    "FINISH_FAILED",
]
