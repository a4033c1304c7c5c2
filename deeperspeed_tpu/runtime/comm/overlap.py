"""Backward-overlap collective scheduling (T3-style, PAPERS.md
arXiv 2401.16677).

PR 6's imperative path serialized communication behind compute twice
over: ``reduce_dispatch`` called ``jax.block_until_ready`` on every
bucket, so bucket ``j+1`` could not even be *launched* until bucket
``j``'s collective had fully finished, and nothing else ran meanwhile.
The fused ``train_batch`` path had the opposite problem — one
whole-tree ``shard_map`` gave XLA a single fat reduction node whose
inputs are *all* gradients, pinning every collective after the complete
backward.

This module is the scheduling half of ISSUE 11's tentpole; the math
half (fused wire-format kernels) lives in ``ops/pallas/fused_quant``.
Enabled by ``"comm": {"overlap": "auto"|"on"}``:

* **imperative** (``backward()``/``step()``): ``reduce_dispatch`` runs
  in *async* mode — each bucket's jitted collective is launched and
  left in flight (JAX dispatch is asynchronous; the block was pure
  serialization), so bucket reductions overlap each other and the
  host-side work of the remaining microbatches. The
  :class:`OverlapScheduler` tracks the in-flight arrays and *drains*
  them at the accumulation boundary in ``step()`` under a
  ``comm/overlap_window`` span — the only comm time left exposed.
* **fused** (``train_batch``): ``reduce_stacked(per_bucket=True)``
  emits one ``shard_map`` per bucket instead of one for the whole
  tree. Each bucket's collective then depends only on its own leaves'
  gradients, so XLA's latency-hiding scheduler is free to start
  early-layer bucket reductions while late-layer backward compute is
  still running (the layer-order ``BucketPlan`` makes "early bucket"
  mean "gradients that materialize first"). Bit-identical to the
  whole-tree emission: the per-bucket math never crosses buckets.

Proof, not promise: ``comm/reduce`` spans carry ``overlapped:
true|false`` and the drain emits ``comm/overlap_window``;
:func:`overlap_fraction` turns a pair of (merged) traces into the
fraction of serialized comm time that the overlap schedule hid.
scripts/comm_bench.py reports it as ``overlap_fraction`` in the
report its ``--out`` names.
"""

from typing import Dict, List

import jax

from ...monitor import trace_span

__all__ = ["resolve_overlap", "OverlapScheduler", "reduce_span_stats",
           "overlap_fraction"]


def resolve_overlap(cfg, *, world: int, canonical: int = 0) -> bool:
    """Effective on/off decision for the ``overlap`` knob.

    ``auto`` declines where there is nothing to overlap: a world of one
    (no collectives) or the canonical-slot elastic mode (its reduction
    is a graph-fixed pairwise tree with no per-bucket collectives).
    ``on`` forces the scheduler even then — harmless, just a no-op
    drain per boundary.
    """
    if cfg.overlap == "off":
        return False
    if cfg.overlap == "on":
        return True
    return world > 1 and not canonical


class OverlapScheduler:
    """Tracks bucket reductions launched asynchronously during backward
    and drains them at the accumulation boundary.

    One instance per engine. ``note()`` is called by the engine after
    each async ``reduce_dispatch`` with whatever arrays are now in
    flight (reduced grads + new residual state); ``drain()`` blocks on
    all of them under a single ``comm/overlap_window`` span — the comm
    time the schedule failed to hide. Everything between the last
    ``note()`` and the ``drain()`` (remaining microbatch launches,
    banking, optimizer dispatch) runs while the collectives progress.
    """

    def __init__(self):
        self._pending: List = []
        self._buckets = 0

    @property
    def pending_buckets(self) -> int:
        return self._buckets

    def note(self, arrays, buckets: int) -> None:
        """Register in-flight device arrays from one async dispatch."""
        self._pending.append(arrays)
        self._buckets += int(buckets)

    def drain(self) -> None:
        """Block on everything in flight (accumulation boundary)."""
        if not self._pending:
            return
        pending, buckets = self._pending, self._buckets
        self._pending, self._buckets = [], 0
        with trace_span("comm/overlap_window", lane="comm",
                        buckets=buckets):
            jax.block_until_ready(pending)


# --------------------------------------------------------------------------
# trace analysis: prove the overlap from merged Chrome-trace events
# --------------------------------------------------------------------------


def _events(trace) -> List[dict]:
    if isinstance(trace, dict):
        trace = trace.get("traceEvents", [])
    return [e for e in trace if isinstance(e, dict)]


def reduce_span_stats(trace) -> Dict[str, float]:
    """Aggregate the comm spans of one trace (list of events or a
    ``{"traceEvents": ...}`` document; merged multi-process traces work
    the same — the names survive ``monitor.aggregate``).

    Returns ``reduce_ms`` (total ``comm/reduce`` duration),
    ``overlapped_spans`` / ``serial_spans`` (reduce spans by their
    ``overlapped`` arg) and ``window_ms`` (total ``comm/overlap_window``
    duration — the exposed comm time under overlap).
    """
    reduce_us = window_us = 0.0
    overlapped = serial = windows = 0
    for ev in _events(trace):
        if ev.get("ph") != "X":
            continue
        name = ev.get("name")
        dur = float(ev.get("dur", 0.0))
        if name == "comm/reduce":
            reduce_us += dur
            if (ev.get("args") or {}).get("overlapped"):
                overlapped += 1
            else:
                serial += 1
        elif name == "comm/overlap_window":
            window_us += dur
            windows += 1
    return {
        "reduce_ms": reduce_us / 1000.0,
        "window_ms": window_us / 1000.0,
        "overlapped_spans": overlapped,
        "serial_spans": serial,
        "windows": windows,
    }


def overlap_fraction(serial_trace, overlap_trace) -> float:
    """Fraction of serialized comm time the overlap schedule hid.

    ``serial_trace`` is a run with ``overlap: off`` — its
    ``comm/reduce`` spans wrap blocking waits, so their total is the
    comm time a serialized schedule exposes. ``overlap_trace`` is the
    same workload with overlap on — there the only exposed comm is the
    ``comm/overlap_window`` drains. ``1 - exposed/serialized``, clamped
    to [0, 1]; 0.0 when the serial trace carries no comm spans.
    """
    serial = reduce_span_stats(serial_trace)["reduce_ms"]
    if serial <= 0:
        return 0.0
    exposed = reduce_span_stats(overlap_trace)["window_ms"]
    return max(0.0, min(1.0, 1.0 - exposed / serial))
