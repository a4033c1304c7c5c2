"""Compiled-cost observability: where do the FLOPs and bytes go?

XLA already knows what every jitted entry point costs — the compiled
executable carries a cost model (``compiled.cost_analysis()``: flops,
bytes accessed, optimal seconds) and a memory breakdown
(``compiled.memory_analysis()``: argument / output / peak-temp bytes).
Until now that knowledge lived only in the offline flops profiler;
this module makes it a live layer:

  * :func:`extract_cost_analysis` / :func:`extract_memory_analysis` —
    the ONE place the raw XLA structures are normalized (the CPU
    backend variously returns ``None``, a list of dicts, or a partial
    dict; the flops profiler shares these helpers instead of a second
    call-site);
  * :class:`CompiledCostIndex` — captures the cost/memory analysis of
    every registered jitted entry point (engine fused/imperative train
    step, serving prefill/decode, comm per-bucket reducers) by AOT
    re-lowering against the *abstract* shapes of the real call (so
    donated/deleted buffers are fine and the jit's own cache is never
    touched), stamps one ``perf/compiled`` instant + Prometheus gauges
    per capture, writes the table into the trace's process metadata,
    and answers the live questions: per-step MFU from measured flops
    over span wall time, and a roofline verdict (compute- / memory- /
    comm-bound) against a small platform peak table.

Capture keys off the same jit-cache counter the recompile watchdog
reads: ``observe(name, fn, args)`` is O(one int compare) while the
function stays warm and only re-captures when the cache grew (i.e. the
watchdog would have fired anyway).

The peak table is keyed by ``device_kind``: ``peak_tflops`` per device
generation plus nominal HBM bandwidth for the roofline ridge. An
accelerator that is not in the table is an error, never a default. The
``cpu`` row is deliberately nominal (0.5 TF) so the plumbing is testable
on the host; a number priced against it is not a device metric.
"""

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

from ..utils.logging import logger
from .tracer import get_tracer, trace_instant

__all__ = [
    "PLATFORM_PEAKS",
    "CompiledCostIndex",
    "CostRecord",
    "extract_cost_analysis",
    "extract_memory_analysis",
    "platform_peaks",
]

# ------------------------------------------------------------------ #
# platform peak table (public spec sheets)
# ------------------------------------------------------------------ #

# peak_tflops: bf16 matmul peak per chip (the basis of every MFU
# number); peak_gbps: nominal HBM bandwidth, the other roofline axis;
# hbm_gib: per-chip capacity (the autotuner's feasibility axis);
# ici_gbps: nominal per-chip interconnect bandwidth (the wire-model
# denominator). Keys are matched as substrings of the lowercased
# device_kind ("TPU v5 lite" -> "v5 lite").
PLATFORM_PEAKS: Dict[str, Dict[str, float]] = {
    "v4": {"peak_tflops": 275.0, "peak_gbps": 1228.0,
           "hbm_gib": 32.0, "ici_gbps": 300.0},
    "v5p": {"peak_tflops": 459.0, "peak_gbps": 2765.0,
            "hbm_gib": 95.0, "ici_gbps": 600.0},
    "v5e": {"peak_tflops": 197.0, "peak_gbps": 819.0,
            "hbm_gib": 16.0, "ici_gbps": 160.0},
    "v5 lite": {"peak_tflops": 197.0, "peak_gbps": 819.0,
                "hbm_gib": 16.0, "ici_gbps": 160.0},
    "v6e": {"peak_tflops": 918.0, "peak_gbps": 1640.0,
            "hbm_gib": 32.0, "ici_gbps": 360.0},
    "v6 lite": {"peak_tflops": 918.0, "peak_gbps": 1640.0,
                "hbm_gib": 32.0, "ici_gbps": 360.0},
    # nominal: keeps CPU MFU numbers finite and the plumbing testable
    # (1 GiB "HBM" puts the serving pool frontier within CPU-test reach)
    "cpu": {"peak_tflops": 0.5, "peak_gbps": 50.0,
            "hbm_gib": 1.0, "ici_gbps": 10.0},
}


def platform_peaks(device=None) -> Dict[str, float]:
    """Peak table row for ``device`` (default: first local device),
    matched by ``device_kind``. The host CPU gets the nominal ``cpu``
    row; any other device that is not in the table raises."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    if device.platform == "cpu":
        return dict(PLATFORM_PEAKS["cpu"], source="cpu")
    kind = device.device_kind.lower()
    for key, row in PLATFORM_PEAKS.items():
        if key != "cpu" and key in kind:
            return dict(row, source=key)
    raise ValueError(
        f"no peak-table row for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to "
        "monitor.perf.PLATFORM_PEAKS with its source")


# ------------------------------------------------------------------ #
# raw-structure normalization (shared with profiling/flops_profiler)
# ------------------------------------------------------------------ #


def extract_cost_analysis(compiled) -> Dict[str, float]:
    """Normalize ``compiled.cost_analysis()`` into a flat dict.

    Handles every shape the backends produce: ``None`` (CPU builds
    without a cost model), a list of per-computation dicts (older
    jaxlib), a single dict, and partial dicts missing keys. Returned
    keys (always present, 0.0 when the backend stayed silent):
    ``flops``, ``bytes_accessed``, ``optimal_seconds``."""
    out = {"flops": 0.0, "bytes_accessed": 0.0, "optimal_seconds": 0.0}
    try:
        ca = compiled.cost_analysis()
    except Exception:  # pragma: no cover - backend refuses entirely
        return out
    if ca is None:
        return out
    if isinstance(ca, (list, tuple)):
        ca = next((c for c in ca if isinstance(c, dict)), None)
        if ca is None:
            return out
    if not isinstance(ca, dict):
        return out

    def _num(key):
        v = ca.get(key)
        try:
            v = float(v)
        except (TypeError, ValueError):
            return 0.0
        return v if v > 0 else 0.0

    out["flops"] = _num("flops")
    out["bytes_accessed"] = _num("bytes accessed")
    out["optimal_seconds"] = _num("optimal_seconds")
    return out


def extract_memory_analysis(compiled) -> Dict[str, float]:
    """Normalize ``compiled.memory_analysis()`` into a flat dict; empty
    when the backend exposes nothing. Keys (when present):
    ``argument_bytes``, ``output_bytes``, ``temp_bytes``,
    ``alias_bytes``, ``code_bytes``, and ``peak_bytes`` (arguments +
    outputs + temporaries − aliased: the executable's HBM footprint
    while it runs — the number the sharding refactor needs per entry
    point before it moves anything)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # pragma: no cover - backend refuses entirely
        return {}
    if ma is None:
        return {}
    fields = {
        "argument_bytes": "argument_size_in_bytes",
        "output_bytes": "output_size_in_bytes",
        "temp_bytes": "temp_size_in_bytes",
        "alias_bytes": "alias_size_in_bytes",
        "code_bytes": "generated_code_size_in_bytes",
    }
    out: Dict[str, float] = {}
    for key, attr in fields.items():
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    if out:
        out["peak_bytes"] = (out.get("argument_bytes", 0.0)
                             + out.get("output_bytes", 0.0)
                             + out.get("temp_bytes", 0.0)
                             - out.get("alias_bytes", 0.0))
    return out


def _abstractify(args: Tuple, kwargs: Optional[dict]):
    """Replace every jax.Array leaf with a ShapeDtypeStruct so the AOT
    re-lower never touches device buffers (donated/deleted inputs from
    the real call still carry their aval)."""
    import jax

    def one(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return (jax.tree.map(one, args),
            jax.tree.map(one, kwargs if kwargs is not None else {}))


def _cache_size(fn) -> Optional[int]:
    get = getattr(fn, "_cache_size", None)
    if get is None:
        return None
    try:
        return int(get())
    except Exception:  # pragma: no cover - defensive
        return None


# ------------------------------------------------------------------ #
# the index
# ------------------------------------------------------------------ #


@dataclasses.dataclass
class CostRecord:
    """One captured entry point. ``flops``/``bytes_accessed`` are whole-
    program (all participating devices); ``peak_bytes`` is the
    executable's device-memory footprint estimate."""

    name: str
    flops: float = 0.0
    bytes_accessed: float = 0.0
    optimal_seconds: float = 0.0
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    peak_bytes: float = 0.0
    cache_size: Optional[int] = None
    captures: int = 0
    error: Optional[str] = None

    def as_args(self) -> Dict[str, float]:
        return {
            "entry": self.name,
            "flops": self.flops,
            "bytes": self.bytes_accessed,
            "peak_hbm": self.peak_bytes,
            "optimal_s": self.optimal_seconds,
        }


class CompiledCostIndex:
    """Live table of what every jitted entry point costs.

    ``observe(name, fn, args)`` sits next to the recompile watchdog's
    ``watch``/``observe`` call sites: cheap while the function stays
    warm, re-captures (AOT lower + compile against abstract avals) when
    the jit cache grew. Every capture emits a ``perf/compiled`` instant,
    refreshes the ``perf_flops`` / ``perf_bytes_accessed`` /
    ``perf_peak_hbm_bytes`` gauges (labeled by entry), and stamps the
    whole table into the tracer's process metadata so a saved trace
    carries its own cost model."""

    def __init__(self, registry=None, peaks: Optional[Dict] = None,
                 emit: bool = True):
        self._lock = threading.Lock()
        self._records: Dict[str, CostRecord] = {}
        self._registry = registry
        self._peaks = peaks  # lazily resolved: jax may not be up yet
        self._devices: Optional[int] = None
        # emit=False sandboxes the index (autotune candidate sweeps):
        # no trace instants, no gauge refresh, no tracer-metadata stamp
        # — speculative captures must not pollute the live monitor
        self._emit = bool(emit)

    # -- platform ---------------------------------------------------- #

    @property
    def peaks(self) -> Dict[str, float]:
        if self._peaks is None:
            self._peaks = platform_peaks()
        return self._peaks

    @property
    def local_devices(self) -> int:
        if self._devices is None:
            try:
                import jax
                self._devices = max(1, jax.local_device_count())
            except Exception:  # pragma: no cover
                self._devices = 1
        return self._devices

    # -- capture ----------------------------------------------------- #

    def observe(self, name: str, fn: Callable, args: Tuple = (),
                kwargs: Optional[dict] = None) -> Optional[CostRecord]:
        """Record ``fn``'s compiled cost under ``name`` if it has not
        been captured yet (or recompiled since). Never raises: a backend
        that refuses to lower leaves a stub record with ``error`` set."""
        size = _cache_size(fn)
        with self._lock:
            rec = self._records.get(name)
        if rec is not None and rec.error is None and rec.cache_size == size:
            return rec
        return self._capture(name, fn, args, kwargs, size)

    def _capture(self, name, fn, args, kwargs, size) -> Optional[CostRecord]:
        rec = CostRecord(name=name, cache_size=size)
        try:
            a_args, a_kwargs = _abstractify(args, kwargs)
            lowered = fn.lower(*a_args, **a_kwargs)
            compiled = lowered.compile()
            rec_dict = extract_cost_analysis(compiled)
            mem = extract_memory_analysis(compiled)
            rec.flops = rec_dict["flops"]
            rec.bytes_accessed = rec_dict["bytes_accessed"]
            rec.optimal_seconds = rec_dict["optimal_seconds"]
            rec.argument_bytes = mem.get("argument_bytes", 0.0)
            rec.output_bytes = mem.get("output_bytes", 0.0)
            rec.temp_bytes = mem.get("temp_bytes", 0.0)
            rec.peak_bytes = mem.get("peak_bytes", 0.0)
        except Exception as e:  # noqa: BLE001 — observability must not kill
            rec.error = f"{type(e).__name__}: {e}"
            logger.debug("perf: cost capture for %r failed: %s", name,
                         rec.error)
        with self._lock:
            prev = self._records.get(name)
            rec.captures = (prev.captures if prev else 0) + 1
            self._records[name] = rec
        if rec.error is None and self._emit:
            trace_instant("perf/compiled", lane="perf", **rec.as_args())
            self._export_gauges(rec)
        if self._emit:
            self._stamp_metadata()
        return rec

    def _export_gauges(self, rec: CostRecord) -> None:
        if self._registry is None:
            return
        lab = {"entry": rec.name}
        self._registry.gauge(
            "perf_flops", "compiled cost model: flops per execution",
            labels=lab).set(rec.flops)
        self._registry.gauge(
            "perf_bytes_accessed", "compiled cost model: bytes accessed "
            "per execution", labels=lab).set(rec.bytes_accessed)
        self._registry.gauge(
            "perf_peak_hbm_bytes", "compiled executable memory footprint "
            "(args+outputs+temps-aliased)", labels=lab).set(rec.peak_bytes)

    def _stamp_metadata(self) -> None:
        t = get_tracer()
        if t is None or not hasattr(t, "set_metadata"):
            return
        t.set_metadata("perf", self.summary())

    # -- queries ------------------------------------------------------ #

    def get(self, name: str) -> Optional[CostRecord]:
        with self._lock:
            return self._records.get(name)

    def records(self) -> Dict[str, CostRecord]:
        with self._lock:
            return dict(self._records)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """JSON-ready table (what the trace metadata / benches carry)."""
        with self._lock:
            recs = list(self._records.values())
        return {
            r.name: {
                "flops": r.flops,
                "bytes_accessed": r.bytes_accessed,
                "peak_hbm_bytes": r.peak_bytes,
                "optimal_seconds": r.optimal_seconds,
                "captures": r.captures,
                **({"error": r.error} if r.error else {}),
            }
            for r in recs
        }

    # -- live MFU / roofline ------------------------------------------ #

    def step_stats(self, name: str, wall_s: float,
                   comm_factor: float = 3.0) -> Optional[Dict[str, Any]]:
        """Measured-step verdict for entry ``name`` over ``wall_s``.

        MFU = measured flops / wall / (peak_tflops × local devices),
        with the compiled cost model supplying the flops. The roofline verdict compares the two
        floor estimates (flops/peak_flops vs bytes/peak_bw): the larger
        names the bound; a measured wall ``comm_factor``× past BOTH
        floors means the time went somewhere the single-program roofline
        cannot see — collectives on a multi-device mesh ("comm-bound"),
        host/dispatch overhead on one device ("host-bound")."""
        rec = self.get(name)
        if rec is None or rec.error is not None or wall_s <= 0:
            return None
        peaks = self.peaks
        ndev = self.local_devices
        peak_flops = peaks["peak_tflops"] * 1e12 * ndev
        peak_bw = peaks["peak_gbps"] * 1e9 * ndev
        tflops = rec.flops / wall_s / 1e12
        mfu = rec.flops / wall_s / peak_flops if peak_flops else 0.0
        est_compute = rec.flops / peak_flops if peak_flops else 0.0
        est_memory = rec.bytes_accessed / peak_bw if peak_bw else 0.0
        floor = max(est_compute, est_memory)
        if floor > 0 and wall_s > comm_factor * floor:
            verdict = "comm-bound" if ndev > 1 else "host-bound"
        elif est_compute >= est_memory:
            verdict = "compute-bound"
        else:
            verdict = "memory-bound"
        stats = {
            "entry": name,
            "wall_ms": wall_s * 1e3,
            "mfu": mfu,
            "tflops": tflops,
            "verdict": verdict,
            "est_compute_ms": est_compute * 1e3,
            "est_memory_ms": est_memory * 1e3,
        }
        if self._registry is not None:
            lab = {"entry": name}
            self._registry.gauge(
                "perf_mfu", "measured model-flops utilization per step",
                labels=lab).set(mfu)
            self._registry.gauge(
                "perf_step_tflops", "measured tflops per step",
                labels=lab).set(tflops)
        return stats

    def note_step(self, name: str, wall_s: float) -> Optional[Dict[str, Any]]:
        """step_stats + a ``perf/step`` trace instant (the live per-step
        MFU lane)."""
        stats = self.step_stats(name, wall_s)
        if stats is not None:
            trace_instant(
                "perf/step", lane="perf", entry=name,
                mfu=round(stats["mfu"], 6),
                wall_ms=round(stats["wall_ms"], 3),
                tflops=round(stats["tflops"], 4),
                verdict=stats["verdict"])
        return stats
