"""Recompile watchdog: catch silent XLA retraces after warmup.

On TPU the dominant invisible failure mode is a jitted hot function
quietly recompiling — a shape or dtype leaked into the trace, a python
scalar that should have been a traced array, a config knob that varies
per call. Wall-clock timers show a mysterious multi-second step; this
watchdog names the function that did it.

Two signals:

  * Per-function jit cache sizes (``fn._cache_size()`` on jitted
    callables — the same counter ``ServingEngine.decode_compile_count``
    already exposes). ``watch(name, fn)`` registers a function;
    ``observe(name)`` is called by the owning engine after each hot-path
    invocation. The first observation that finds a non-empty cache marks
    the function WARM and records the baseline; any growth past the
    baseline afterwards fires the watchdog.
  * ``jax.monitoring`` duration events feed a process-global compile
    ACCOUNT by program name (``compile_account()``): JAX hands every
    listener the ``fun_name`` of what it traced (``ds_decode_step``),
    lowered and compiled or loaded from the persistent cache
    (``jit(ds_decode_step)``), so the account says which program cost
    what before and in the backend, and a lowering for a second shape
    shows as a second count. Each event is also an ``xla_compile``
    instant, so unwatched compiles show on the timeline. The engines'
    constructors install the listener whether or not a monitor is on: a
    dict update per compile, nothing per step.

Firing emits a trace instant (``recompile!``) plus a rank-0 warning; in
``strict`` mode it raises :class:`RecompileError` instead — the mode the
serving tests run under, proving the decode step compiles exactly once
across a multi-request run.
"""

import functools
import threading
import time
from typing import Callable, Dict, List, Optional

from ..utils.logging import logger
from .runctx import current as current_run
from .tracer import trace_instant

__all__ = ["RecompileError", "RecompileWatchdog", "compile_account",
           "install_compile_listener"]

MODES = ("off", "warn", "strict")

# the three phases JAX times for every program: tracing the function to
# a jaxpr, lowering the jaxpr to a module, and the backend's compile (a
# load from the persistent cache fires the last one too)
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
EAGER_ROW = "eager"

# process-global compile account fed by jax.monitoring (see
# install_compile_listener): program name -> phase -> [count, seconds]
_account: Dict[str, Dict[str, List[float]]] = {}
_last_compile_t: Optional[float] = None  # perf_counter of the newest one
_listener_installed = False
_listener_lock = threading.Lock()


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    global _last_compile_t
    phase = COMPILE_PHASES.get(event)
    if phase is None:
        return
    fun_name = str(kwargs.get("fun_name", "<unknown>"))
    # tracing names the bare function, lowering and compiling "jit(<it>)"
    name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    cell = _account.setdefault(name, {}).setdefault(phase, [0, 0.0])
    cell[0] += 1
    cell[1] += duration
    if phase == "compile":
        _last_compile_t = time.perf_counter()
    if phase != "trace":      # every traced sub-function fires "trace"
        trace_instant("xla_compile", lane="compile", fun_name=fun_name,
                      phase=phase, seconds=round(duration, 4))


@functools.lru_cache(maxsize=None)
def _one_primitive_names():
    """Names of the programs an eager one-primitive dispatch lowers
    (``jit(convert_element_type)``, ``jit(broadcast_in_dim)``)."""
    from jax.extend.core import primitives as prims

    return frozenset(
        getattr(prims, n).name for n in dir(prims) if n.endswith("_p"))


def compile_account() -> Dict[str, Dict[str, Dict[str, float]]]:
    """What this process traced, lowered and compiled since the listener
    was installed: ``{program: {phase: {"count", "seconds"}}}`` with the
    phases ``trace``, ``lower`` and ``compile`` (compiled, or loaded
    from the persistent cache). A program is named as ``jax.jit`` names
    it, without the ``jit(...)``: the engines' own all start ``ds_``.
    Every program of one primitive, which is what an eager operation on
    an array dispatches, is summed under the one row ``eager``; a row
    with no ``lower`` is a function traced inside another program."""
    eager = _one_primitive_names()
    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    for name, phases in list(_account.items()):
        row = out.setdefault(EAGER_ROW if name in eager else name, {})
        for phase, (count, seconds) in list(phases.items()):
            cell = row.setdefault(phase, {"count": 0, "seconds": 0.0})
            cell["count"] += count
            cell["seconds"] += seconds
    return out


def install_compile_listener() -> bool:
    """Register the jax.monitoring duration listener that keeps the
    compile account (once per process; jax offers no per-listener
    unregister so it stays installed). Returns True when the listener
    is active."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _on_duration_event)
        except Exception:  # pragma: no cover - very old jax
            return False
        _listener_installed = True
        return True


def _cache_size(fn) -> Optional[int]:
    get = getattr(fn, "_cache_size", None)
    if get is None:
        return None
    try:
        return int(get())
    except Exception:  # pragma: no cover - defensive
        return None


class RecompileError(RuntimeError):
    """Raised in strict mode when a watched function recompiles after
    warmup."""


class RecompileWatchdog:
    def __init__(self, mode: str = "warn"):
        if mode not in MODES:
            raise ValueError(f"watchdog mode must be one of {MODES}, "
                             f"got {mode!r}")
        self.mode = mode
        self._lock = threading.Lock()
        self._fns: Dict[str, Callable] = {}
        self._baseline: Dict[str, Optional[int]] = {}  # None until warm
        self.fired: List[dict] = []  # one record per detected recompile
        if mode != "off":
            install_compile_listener()

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    # -------------------------------------------------------------- #

    def watch(self, name: str, fn: Callable) -> None:
        """Register a jitted function under ``name`` (idempotent; re-
        registering a new fn object resets its warmup)."""
        with self._lock:
            if self._fns.get(name) is fn:
                return
            self._fns[name] = fn
            self._baseline[name] = None

    def watched(self) -> List[str]:
        with self._lock:
            return list(self._fns)

    def counts(self) -> Dict[str, Optional[int]]:
        """Current jit-cache entry count per watched function."""
        with self._lock:
            fns = dict(self._fns)
        return {name: _cache_size(fn) for name, fn in fns.items()}

    def mark_warm(self, name: Optional[str] = None) -> None:
        """Snapshot current cache sizes as the post-warmup baseline
        (``observe`` does this automatically on the first non-empty
        sighting; call this to warm explicitly, e.g. after a warmup
        batch)."""
        with self._lock:
            names = [name] if name is not None else list(self._fns)
            for n in names:
                self._baseline[n] = _cache_size(self._fns[n])

    def observe(self, name: Optional[str] = None,
                step: Optional[int] = None) -> List[str]:
        """Compare watched functions' cache sizes against their warm
        baselines; returns the names that recompiled (after firing the
        configured reaction for each). ``step`` is the caller's step
        counter, carried into the warning/instant so a firing is
        attributable to a specific point in the run."""
        if not self.enabled:
            return []
        with self._lock:
            items = ([(name, self._fns[name])] if name is not None
                     else list(self._fns.items()))
        recompiled = []
        for n, fn in items:
            size = _cache_size(fn)
            if size is None:
                continue
            base = self._baseline.get(n)
            if base is None:
                if size > 0:  # first compile = warmup, not a violation
                    with self._lock:
                        self._baseline[n] = size
                continue
            if size > base:
                with self._lock:
                    self._baseline[n] = size  # report each growth once
                recompiled.append(n)
                self._fire(n, base, size, step=step)
        return recompiled

    # -------------------------------------------------------------- #

    def _fire(self, name: str, baseline: int, size: int,
              step: Optional[int] = None) -> None:
        rc = current_run()
        since = (time.perf_counter() - _last_compile_t
                 if _last_compile_t is not None else None)
        record = {"name": name, "baseline": baseline, "cache_size": size,
                  "step": step, "run_id": rc.run_id,
                  "since_last_compile_s": since}
        self.fired.append(record)
        args = {"fn": name, "cache_size": size,
                "run_id": rc.run_id or "", "role": rc.role,
                "incarnation": rc.incarnation}
        if step is not None:
            args["step"] = step
        if since is not None:
            args["since_last_compile_s"] = round(since, 3)
        trace_instant("recompile!", lane="compile", **args)
        ctx = f" [run {rc.run_id}]" if rc.run_id else ""
        if step is not None:
            ctx += f" at step {step}"
        if since is not None:
            ctx += f", {since:.1f}s since the last backend compile"
        msg = (f"recompile watchdog: {name!r} recompiled after warmup "
               f"(jit cache {baseline} -> {size}){ctx}; a shape/dtype is "
               f"leaking into the trace")
        if self.mode == "strict":
            raise RecompileError(msg)
        try:
            import jax
            rank0 = jax.process_index() == 0
        except Exception:  # pragma: no cover
            rank0 = True
        if rank0:
            logger.warning(msg)
