"""Process-global selection state for the fused Pallas kernel layer.

The "kernels" config block (runtime/config.py) picks how the elementwise /
optimizer / short-sequence-attention residual is executed:

  off    — plain XLA everywhere (default; byte-identical to the pre-fusion
           graphs, the safe fallback).
  fused  — force the Pallas kernels on every supported call site. On the
           host CPU backend (JAX_PLATFORMS=cpu, the test suite) they run
           in interpret mode; on a TPU they are always the compiled
           Mosaic kernels unless `interpret` is set.
  auto   — Pallas on TPU when the per-surface geometry gates pass, XLA
           otherwise. This is the production setting.

Per-surface booleans (fused_blocks / fused_adam / supertile / fused_quant)
narrow a mode
to a subset of surfaces, e.g. {"mode": "auto", "fused_adam": False} keeps
the optimizer on XLA while fusing layernorm/gelu and attention.

The state is process-global (like the monitor tracer) because the consumers
are free functions deep inside model code — threading a config handle
through every layer_norm call would churn every model signature. Engines
configure it once at init from TrainingConfig; tests use `override()`.

Several devices: XLA cannot partition a Mosaic kernel ("Mosaic kernels
cannot be automatically partitioned"), so under a jit whose arrays are
sharded over a mesh every compiled Pallas call must sit inside a
``shard_map``. The kernels cannot see the mesh from their arguments, so
whoever traces them says which mesh it is tracing under with
:func:`mesh_scope` (the engines do, around the model and the optimizer;
``make_gpt(cfg, mesh=...)`` does for its attention). Flash attention wraps
itself in a ``shard_map`` over that mesh; the surfaces without a wrapper
(fused_blocks, fused_adam) are single-device kernels — ``auto`` leaves
them to XLA under a multi-device mesh and ``fused`` raises.
"""

import contextlib
import dataclasses
import threading

MODES = ("off", "fused", "auto")
SURFACES = ("fused_blocks", "fused_adam", "supertile", "fused_quant")
# compiled only where one device holds the whole array: supertile rides
# flash attention's shard_map and fused_quant runs inside the reducer's
_SINGLE_DEVICE_SURFACES = ("fused_blocks", "fused_adam")


@dataclasses.dataclass(frozen=True)
class KernelsConfig:
    mode: str = "off"
    interpret: bool = False   # force interpret-mode launches (debugging)
    fused_blocks: bool = True
    fused_adam: bool = True
    supertile: bool = True
    fused_quant: bool = True  # comm wire-format kernels (pallas/fused_quant)


_LOCK = threading.Lock()
_STATE = KernelsConfig()
_TRACING = threading.local()  # .mesh: the mesh the current trace runs under


_UNDECLARED = object()


@contextlib.contextmanager
def mesh_scope(mesh):
    """Declare the mesh the enclosed code is traced under (None: a single
    device, or the per-shard body of a ``shard_map``). Thread-local and
    nested — it lives exactly as long as the Python trace it wraps. A
    per-shard body stays per-shard all the way down: inside a ``None``
    scope a nested declaration is ignored (the engine names its mesh
    around every model call, including the ones it makes from inside its
    own ``shard_map``)."""
    prev = getattr(_TRACING, "mesh", _UNDECLARED)
    _TRACING.mesh = None if prev is None else mesh
    try:
        yield
    finally:
        if prev is _UNDECLARED:
            del _TRACING.mesh
        else:
            _TRACING.mesh = prev


def active_mesh():
    """The multi-device mesh the current trace runs under, else None."""
    mesh = getattr(_TRACING, "mesh", None)
    return mesh if mesh is not None and mesh.size > 1 else None


def get() -> KernelsConfig:
    return _STATE


def _check(kwargs):
    bad = set(kwargs) - {f.name for f in dataclasses.fields(KernelsConfig)}
    if bad:
        raise ValueError(f"unknown kernels config keys: {sorted(bad)}")
    mode = kwargs.get("mode")
    if mode is not None and mode not in MODES:
        raise ValueError(f"kernels mode must be one of {MODES}, got {mode!r}")
    for k in ("interpret",) + SURFACES:
        if k in kwargs and not isinstance(kwargs[k], bool):
            raise ValueError(f"kernels.{k} must be a bool, got {kwargs[k]!r}")


def validate(params) -> dict:
    """Check a "kernels" config-block dict WITHOUT touching global state
    (runtime/config.py parses eagerly; the engine applies at init)."""
    if not isinstance(params, dict):
        raise ValueError('"kernels" must be a dict of KernelsConfig fields')
    _check(params)
    return dict(params)


def configure(**kwargs) -> KernelsConfig:
    """Replace fields of the global kernels config; returns the new value."""
    global _STATE
    _check(kwargs)
    with _LOCK:
        _STATE = dataclasses.replace(_STATE, **kwargs)
        return _STATE


@contextlib.contextmanager
def override(**kwargs):
    """Temporarily swap the global config (tests, scoped experiments)."""
    global _STATE
    with _LOCK:
        prev = _STATE
    try:
        configure(**kwargs)
        yield _STATE
    finally:
        with _LOCK:
            _STATE = prev


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU. A backend that fails
    to initialise raises here — it is never read as "not a TPU", which
    would route a chip run to XLA or to interpret mode unnoticed."""
    import jax

    return jax.default_backend() == "tpu"


def resolve(surface: str):
    """(use_pallas, interpret) decision for one surface at trace time.

    `fused` forces the kernel; on the host CPU backend that means
    interpret mode (slow, but the graph under test is the real kernel).
    `auto` only fires on TPU, and only where the kernel can run: a
    single-device surface traced under a multi-device mesh (see
    :func:`mesh_scope`) stays on XLA. Geometry gates are the caller's job —
    this answers "does the config want Pallas here", not "does the shape
    fit".
    """
    st = _STATE
    if surface not in SURFACES:
        raise ValueError(f"unknown kernel surface {surface!r}")
    if st.mode == "off" or not getattr(st, surface):
        return False, False
    interpret = st.interpret or (st.mode == "fused" and not on_tpu())
    use = st.mode == "fused" or on_tpu()
    if (use and not interpret and surface in _SINGLE_DEVICE_SURFACES
            and active_mesh() is not None):
        if st.mode == "fused":
            raise NotImplementedError(
                f"kernels.{surface} is a single-device Mosaic kernel with "
                f"no shard_map wrapper; mesh {dict(active_mesh().shape)} "
                "has several devices. Use mode 'auto' (XLA takes this "
                "surface there) or turn the surface off.")
        return False, False
    return use, interpret
