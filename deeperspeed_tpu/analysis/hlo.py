"""Level-1 compiled-program auditor.

Audits jitted entry points the same donation-safe way the perf doctor
captures cost analysis (monitor/perf.py): AOT ``fn.lower(...)`` over
``ShapeDtypeStruct``s — the jit cache is never touched, so auditing a
live engine cannot trip the recompile watchdog.

Checks per program:

``donation-dropped`` / ``donation-partial``
    ``donate_argnums`` declared but the compiled executable has no (or
    fewer) input-output aliases than donated input leaves. A dropped
    donation silently doubles HBM for the donated tree; XLA does NOT
    warn on CPU, so the only reliable detection is exactly this diff
    between ``lowered.args_info`` (declared) and the compiled HLO's
    ``input_output_alias`` table (honored).
``fp64-in-program``
    a float64/complex128 value anywhere in the step jaxpr — on TPU
    this is an emulation cliff, and in this codebase always a leaked
    python float via x64 mode.
``weak-promotion``
    an elementwise op whose output is a wider float than one of its
    array inputs — an accidental upcast (bf16 tensor silently computed
    in f32). Explicit ``convert_element_type`` (master-weight casts)
    is intentionally out of scope.
``collective-axis`` / ``collective-axis-unknown``
    every collective's named axis must exist in the mesh the program
    runs under, and belong to the axis vocabulary of the
    ``sharding/rules.py`` table (canonical dp/fsdp/tp/sp + the legacy
    aliases ``translate_spec`` accepts).
``zero3-allgather-leak``
    under ZeRO-3 no single all-gather result may approach the full
    parameter footprint — a gather whose result is larger than any
    parameter leaf by a wide margin means sharding leaked and the
    "partitioned" params are materialized whole.
``host-callback``
    callback primitives (``jax.debug.print``, ``pure_callback``, ...)
    inside a hot entry point: a host round-trip per step.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding

try:  # jaxpr node types moved around across jax versions
    from jax._src.core import ClosedJaxpr, Jaxpr
except ImportError:  # pragma: no cover
    from jax.core import ClosedJaxpr, Jaxpr  # type: ignore


@dataclasses.dataclass
class ProgramSpec:
    """One jitted entry point to audit.

    ``fn`` must be a jitted callable (supports ``.lower``); ``args`` /
    ``kwargs`` may be real arrays or ShapeDtypeStructs — they are
    abstractified before lowering either way.
    """

    name: str
    fn: Any
    args: Tuple = ()
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    mesh: Any = None             # jax.sharding.Mesh the program runs under
    zero_stage: int = 0
    hot: bool = True             # per-step entry point?
    param_bytes_total: int = 0   # for the ZeRO-3 gather-leak bound
    param_bytes_largest: int = 0


# ---------------------------------------------------------------------------
# jaxpr walking

_COLLECTIVE_AXIS_PARAMS = ("axis_name", "axes")
# jax.debug.print lowers to a primitive named "debug_print" (jax >= 0.5)
_CALLBACK_MARKERS = ("callback", "outside_call", "host_call", "debug_print")
_PROMOTION_PRIMS = {"add", "sub", "mul", "div", "max", "min"}


def _sub_jaxprs(value):
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr):
    """Depth-first over every equation, descending through pjit/scan/
    while/cond/shard_map/custom_* sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _aval(v):
    return getattr(v, "aval", None)


def collect_collectives(closed) -> List[Tuple[str, Tuple[str, ...]]]:
    """[(primitive_name, (axis, ...))] for every collective in the jaxpr."""
    out = []
    for eqn in iter_eqns(closed.jaxpr):
        axes: List[str] = []
        for key in _COLLECTIVE_AXIS_PARAMS:
            if key in eqn.params:
                val = eqn.params[key]
                vals = val if isinstance(val, (tuple, list)) else (val,)
                axes.extend(a for a in vals if isinstance(a, str))
        if axes:
            out.append((eqn.primitive.name, tuple(axes)))
    return out


def known_rule_axes() -> Set[str]:
    """Axis vocabulary of the sharding rules table: the canonical mesh
    axes plus every legacy alias translate_spec understands."""
    axes: Set[str] = set()
    try:
        from ..sharding import mesh as _m
        axes |= {_m.DP_AXIS, _m.FSDP_AXIS, _m.TP_AXIS, _m.SP_AXIS}
    except Exception:  # pragma: no cover
        axes |= {"dp", "fsdp", "tp", "sp"}
    try:
        from ..sharding import rules as _r
        for spec in getattr(_r, "DEFAULT_RULES", {}).values():
            parts = spec if isinstance(spec, (tuple, list)) else (spec,)
            for part in parts:
                sub = part if isinstance(part, (tuple, list)) else (part,)
                axes |= {a for a in sub if isinstance(a, str)}
        axes |= {a for a in getattr(_r, "LEGACY_AXES", ()) or ()}
    except Exception:  # pragma: no cover
        pass
    # legacy generation (parallel/topology.py constants)
    try:
        from ..parallel import topology as _t
        for const in ("DATA_AXIS", "PIPE_AXIS", "MODEL_AXIS", "SEQ_AXIS",
                      "EXPERT_AXIS"):
            v = getattr(_t, const, None)
            if isinstance(v, str):
                axes.add(v)
    except Exception:  # pragma: no cover
        axes |= {"data", "pipe", "model", "seq", "expert"}
    return axes


# ---------------------------------------------------------------------------
# HLO text parsing

_HLO_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = _HLO_BYTES.get(dtype, 4)
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n


def count_alias_pairs(hlo_text: str) -> int:
    """Number of honored input→output aliases in a compiled HLO module
    header (``input_output_alias={ {0}: (0, {}, may-alias), ... }``).
    Brace-matched by hand — the table nests braces."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return 0
    i = hlo_text.index("{", start)
    depth, j = 0, i
    while j < len(hlo_text):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    table = hlo_text[i:j + 1]
    return table.count("-alias")


def all_gather_result_bytes(hlo_text: str) -> List[int]:
    """Result size (bytes) of every all-gather in the HLO text."""
    out = []
    for line in hlo_text.splitlines():
        if "all-gather(" not in line and "all-gather-start(" not in line:
            continue
        lhs = line.split("all-gather", 1)[0]
        shapes = _SHAPE_RE.findall(lhs)
        if shapes:
            # tuple results (all-gather-start) list operand+result
            # shapes; the result is the largest
            out.append(max(_shape_bytes(d, dims) for d, dims in shapes))
    return out


# ---------------------------------------------------------------------------
# the audit


def _abstractify(args, kwargs):
    """Like monitor/perf.py's donation-safe abstractify, but KEEPING
    each array's sharding: the audit must see the SPMD program (its
    collectives and gathers), not a single-device re-lowering."""
    import jax

    def one(x):
        if isinstance(x, jax.Array):
            # only pin COMMITTED placements: a ShapeDtypeStruct sharding
            # is always treated as committed, so carrying over the
            # default single-device placement of an uncommitted scalar
            # (e.g. a step counter) fails lowering against mesh-wide
            # params that jit would happily have co-located at runtime
            if getattr(x, "committed", False):
                try:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                sharding=x.sharding)
                except Exception:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return (jax.tree.map(one, args),
            jax.tree.map(one, kwargs if kwargs is not None else {}))


def _donated_leaves(lowered) -> int:
    import jax
    n = 0
    for leaf in jax.tree_util.tree_leaves(lowered.args_info):
        if getattr(leaf, "donated", False):
            n += 1
    return n


def audit_program(spec: ProgramSpec) -> List[Finding]:
    """Run every compiled-program check against one entry point."""
    import jax

    findings: List[Finding] = []

    def add(rule, severity, message, **detail):
        findings.append(Finding(rule=rule, severity=severity, path=spec.name,
                                line=0, message=message,
                                detail=detail or None))

    a_args, a_kwargs = _abstractify(spec.args, spec.kwargs)
    try:
        lowered = spec.fn.lower(*a_args, **a_kwargs)
        compiled = lowered.compile()
    except Exception as e:  # lowering itself failed — that IS a finding
        add("lowering-failed", "error",
            f"entry point failed to lower/compile: {type(e).__name__}: {e}")
        return findings

    # ---- donation: declared vs honored ------------------------------
    donated = _donated_leaves(lowered)
    hlo_text = ""
    try:
        hlo_text = compiled.as_text()
    except Exception:  # pragma: no cover - backend without text dump
        pass
    if donated and hlo_text:
        pairs = count_alias_pairs(hlo_text)
        if pairs == 0:
            add("donation-dropped", "error",
                f"{donated} input leaf/leaves declared donated but the "
                "compiled executable has NO input-output aliases — the "
                "donation was silently dropped (double HBM for the "
                "donated tree)",
                donated_leaves=donated, alias_pairs=0)
        elif pairs < donated:
            add("donation-partial", "warning",
                f"only {pairs}/{donated} donated input leaves alias an "
                "output in the compiled executable — the rest are "
                "retained alongside their replacements",
                donated_leaves=donated, alias_pairs=pairs)

    # ---- jaxpr-level checks -----------------------------------------
    try:
        closed = jax.make_jaxpr(spec.fn)(*a_args, **a_kwargs)
    except Exception as e:
        add("lowering-failed", "error",
            f"make_jaxpr failed: {type(e).__name__}: {e}")
        return findings

    import numpy as np

    import jax.numpy as jnp

    def _is_float(dt):
        # jnp.issubdtype, not np: bf16/fp8 are ml_dtypes extension
        # types that numpy does not place under np.floating
        try:
            return bool(jnp.issubdtype(dt, jnp.floating))
        except Exception:
            return False

    seen_f64 = set()
    seen_promo = set()
    # jnp dtype promotion inserts a convert_element_type BEFORE the
    # arithmetic op, so the op itself sees uniform dtypes — the implicit
    # upcast is only visible as a widening float convert whose result
    # feeds arithmetic. Track those converts by their output var.
    widened: Dict[Any, Tuple[str, str]] = {}
    for eqn in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim == "convert_element_type" and eqn.invars and eqn.outvars:
            av_in = _aval(eqn.invars[0])
            av_out = _aval(eqn.outvars[0])
            dt_in = getattr(av_in, "dtype", None)
            dt_out = getattr(av_out, "dtype", None)
            if (dt_in is not None and dt_out is not None
                    and _is_float(dt_in)
                    and _is_float(dt_out)
                    and getattr(av_in, "ndim", 0) > 0
                    and np.dtype(dt_in).itemsize
                    < np.dtype(dt_out).itemsize):
                try:
                    widened[eqn.outvars[0]] = (np.dtype(dt_in).name,
                                               np.dtype(dt_out).name)
                except TypeError:
                    pass
        # fp64 / complex128 anywhere
        for v in list(eqn.invars) + list(eqn.outvars):
            av = _aval(v)
            dt = getattr(av, "dtype", None)
            if dt is not None and dt in (np.float64, np.complex128):
                if prim not in seen_f64:
                    seen_f64.add(prim)
                    add("fp64-in-program", "error",
                        f"{np.dtype(dt).name} value flows through "
                        f"`{prim}` — double precision leaked into the "
                        "step program (x64 promotion)",
                        primitive=prim, dtype=np.dtype(dt).name)
        # implicit widening in elementwise arithmetic
        if prim in _PROMOTION_PRIMS:
            out_av = _aval(eqn.outvars[0])
            out_dt = getattr(out_av, "dtype", None)
            if out_dt is not None and _is_float(out_dt):
                for v in eqn.invars:
                    try:
                        conv = widened.get(v)
                    except TypeError:
                        conv = None
                    av = _aval(v)
                    dt = getattr(av, "dtype", None)
                    direct = (dt is not None
                              and _is_float(dt)
                              and getattr(av, "ndim", 0) > 0
                              and np.dtype(dt).itemsize
                              < np.dtype(out_dt).itemsize)
                    if conv is None and not direct:
                        continue
                    narrow = conv[0] if conv else np.dtype(dt).name
                    key = (prim, narrow, np.dtype(out_dt).name)
                    if key not in seen_promo:
                        seen_promo.add(key)
                        add("weak-promotion", "warning",
                            f"`{prim}` widens a {narrow} array to "
                            f"{np.dtype(out_dt).name} — implicit "
                            "promotion; cast explicitly if intended",
                            primitive=prim, narrow=narrow,
                            wide=np.dtype(out_dt).name)
        # host callbacks in hot paths
        if any(m in prim for m in _CALLBACK_MARKERS):
            add("host-callback", "error" if spec.hot else "info",
                f"host callback primitive `{prim}` inside "
                + ("hot entry point — a host round-trip every step"
                   if spec.hot else "entry point"),
                primitive=prim)

    # ---- collective axes vs mesh + rules table ----------------------
    mesh_axes = set(getattr(spec.mesh, "axis_names", ()) or ())
    vocab = known_rule_axes()
    for prim, axes in collect_collectives(closed):
        for ax in axes:
            if mesh_axes and ax not in mesh_axes:
                add("collective-axis", "error",
                    f"collective `{prim}` reduces over axis {ax!r} which "
                    f"does not exist in the program's mesh "
                    f"{sorted(mesh_axes)}",
                    primitive=prim, axis=ax, mesh_axes=sorted(mesh_axes))
            elif ax not in vocab:
                add("collective-axis-unknown", "warning",
                    f"collective `{prim}` uses axis {ax!r} that is outside "
                    "the sharding/rules.py axis vocabulary "
                    f"{sorted(vocab)}",
                    primitive=prim, axis=ax)

    # ---- ZeRO-3 full-param gather leak ------------------------------
    if spec.zero_stage >= 3 and spec.param_bytes_total > 0 and hlo_text:
        bound = max(1.5 * spec.param_bytes_largest,
                    0.6 * spec.param_bytes_total)
        for nbytes in all_gather_result_bytes(hlo_text):
            if nbytes > bound:
                add("zero3-allgather-leak", "error",
                    f"all-gather materializes {nbytes} bytes under ZeRO-3 "
                    f"(largest param leaf {spec.param_bytes_largest}, "
                    f"total {spec.param_bytes_total}) — the partitioned "
                    "parameters are being gathered whole",
                    gather_bytes=nbytes,
                    bound_bytes=int(bound))
                break  # one finding per program is enough signal

    return findings


def audit_programs(specs: Sequence[ProgramSpec]) -> List[Finding]:
    out: List[Finding] = []
    for spec in specs:
        out.extend(audit_program(spec))
    return out
