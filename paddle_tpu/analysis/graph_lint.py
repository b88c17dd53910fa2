"""Graph Lint: jaxpr-level static analysis of traced programs.

The repo traces whole train steps into single XLA programs (`jit/api.py`),
caches per-op jitted programs (`core/op_cache.py`) and runs a retrace-free
decode engine (`models/generation.py`) — this module inspects the programs
we actually emit, so silent dtype promotions, undonated multi-GB buffers,
tile-misaligned dims and accidental host syncs surface as findings with
stable codes instead of mysterious HBM/bench regressions.

Passes over a ``ClosedJaxpr`` (recursing into sub-jaxprs: pjit bodies,
scan/while/cond branches, custom_vjp calls):

- **GL001 dtype-promotion**: a bf16/fp16 value upcast to fp32 that feeds a
  ``dot_general``/conv (the matmul leaves the bf16 MXU path and doubles its
  operand bytes — silent because jax promotes mixed-dtype dots without
  warning); plus any f64/c128 leak (x64 mode has no TPU fast path).
- **GL002 tile-misalignment**: dot/reduce operands with trailing dims
  beyond one (8, 128) tile but not tile-multiples — partial-tile padding
  waste.  Same rules the Pallas kernel eligibility gates apply
  (``analysis/codes.py``).
- **GL003 host-sync**: callback-class primitives inside a traced program
  (io/pure callbacks synchronize with the host per step; debug callbacks
  are async but still ship device->host traffic).
- **GL004 donation-miss**: large inputs that are consumed (dead after the
  program) and shape/dtype-match an output yet are not donated — XLA must
  double-buffer them (the KV cache / optimizer-state hazard).
- **GL005 dead-code**: equations whose results are never consumed (traced
  work + trace time for nothing; XLA DCEs them, but they signal a bug —
  an output the caller meant to return, a mutation that never landed).
- **GL006 intermediate-blowup**: broadcast/concat/pad/gather results that
  exceed a configurable multiple of their inputs — the intermediates that
  OOM a step that "should" fit.

plus a runtime pass fed by dispatch counters rather than a jaxpr:

- **GL007 retrace-churn**: one function traced under many distinct shape
  keys (``core.op_cache`` per-op shape-key counts, ``jit.to_static`` code
  caches, ``models.generation.trace_counts``) — each retrace is seconds of
  compile on the hot path.

Entry points: :func:`lint` (programmatic), :func:`lint_jaxpr`, the
``FLAGS_graph_lint`` / ``PADDLE_TPU_GRAPH_LINT=1`` hook inside
``jit.to_static`` (every compiled program linted at install time, findings
collected in :func:`reports`), and the CLI ``tools/graph_lint.py`` with a
committed baseline-suppression file so CI fails only on NEW findings.
See docs/graph_lint.md.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from .codes import (CODES, SEVERITY_RANK, misaligned_dims,
                    padding_waste_elems)

# the jaxpr datatypes have moved around across jax releases; probe the
# private home last and never let a rename break `import paddle_tpu`
# (paddle_tpu/__init__.py imports analysis)
for _home in ("jax._src.core", "jax.core", "jax.extend.core"):
    try:
        import importlib

        _jcore = importlib.import_module(_home)
        if hasattr(_jcore, "ClosedJaxpr") and hasattr(_jcore, "Var"):
            break
    except ImportError:
        continue
else:  # pragma: no cover - some home above always resolves
    _jcore = None

# DropVar marks discarded eqn outputs; absent from some public namespaces.
# () fallbacks keep every isinstance() below valid (always-False) even if
# a future jax hides one of these — the linter degrades, imports don't.
_DROPVAR = getattr(_jcore, "DropVar", ()) if _jcore else ()
_CLOSED_JAXPR = getattr(_jcore, "ClosedJaxpr", ()) if _jcore else ()
_JAXPR = getattr(_jcore, "Jaxpr", ()) if _jcore else ()
_VAR = getattr(_jcore, "Var", ()) if _jcore else ()

try:  # provenance formatting ("file:line (fn)") — optional, jax-internal
    from jax._src import source_info_util as _src_info
except Exception:  # pragma: no cover - older/newer jax layouts
    _src_info = None

__all__ = [
    "Finding", "LintConfig", "LintReport", "Baseline",
    "lint", "lint_jaxpr", "lint_static_program", "churn_findings",
    "reports", "clear_reports",
]


# ---------------------------------------------------------------------------
# findings and configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Finding:
    """One lint finding.  ``message`` is the human line (carries eqn
    provenance); ``detail`` is the provenance-free payload the
    :attr:`fingerprint` is built from, so baseline suppressions survive
    line-number drift."""

    code: str
    message: str
    detail: str
    severity: str = ""
    primitive: str = ""
    provenance: str = ""
    program: str = "<program>"
    # estimated cost of the hazard ("~X MiB padding waste, ~Y MFLOP at
    # risk"), populated by the size-sensitive passes (GL002/GL006) from
    # the static cost model.  NOT part of the fingerprint: baselines
    # survive cost-model refinements.
    cost: str = ""

    def __post_init__(self):
        if not self.severity:
            self.severity = CODES.get(self.code, ("", "warning"))[1]

    @property
    def fingerprint(self) -> str:
        return f"{self.code}|{self.program}|{self.primitive}|{self.detail}"

    @property
    def rank(self) -> int:
        return SEVERITY_RANK.get(self.severity, 0)

    def render(self) -> str:
        name = CODES.get(self.code, ("?", ""))[0]
        where = f" @ {self.provenance}" if self.provenance else ""
        est = f" [est: {self.cost}]" if self.cost else ""
        return (f"{self.code} [{self.severity}] {name}: {self.message}"
                f"{est}{where} (program={self.program})")


@dataclasses.dataclass
class LintConfig:
    """Thresholds for the size-sensitive passes.  Defaults target bench-
    scale programs; tests shrink them to fire on toy shapes."""

    # GL002: ignore operands smaller than this (padding a tiny array once
    # is not actionable)
    tile_min_bytes: int = 64 * 1024
    # GL004: only inputs at least this large are donation candidates
    donation_min_bytes: int = 1 << 20
    # GL005: dead eqns below this output size are "info", above "warning"
    dead_min_bytes: int = 1 << 20
    # GL006: flag when out_bytes >= blowup_min_bytes AND
    # out_bytes > blowup_ratio * in_bytes
    blowup_ratio: float = 4.0
    blowup_min_bytes: int = 32 << 20
    # GL007 (runtime counters)
    churn_shape_keys: int = 128       # distinct shape keys per eager op
    churn_static_entries: int = 8     # compiled entries per to_static fn
    churn_max_prefill_traces: int = 16
    churn_max_decode_traces: int = 6  # scout+lint+jit per compile =~ 3
    # GL008: flag a collective whose result is consumed while at least
    # this many per-chip FLOPs of INDEPENDENT work are still pending
    # (~50 us of a v5e-class chip — the serialized grad-reduction smell)
    gl008_min_pending_flops: int = 10_000_000
    # GL009: per-chip replicated bytes worth a ZeRO-style shard
    gl009_min_bytes: int = 1 << 20
    # GL011: degenerate collectives below this payload are ignored (the
    # `psum(1, axis)` axis-size idiom is intentional dispatch)
    gl011_min_bytes: int = 1 << 10
    # which jaxpr passes run (GL007 is invoked separately)
    passes: Tuple[str, ...] = ("GL001", "GL002", "GL003", "GL004",
                               "GL005", "GL006", "GL008", "GL009",
                               "GL010", "GL011")


class LintReport:
    """Findings for one program, ordered most-severe first."""

    def __init__(self, program: str, findings: List[Finding]):
        self.program = program
        self.findings = sorted(findings, key=lambda f: -f.rank)

    def __iter__(self):
        return iter(self.findings)

    def __len__(self):
        return len(self.findings)

    def at_least(self, severity: str) -> List[Finding]:
        floor = SEVERITY_RANK[severity]
        return [f for f in self.findings if f.rank >= floor]

    @property
    def errors(self) -> List[Finding]:
        return self.at_least("error")

    def render(self) -> str:
        if not self.findings:
            return f"graph_lint: {self.program}: clean"
        lines = [f"graph_lint: {self.program}: {len(self.findings)} finding(s)"]
        lines += ["  " + f.render() for f in self.findings]
        return "\n".join(lines)

    __str__ = render


# ---------------------------------------------------------------------------
# jaxpr walking helpers
# ---------------------------------------------------------------------------

# layout-only primitives: a promoted value flowing through these is still
# "the same bytes" when it reaches a dot
_LAYOUT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "rev", "copy",
    "slice", "dynamic_slice", "expand_dims",
}

# host-interaction primitives (GL003).  io/pure callbacks run host python
# inside the program; infeed/outfeed are explicit host transfers.
_SYNC_PRIMS = {"io_callback", "pure_callback", "callback", "outside_call",
               "host_callback_call", "infeed", "outfeed"}
_ASYNC_HOST_PRIMS = {"debug_callback", "debug_print"}

_DOT_PRIMS = {"dot_general", "conv_general_dilated", "ragged_dot"}
_REDUCE_PRIMS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                 "reduce_and", "reduce_or", "argmax", "argmin",
                 "reduce_precision"}
_BLOWUP_PRIMS = {"broadcast_in_dim", "concatenate", "pad", "gather", "iota"}


def _aval(v):
    return getattr(v, "aval", None)


def _nbytes(v) -> int:
    aval = _aval(v)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    except Exception:
        return 0


def _dtype_of(v):
    aval = _aval(v)
    return getattr(aval, "dtype", None)


def _shape_of(v):
    aval = _aval(v)
    return tuple(getattr(aval, "shape", ()))


def _dtype_name(dt) -> str:
    """np.dtype name, tolerating jax EXTENDED dtypes (e.g. the typed RNG
    key 'key<fry>' a sampling decode program captures) that np.dtype
    cannot interpret — those fall through as their string form and simply
    never match any numeric-dtype rule."""
    if dt is None:
        return "?"
    try:
        return np.dtype(dt).name
    except TypeError:
        return str(dt)


def _fmt_aval(v) -> str:
    shape = ",".join(str(d) for d in _shape_of(v))
    name = _dtype_name(_dtype_of(v))
    short = {"float32": "f32", "float64": "f64", "float16": "f16",
             "bfloat16": "bf16", "int32": "i32", "int64": "i64",
             "bool": "b1", "complex64": "c64", "complex128": "c128"}
    return f"{short.get(name, name)}[{shape}]"


def _provenance(eqn) -> str:
    if _src_info is None:
        return ""
    try:
        return _src_info.summarize(eqn.source_info)
    except Exception:
        return ""


def _sub_jaxprs(params: Dict[str, Any]):
    """Yield every Jaxpr hiding in an eqn's params (pjit 'jaxpr', scan
    'jaxpr', while 'cond_jaxpr'/'body_jaxpr', cond 'branches',
    custom_* 'call_jaxpr'/'fun_jaxpr', checkpoint bodies, ...)."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, _CLOSED_JAXPR):
                yield v.jaxpr
            elif isinstance(v, _JAXPR):
                yield v


def _is_var(v) -> bool:
    return isinstance(v, _VAR) and not isinstance(v, _DROPVAR)


def _gl002_cost(eqn, v) -> str:
    """Estimated cost of a tile-misaligned operand: bytes of partial-tile
    padding in its physical layout, plus (for contractions) the padded-away
    MXU FLOPs — the numbers the autotuner/roofline model (analysis/cost_model.py)
    computes, quoted on the finding so GL002 is a quantified suggestion
    instead of a bare warning."""
    try:
        dt = _dtype_of(v)
        itemsize = np.dtype(dt).itemsize if dt is not None else 0
        waste = padding_waste_elems(_shape_of(v)) * itemsize
        total = max(_nbytes(v) + waste, 1)
        parts = [f"~{waste / 2**20:.2f} MiB padding waste "
                 f"({100.0 * waste / total:.0f}% of the padded operand)"]
        if eqn.primitive.name in _DOT_PRIMS:
            from .cost_model import dot_flops  # lazy: it imports this module

            at_risk = dot_flops(eqn, padded=True) - dot_flops(eqn)
            if at_risk > 0:
                parts.append(f"~{at_risk / 1e6:.1f} MFLOP of padded-away "
                             "MXU work per execution")
        return ", ".join(parts)
    except Exception:  # noqa: BLE001 — annotation must never break a lint
        return ""


def _gl009_pass(eqn, ctx: "_Ctx", prov: str):
    """GL009 replication-blowup, evaluated AT a shard_map eqn: any large
    input whose in_specs entry omits a manual mesh axis (size > 1) is
    materialized once per chip along that axis — the optimizer-moment /
    master-weight hazard ROADMAP item 1's ZeRO shard reclaims.  Shapes
    here are GLOBAL (the shard_map boundary), so per-chip bytes divide by
    the axes the input IS sharded over."""
    from .cost_model import mesh_axis_sizes  # lazy: it imports this module

    cfg = ctx.config
    try:
        mesh_axes = mesh_axis_sizes(eqn.params.get("mesh"))
        if not mesh_axes:
            return
        # the eqn carries ``manual_axes`` and one PartitionSpec an input
        # (``in_specs``), as jax 0.9 writes it
        manual_axes = eqn.params.get("manual_axes") or frozenset(mesh_axes)
        manual = {a: s for a, s in mesh_axes.items()
                  if a in manual_axes and int(s) > 1}
        if not manual:
            return
        in_specs = eqn.params.get("in_specs") or ()
    except Exception:  # noqa: BLE001 — lint must never crash on odd params
        return
    for opi, (v, spec) in enumerate(zip(eqn.invars, in_specs)):
        try:
            used: Set[str] = set()
            for axes in spec:
                if axes is not None:
                    axes = (axes,) if isinstance(axes, str) else axes
                    used.update(str(a) for a in axes)
            missing = sorted(a for a in manual if a not in used)
            if not missing:
                continue
            shard = 1
            for a in used:
                shard *= int(mesh_axes.get(a, 1))
            per_chip = _nbytes(v) // max(shard, 1)
            if per_chip < cfg.gl009_min_bytes:
                continue
            repl = 1
            for a in missing:
                repl *= int(manual[a])
            reclaim = per_chip * (1 - 1 / repl)
            ctx.add(
                "GL009",
                f"shard_map input {opi} ({_fmt_aval(v)}, "
                f"{per_chip / 2**20:.1f} MiB/chip) is replicated over mesh "
                f"axis(es) {','.join(missing)} (x{repl}) instead of "
                "sharded — optimizer moments / master weights belong in a "
                "ZeRO-style shard over the data axis",
                detail=f"shard_map:invar[{opi}]:{_fmt_aval(v)}:replicated:"
                       f"{','.join(missing)}",
                primitive="shard_map", provenance=prov,
                cost=f"~{reclaim / 2**20:.1f} MiB/chip HBM reclaimable by "
                     f"sharding over {','.join(missing)}")
        except Exception:  # noqa: BLE001
            continue


def _collective_pass(eqn, eqns, i: int, ctx: "_Ctx",
                     axis_sizes: Dict[str, int], prov: str):
    """GL008/GL010/GL011 at one collective eqn (shapes here are
    PER-SHARD: we are inside the shard_map body)."""
    from . import cost_model as _cm  # lazy: it imports this module

    cfg = ctx.config
    cc = _cm._collective_cost(eqn, eqns, i, axis_sizes, 1)
    if cc is None:
        return
    spec = _cm.TARGET_SPEC
    fmt_axes = ",".join(cc.axes)

    if "GL011" in cfg.passes and cc.axis_size <= 1:
        if cc.payload_bytes >= cfg.gl011_min_bytes:
            ctx.add(
                "GL011",
                f"'{cc.primitive}' over size-1 axis '{fmt_axes}' moves "
                f"{cc.payload_bytes / 2**10:.1f} KiB through a degenerate "
                "collective — pure dispatch overhead; gate it on the axis "
                "size or drop the collective",
                detail=f"{cc.primitive}:axis[{fmt_axes}]=1:{cc.out}",
                primitive=cc.primitive, provenance=prov)
        return  # n == 1: no wire, nothing below applies

    if ("GL008" in cfg.passes and cc.consumed_in_body
            and cc.pending_indep_flops >= cfg.gl008_min_pending_flops):
        ctx.add(
            "GL008",
            f"'{cc.primitive}' over '{fmt_axes}' is consumed with "
            f"~{cc.pending_indep_flops / 1e6:.0f} MFLOP of independent "
            "work still pending — the program serializes on the wire; "
            "reorder the consumer after the independent compute (bucketed "
            "async reduction)",
            detail=f"{cc.primitive}:{fmt_axes}:{cc.out}",
            primitive=cc.primitive, provenance=prov,
            cost=f"~{cc.comm_seconds(spec) * 1e6:.1f} us ICI blocking, "
                 f"overlap fraction {cc.overlap_fraction(spec):.2f} "
                 f"(chip={spec.name})")

    if "GL010" in cfg.passes and cc.payload_bytes >= cfg.tile_min_bytes:
        wire_factor = cc.wire_bytes / max(cc.payload_bytes, 1)
        for opi, v in enumerate(eqn.invars):
            nbytes = _nbytes(v)
            if nbytes < cfg.tile_min_bytes:
                continue
            problems = []
            pad_bytes = 0
            try:
                elems = int(np.prod(_shape_of(v), dtype=np.int64))
                itemsize = nbytes // max(elems, 1)
            except Exception:  # noqa: BLE001
                continue
            n = cc.axis_size
            # ppermute ships the whole payload one hop — no ring chunking
            if cc.primitive != "ppermute" and elems % n:
                chunk_pad = (-(-elems // n) * n - elems) * itemsize
                pad_bytes += chunk_pad
                problems.append(
                    f"{elems} elems % axis size {n} != 0 (ring chunks pad)")
            bad = misaligned_dims(_shape_of(v))
            if bad:
                tile_pad = padding_waste_elems(_shape_of(v)) * itemsize
                pad_bytes += tile_pad
                problems.append(", ".join(
                    f"dim[{ax}]={d} % {tile} != 0" for ax, d, tile in bad))
            if not problems:
                continue
            ctx.add(
                "GL010",
                f"'{cc.primitive}' over '{fmt_axes}' payload "
                f"({_fmt_aval(v)}) is misaligned: {'; '.join(problems)} — "
                "padded bytes ride the wire every execution",
                detail=f"{cc.primitive}:operand{opi}:{_fmt_aval(v)}",
                primitive=cc.primitive, provenance=prov,
                cost=f"~{pad_bytes * wire_factor / 2**10:.1f} KiB padded "
                     "ICI wire bytes per execution")


# ---------------------------------------------------------------------------
# the jaxpr passes
# ---------------------------------------------------------------------------

class _Ctx:
    def __init__(self, config: LintConfig, program: str):
        self.config = config
        self.program = program
        self.findings: List[Finding] = []
        self.seen: Set[str] = set()  # fingerprint dedup within one report

    def add(self, code, message, detail, primitive="", provenance="",
            severity="", cost=""):
        f = Finding(code=code, message=message, detail=detail,
                    severity=severity, primitive=primitive,
                    provenance=provenance, program=self.program, cost=cost)
        if f.fingerprint in self.seen:
            return
        self.seen.add(f.fingerprint)
        self.findings.append(f)


def _walk(jaxpr: "_jcore.Jaxpr", ctx: _Ctx, depth: int = 0,
          axis_sizes: Optional[Dict[str, int]] = None):
    cfg = ctx.config
    if depth > 32:  # defensive: malformed/cyclic params
        return
    axis_sizes = axis_sizes or {}
    eqns = list(jaxpr.eqns)

    # var -> (origin dtype name, provenance of the upcast) for values that
    # were promoted sub-fp32 -> fp32 inside THIS jaxpr (GL001)
    promoted: Dict[Any, Tuple[str, str]] = {}

    # liveness (GL005): an eqn is live when any non-dropped output is
    # needed by a later live eqn or by the jaxpr outputs, or it has effects
    live_vars = {v for v in jaxpr.outvars if _is_var(v)}
    live_eqn = [True] * len(jaxpr.eqns)
    for i in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[i]
        needed = bool(eqn.effects) or any(
            v in live_vars for v in eqn.outvars if _is_var(v))
        live_eqn[i] = needed
        if needed:
            live_vars.update(v for v in eqn.invars if _is_var(v))

    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        prov = _provenance(eqn)

        if "GL005" in cfg.passes and not live_eqn[i]:
            out_bytes = sum(_nbytes(v) for v in eqn.outvars)
            if out_bytes == 0:
                # zero-byte results (float0 autograd tangents of integer
                # inputs, empty arrays) are bookkeeping, not dead work
                continue
            sev = "warning" if out_bytes >= cfg.dead_min_bytes else "info"
            ctx.add(
                "GL005",
                f"result of '{prim}' ({', '.join(_fmt_aval(v) for v in eqn.outvars)}) "
                "is never consumed — traced work that XLA will DCE",
                detail=f"{prim}:{'/'.join(_fmt_aval(v) for v in eqn.outvars)}",
                primitive=prim, provenance=prov, severity=sev)
            continue  # findings inside dead eqns would be double noise

        if "GL001" in cfg.passes:
            if prim == "convert_element_type":
                src = _dtype_of(eqn.invars[0])
                dst = eqn.params.get("new_dtype")
                if (src is not None and dst is not None
                        and _dtype_name(src) in ("bfloat16", "float16")
                        and _dtype_name(dst) == "float32"):
                    promoted[eqn.outvars[0]] = (_dtype_name(src), prov)
            elif prim in _LAYOUT_PRIMS:
                for v in eqn.invars:
                    if _is_var(v) and v in promoted:
                        promoted[eqn.outvars[0]] = promoted[v]
                        break
            if prim in _DOT_PRIMS:
                upcast_flagged = False
                for opi, v in enumerate(eqn.invars[:2]):
                    if _is_var(v) and v in promoted:
                        src, src_prov = promoted[v]
                        upcast_flagged = True
                        ctx.add(
                            "GL001",
                            f"'{prim}' operand {opi} ({_fmt_aval(v)}) was "
                            f"silently upcast from {src} (at {src_prov or '?'})"
                            " — the contraction leaves the bf16 MXU path and "
                            "doubles operand bytes; cast back to the storage "
                            "dtype before the matmul",
                            detail=f"{prim}:operand{opi}:{src}->f32:"
                                   f"{_fmt_aval(v)}",
                            primitive=prim, provenance=prov)
                # jax also accepts MIXED operand dtypes directly (f32 x bf16
                # dot_general, no convert eqn): the sub-fp32 side is
                # promoted inside the op — the same silent hazard.  Skipped
                # when the explicit-upcast branch already blamed this eqn
                # (one root cause must not mint two fingerprints).
                names = [_dtype_name(d) if d is not None else ""
                         for d in (_dtype_of(eqn.invars[0]),
                                   _dtype_of(eqn.invars[1]))]
                if not upcast_flagged and "float32" in names and any(
                        n in ("bfloat16", "float16") for n in names):
                    lo = 1 - names.index("float32")
                    ctx.add(
                        "GL001",
                        f"'{prim}' contracts mixed dtypes "
                        f"({_fmt_aval(eqn.invars[0])} x "
                        f"{_fmt_aval(eqn.invars[1])}) — the {names[lo]} "
                        "operand is promoted to fp32 inside the op, leaving "
                        "the bf16 MXU path; cast the fp32 side down (fp32 "
                        "accumulation is kept by preferred_element_type)",
                        detail=f"{prim}:mixed:{_fmt_aval(eqn.invars[0])}x"
                               f"{_fmt_aval(eqn.invars[1])}",
                        primitive=prim, provenance=prov)
            for v in eqn.outvars:
                dt = _dtype_of(v)
                if dt is not None and _dtype_name(dt) in ("float64",
                                                          "complex128"):
                    ctx.add(
                        "GL001",
                        f"'{prim}' produces {_fmt_aval(v)} — an x64 leak "
                        "(f64 has no TPU fast path and doubles bytes)",
                        detail=f"x64:{prim}:{_dtype_name(dt)}",
                        primitive=prim, provenance=prov)

        if "GL002" in cfg.passes and prim in (_DOT_PRIMS | _REDUCE_PRIMS):
            lane_only = prim in _REDUCE_PRIMS
            for opi, v in enumerate(eqn.invars[:2]):
                if _nbytes(v) < cfg.tile_min_bytes:
                    continue
                bad = misaligned_dims(_shape_of(v))
                if lane_only:
                    bad = [b for b in bad if b[2] == 128]
                if bad:
                    dims = ", ".join(
                        f"dim[{ax}]={d} % {tile} != 0" for ax, d, tile in bad)
                    ctx.add(
                        "GL002",
                        f"'{prim}' operand {opi} ({_fmt_aval(v)}) is not "
                        f"(8,128)-tile aligned: {dims} — partial-tile "
                        "padding on every tile row/column",
                        detail=f"{prim}:operand{opi}:{_fmt_aval(v)}",
                        primitive=prim, provenance=prov,
                        severity="info" if lane_only else "warning",
                        cost=_gl002_cost(eqn, v))

        if "GL003" in cfg.passes and (prim in _SYNC_PRIMS
                                      or prim in _ASYNC_HOST_PRIMS):
            sync = prim in _SYNC_PRIMS
            ctx.add(
                "GL003",
                f"'{prim}' inside a compiled program "
                + ("synchronizes with the host every step"
                   if sync else
                   "ships device->host traffic every step (async)"),
                detail=f"{prim}",
                primitive=prim, provenance=prov,
                severity="error" if sync else "warning")

        if "GL006" in cfg.passes and prim in _BLOWUP_PRIMS:
            out_bytes = sum(_nbytes(v) for v in eqn.outvars)
            in_bytes = sum(_nbytes(v) for v in eqn.invars)
            if (out_bytes >= cfg.blowup_min_bytes
                    and out_bytes > cfg.blowup_ratio * max(in_bytes, 1)):
                ctx.add(
                    "GL006",
                    f"'{prim}' materializes {out_bytes / 2**20:.1f} MiB from "
                    f"{in_bytes / 2**20:.1f} MiB of inputs "
                    f"({out_bytes / max(in_bytes, 1):.0f}x) — intermediate "
                    "blowup; check it fuses or is really needed",
                    detail=f"{prim}:{'/'.join(_fmt_aval(v) for v in eqn.outvars)}",
                    primitive=prim, provenance=prov,
                    cost=f"+{(out_bytes - in_bytes) / 2**20:.1f} MiB HBM "
                         "traffic and residency per execution if it fails "
                         "to fuse")

        # v3 SPMD passes: GL009 at the shard_map boundary, GL008/GL010/
        # GL011 at the collective eqns inside its body
        child_axes = axis_sizes
        if prim == "shard_map" or "mesh" in eqn.params:
            from .cost_model import mesh_axis_sizes  # lazy (circular)

            child_axes = dict(axis_sizes)
            child_axes.update(mesh_axis_sizes(eqn.params.get("mesh")))
            if "GL009" in cfg.passes:
                _gl009_pass(eqn, ctx, prov)
        else:
            from .cost_model import COLLECTIVE_PRIMS  # lazy (circular)

            if prim in COLLECTIVE_PRIMS and (
                    {"GL008", "GL010", "GL011"} & set(cfg.passes)):
                _collective_pass(eqn, eqns, i, ctx, axis_sizes, prov)

        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, ctx, depth + 1, child_axes)


def _donation_pass(jaxpr: "_jcore.Jaxpr", donated: Set[int], ctx: _Ctx):
    """GL004 over the TOP-LEVEL jaxpr only (donation is a property of the
    program boundary).  A large undonated input that (a) is consumed, (b)
    is not itself returned, and (c) shape/dtype-matches an output that no
    donated input already aliases, could have been donated — XLA keeps the
    input buffer alive across the whole program instead of aliasing the
    update into it."""
    cfg = ctx.config
    consumed = {v for eqn in jaxpr.eqns for v in eqn.invars if _is_var(v)}
    out_list = [v for v in jaxpr.outvars if _is_var(v)]
    invar_ids = {id(v): i for i, v in enumerate(jaxpr.invars)}
    forwarded = {id(v) for v in out_list if id(v) in invar_ids}

    def sig(v):
        return (_shape_of(v), str(_dtype_of(v)))

    # outputs available as donation targets (not plain pass-throughs)
    out_sigs: Dict[Tuple, int] = {}
    for v in out_list:
        if id(v) not in forwarded:
            out_sigs[sig(v)] = out_sigs.get(sig(v), 0) + 1
    # donated inputs already claim a matching output slot each
    for i in donated:
        if i < len(jaxpr.invars):
            s = sig(jaxpr.invars[i])
            if out_sigs.get(s, 0) > 0:
                out_sigs[s] -= 1

    for i, v in enumerate(jaxpr.invars):
        if i in donated or id(v) in forwarded:
            continue
        nbytes = _nbytes(v)
        if nbytes < cfg.donation_min_bytes or v not in consumed:
            continue
        s = sig(v)
        if out_sigs.get(s, 0) > 0:
            out_sigs[s] -= 1
            ctx.add(
                "GL004",
                f"input {i} ({_fmt_aval(v)}, {nbytes / 2**20:.1f} MiB) is "
                "dead after use and shape-matches an output, but is not "
                "donated — XLA double-buffers it (donate_argnums, or make "
                "the mutation visible to jit.to_static's scout)",
                detail=f"invar[{i}]:{_fmt_aval(v)}",
                primitive="<program-boundary>")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def lint_jaxpr(closed, donated: Optional[Iterable[int]] = None,
               config: Optional[LintConfig] = None,
               program: str = "<program>") -> LintReport:
    """Lint a ``ClosedJaxpr`` (or ``Jaxpr``).  ``donated``: flat indices of
    donated invars for the GL004 pass."""
    cfg = config or LintConfig()
    jaxpr = closed.jaxpr if isinstance(closed, _CLOSED_JAXPR) else closed
    ctx = _Ctx(cfg, program)
    _walk(jaxpr, ctx)
    if "GL004" in cfg.passes:
        _donation_pass(jaxpr, set(donated or ()), ctx)
    return LintReport(program, ctx.findings)


def _flat_donated(args, donate_argnums) -> Set[int]:
    """Map top-level positional donate_argnums to flat invar indices."""
    donated: Set[int] = set()
    offset = 0
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_leaves(a)
        if i in donate_argnums:
            donated.update(range(offset, offset + len(leaves)))
        offset += len(leaves)
    return donated


def lint(fn, *args, donate_argnums: Sequence[int] = (),
         static_argnums: Sequence[int] = (),
         config: Optional[LintConfig] = None,
         program: Optional[str] = None, **kwargs) -> LintReport:
    """Trace ``fn(*args, **kwargs)`` with ``jax.make_jaxpr`` and lint the
    result.  Args may be arrays or ``jax.ShapeDtypeStruct``s (nothing is
    executed).  ``donate_argnums`` feeds the GL004 donation pass."""
    closed = jax.make_jaxpr(fn, static_argnums=tuple(static_argnums))(
        *args, **kwargs)
    dyn_args = [a for i, a in enumerate(args)
                if i not in set(static_argnums)]
    dyn_donate = {i - sum(1 for s in static_argnums if s < i)
                  for i in donate_argnums}
    return lint_jaxpr(
        closed, donated=_flat_donated(dyn_args, dyn_donate), config=config,
        program=program or getattr(fn, "__name__", "<fn>"))


# ---------------------------------------------------------------------------
# the jit.to_static hook: report collection
# ---------------------------------------------------------------------------

_REPORTS_LOCK = threading.Lock()
_REPORTS: List[LintReport] = []
_MAX_REPORTS = 256
_ANNOUNCE = [True]


def set_announce(enabled: bool):
    """Toggle the compile hook's stderr announcement of findings.  The
    CLI turns it off — it renders the collected reports itself, and CI
    logs must not show every finding twice."""
    _ANNOUNCE[0] = bool(enabled)


def _record(report: LintReport, announce: bool = True):
    with _REPORTS_LOCK:
        _REPORTS.append(report)
        del _REPORTS[:-_MAX_REPORTS]
    if announce and _ANNOUNCE[0] and report.findings:
        sys.stderr.write("[paddle_tpu.graph_lint] " + report.render() + "\n")


def reports() -> List[LintReport]:
    """Reports collected by the FLAGS_graph_lint compile hooks (and
    anything linted through :func:`lint_static_program`)."""
    with _REPORTS_LOCK:
        return list(_REPORTS)


def clear_reports():
    with _REPORTS_LOCK:
        _REPORTS.clear()


def lint_static_program(pure_fn, arg_structs, mut_structs, ro_structs,
                        program: str,
                        config: Optional[LintConfig] = None,
                        jaxpr=None) -> LintReport:
    """Lint one jit.to_static compiled entry: trace ``pure_fn(raw_args,
    raw_mut, raw_ro)`` abstractly and mark the mutated-capture block as
    donated (jit/api.py jits it with ``donate_argnums=(1,)``).  Pass an
    already-traced ``jaxpr`` to skip the abstract trace (the compile hook
    shares one trace between this and the cost model)."""
    closed = (jaxpr if jaxpr is not None
              else jax.make_jaxpr(pure_fn)(arg_structs, mut_structs,
                                           ro_structs))
    donated = set(range(len(arg_structs),
                        len(arg_structs) + len(mut_structs)))
    report = lint_jaxpr(closed, donated=donated, config=config,
                        program=program)
    _record(report)
    return report


# ---------------------------------------------------------------------------
# GL007: retrace churn from live dispatch counters
# ---------------------------------------------------------------------------

def churn_findings(config: Optional[LintConfig] = None,
                   op_stats: Optional[Dict[str, Dict]] = None,
                   static_fns: Optional[Dict[str, int]] = None,
                   trace_counts: Optional[Dict[str, int]] = None,
                   program_counts: Optional[Dict[str, int]] = None
                   ) -> LintReport:
    """The runtime pass: flag shape-key churn in the eager op cache, code-
    cache churn in ``jit.to_static`` functions, and decode-engine retraces.
    Arguments default to the live process counters; tests pass dicts.

    ``program_counts``: compiled prefill/decode programs per phase — the
    trace-count limits scale with it, because ``generation._TRACE_COUNTS``
    is process-global and every legitimately cached engine pays its own
    scout+jit(+lint) traces (live default: summed code-cache sizes of the
    registered ``prefill_step``/``decode_step`` functions)."""
    cfg = config or LintConfig()
    ctx = _Ctx(cfg, "<runtime-counters>")

    if op_stats is None:
        from ..core import op_cache as _op_cache

        op_stats = _op_cache.stats()
    for op, st in sorted(op_stats.items()):
        sk = int(st.get("shape_keys", 0))
        overflow = bool(st.get("shape_keys_overflow", False))
        if sk > cfg.churn_shape_keys or overflow:
            bound = (f">= {sk} (tracking set saturated — the true count "
                     "is higher)" if overflow else str(sk))
            ctx.add(
                "GL007",
                f"eager op '{op}' compiled under {bound} distinct shape "
                f"keys (> {cfg.churn_shape_keys}) — shape churn retraces "
                "on the hot path; pad/bucket the varying dim",
                detail=f"op_cache:{op}", primitive=op)

    if static_fns is None:
        from ..jit import api as _jit_api

        static_fns = {}
        for sf in list(getattr(_jit_api, "_STATIC_REGISTRY", ())):
            name = getattr(sf, "__name__", "to_static_fn")
            n = len(getattr(sf, "_cache", ()))
            static_fns[name] = max(static_fns.get(name, 0), n)
    for name, entries in sorted(static_fns.items()):
        if entries > cfg.churn_static_entries:
            ctx.add(
                "GL007",
                f"jit.to_static fn '{name}' holds {entries} compiled "
                f"programs (> {cfg.churn_static_entries}) — the same fn "
                "keeps retracing under new shape keys",
                detail=f"to_static:{name}", primitive=name)

    if trace_counts is None:
        from ..models import generation as _generation

        trace_counts = _generation.trace_counts()
    if program_counts is None:
        from ..jit import api as _jit_api

        program_counts = {}
        for sf in list(getattr(_jit_api, "_STATIC_REGISTRY", ())):
            name = getattr(sf, "__name__", "")
            if name in ("prefill_step", "decode_step"):
                phase = name[:-len("_step")]
                program_counts[phase] = (program_counts.get(phase, 0)
                                         + len(getattr(sf, "_cache", ())))
    limits = {"prefill": cfg.churn_max_prefill_traces,
              "decode": cfg.churn_max_decode_traces}
    for phase, n in sorted(trace_counts.items()):
        per_program = limits.get(phase, cfg.churn_max_decode_traces)
        limit = per_program * max(1, program_counts.get(phase, 1))
        if n > limit:
            ctx.add(
                "GL007",
                f"decode-engine {phase} step body traced {n} times across "
                f"{max(1, program_counts.get(phase, 1))} compiled "
                f"program(s) (> {limit}) — the retrace-free invariant is "
                "broken (a shape or python value is leaking into the trace "
                "key)",
                detail=f"generation:{phase}", primitive=phase)

    return LintReport("<runtime-counters>", ctx.findings)


# ---------------------------------------------------------------------------
# baseline suppression
# ---------------------------------------------------------------------------

class Baseline:
    """Committed suppression file: known findings (fingerprint +
    justification) that the CI gate tolerates.  The gate fails only on
    findings NOT in the baseline, so new hazards can't hide behind old
    accepted ones."""

    VERSION = 1

    def __init__(self, suppressions: Optional[Dict[str, str]] = None):
        self.suppressions: Dict[str, str] = dict(suppressions or {})

    # -- persistence -------------------------------------------------------
    @classmethod
    def load(cls, path: str) -> "Baseline":
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != cls.VERSION:
            raise ValueError(
                f"baseline {path}: unsupported version {data.get('version')}")
        sup = {e["fingerprint"]: e.get("justification", "")
               for e in data.get("suppressions", ())}
        return cls(sup)

    def save(self, path: str):
        data = {
            "version": self.VERSION,
            "suppressions": [
                {"fingerprint": fp, "code": fp.split("|", 1)[0],
                 "justification": j}
                for fp, j in sorted(self.suppressions.items())
            ],
        }
        with open(path, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")

    # -- matching ----------------------------------------------------------
    def suppresses(self, finding: Finding) -> bool:
        return finding.fingerprint in self.suppressions

    def filter_new(self, findings: Iterable[Finding]) -> List[Finding]:
        return [f for f in findings if not self.suppresses(f)]

    def add(self, finding: Finding, justification: str = ""):
        self.suppressions[finding.fingerprint] = justification
