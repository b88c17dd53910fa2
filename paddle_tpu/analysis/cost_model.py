"""Static roofline cost model over jaxprs (Graph Lint v2).

`graph_lint.py` tells you a program contains a hazard; this module tells
you what the hazard *costs*.  It walks the same jaxprs (recursing into
pjit/scan/cond/while/custom-vjp sub-jaxprs) and computes, per equation and
per program:

- **FLOPs** — exact for ``dot_general``/``conv_general_dilated`` (2·N·K
  from the contraction dims), element-count heuristics elsewhere (1
  flop/output element for arithmetic, 1 flop/input element for
  reductions, 0 for pure data movement);
- **HBM bytes** — two bounds, because fusion is unknowable statically:
  ``bytes_upper`` sums every equation's operand+result bytes (the
  nothing-fuses bound) and ``boundary_bytes`` counts only the program's
  inputs+outputs (the everything-fuses bound).  The truth sits between;
  the roofline verdict uses the upper bound (conservative attainable);
- **arithmetic intensity** — FLOPs / HBM bytes, against a per-chip
  :class:`HardwareSpec` (peak bf16 FLOP/s + HBM bandwidth) so a program
  classifies compute-bound vs memory-bound and a *measured* wall time
  turns into a roofline fraction (bench.py's ``*_roofline_fraction``
  lines);
- **(8, 128)-tile padding waste** — for every dot/reduce operand, the
  bytes the physical layout spends on partial tiles
  (``codes.padding_waste_elems``, the same rule GL002 fires on).

Loop handling: ``scan`` bodies are multiplied by their trip count;
``while`` bodies count once and set :attr:`CostReport.has_unbounded_loops`
(the static model cannot bound them); ``cond`` takes its most expensive
branch.  Equations that carry sub-jaxprs contribute ONLY their bodies
(counting both the call eqn's operands and the body would double-count).
``shard_map`` bodies (the mesh-sharded serving step) see PER-SHARD
shapes, so they are multiplied by the shard count — the product of the
mesh axes the body runs manually over — keeping every count in GLOBAL
(whole-cluster) units like the rest of the program's GSPMD-annotated
equations.

v3 adds the SPMD/communication model (see docs/graph_lint.md "v3"):
every collective primitive reachable by the same walk (``psum``,
``all_gather``, ``reduce_scatter``, ``all_to_all``, ``ppermute`` inside
``shard_map`` bodies, with mesh-axis sizes resolved from the enclosing
``shard_map`` eqn's mesh) contributes a :class:`CollectiveCost`: the
serialized **wire bytes over the slowest ICI link** under the standard
ring schedules (all-reduce ``2(n-1)/n·B``, all-gather/reduce-scatter
``(n-1)/n`` of the full payload, all-to-all ``(n-1)/n·B``, ppermute one
hop of ``B``), hop-latency terms, and a statically computed **overlap
fraction** — the per-chip FLOPs scheduled between the collective's issue
point and its first consumer, as a fraction of the collective's
estimated wire time.  ``CostReport.comm_seconds(spec)`` /
``comm_seconds_by_axis`` / ``overlap_fraction`` aggregate these;
collectives are costed per-LINK (never multiplied by the shard count —
all chips drive their links concurrently), only by loop trip counts.

Entry points mirror the linter: :func:`cost` traces a function
abstractly, :func:`cost_jaxpr` takes a ClosedJaxpr,
:func:`cost_static_program` costs one ``jit.to_static`` entry (the
``FLAGS_graph_cost`` compile hook in ``jit/api.py`` calls it and stashes
the report on the entry + the :func:`cost_reports` registry).  The CLI is
``tools/graph_lint.py --cost``.  See docs/graph_lint.md "v2: cost model".
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from .codes import padding_waste_elems

from .graph_lint import (  # shared jaxpr plumbing — one walker idiom
    _CLOSED_JAXPR,
    _aval,
    _dtype_of,
    _fmt_aval,
    _is_var,
    _nbytes,
    _provenance,
    _shape_of,
    _sub_jaxprs,
)

__all__ = [
    "HardwareSpec", "chip_spec", "TARGET_SPEC", "EqnCost", "CostReport",
    "CollectiveCost", "COLLECTIVE_PRIMS",
    "collective_wire_bytes", "collective_hops", "collective_axis_names",
    "cost", "cost_jaxpr", "cost_static_program",
    "cost_reports", "clear_cost_reports",
    "dot_flops", "eqn_flops", "ragged_padding_waste",
    "paged_pool_bytes", "decode_step_kv_bytes",
    "page_transfer_bytes", "page_transfer_cost",
]


# ---------------------------------------------------------------------------
# hardware specs (public spec-sheet numbers; bench.py routes through these
# so the MFU and roofline denominators can't drift apart)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """One chip's roofline: bf16 peak FLOP/s and HBM bandwidth (bytes/s),
    plus the ICI terms the v3 comm model uses — ``ici_bw`` is the ONE-WAY
    bandwidth of a single ICI link (bytes/s; ring collectives are
    serialized on the slowest link, so per-link is the time-determining
    number, not the per-chip aggregate) and ``ici_latency`` the per-hop
    latency (seconds).  ``ridge`` is the arithmetic intensity
    (flops/byte) above which a program is compute-bound."""

    name: str
    peak_flops: float
    hbm_bw: float
    ici_bw: float = 5e10
    ici_latency: float = 1e-6

    @property
    def ridge(self) -> float:
        return self.peak_flops / self.hbm_bw

    def attainable_flops(self, intensity: float) -> float:
        """Roofline-attainable FLOP/s at ``intensity`` flops/byte."""
        return min(self.peak_flops, max(intensity, 0.0) * self.hbm_bw)


# substring probes in priority order ('v5e'/'lite' must win over bare
# 'v5'); FLOPs are bf16 peak, BW is HBM per chip, ICI numbers are
# approximate public per-link one-way figures (aggregate per-chip ICI
# divided by the link count of the generation's torus)
_CHIP_TABLE = (
    (("v6",), HardwareSpec("v6e", 918e12, 1640e9, 112e9, 1e-6)),
    (("v5e", "lite"), HardwareSpec("v5e", 197e12, 819e9, 50e9, 1e-6)),
    (("v5",), HardwareSpec("v5p", 459e12, 2765e9, 100e9, 1e-6)),
    (("v4",), HardwareSpec("v4", 275e12, 1228e9, 50e9, 1e-6)),
    (("v3",), HardwareSpec("v3", 123e12, 900e9, 82e9, 1e-6)),
    (("v2",), HardwareSpec("v2", 45e12, 700e9, 62e9, 1e-6)),
)


def chip_spec(*probes: str) -> HardwareSpec:
    """Resolve a :class:`HardwareSpec` from device-kind / generation
    strings ('TPU v5 lite', 'v4', ...).  First matching probe wins; a
    device that is not in the table is an error, never a default — a
    guessed peak under a measurement is how a CPU run once printed
    ``peak_flops=197e12``."""
    for probe in probes:
        p = (probe or "").lower()
        if not p:
            continue
        for keys, spec in _CHIP_TABLE:
            if any(k in p for k in keys):
                return spec
    raise ValueError(
        f"no HardwareSpec for {probes!r}: not a chip in "
        "analysis/cost_model._CHIP_TABLE (add it with its source, or name "
        "a target chip explicitly)")


# The chip the static passes MODEL when the caller passes no spec.  Graph
# lint and the cost reports run with no device present (CI, the CPU
# suite), so this is a stated target — the v5e the repo is measured on —
# and never a guess at the hardware under a measurement.
TARGET_SPEC = chip_spec("v5e")


# ---------------------------------------------------------------------------
# collectives (the v3 comm model)
# ---------------------------------------------------------------------------

# the explicit collective primitives our shard_map bodies emit (GSPMD-
# inserted collectives materialize only after partitioning and are
# invisible at the jaxpr level — this model covers the manual ones).
# ``psum2`` is what a checked-replication shard_map body binds psum as;
# it is normalized to "psum" everywhere downstream so findings and
# formulas are jax-version-stable.
COLLECTIVE_PRIMS = frozenset(
    {"psum", "psum2", "all_gather", "reduce_scatter", "all_to_all",
     "ppermute"})


def _norm_prim(prim: str) -> str:
    return "psum" if prim == "psum2" else prim


def collective_axis_names(eqn) -> Tuple[str, ...]:
    """Mesh-axis names a collective eqn runs over (``axes`` on psum,
    ``axis_name`` elsewhere; either may be a bare name or a tuple)."""
    try:
        axes = eqn.params.get("axes", None)
        if axes is None:
            axes = eqn.params.get("axis_name", ())
        if isinstance(axes, (str, int)):
            axes = (axes,)
        return tuple(str(a) for a in axes)
    except Exception:  # noqa: BLE001 — cost model must never crash a walk
        return ()


def collective_wire_bytes(prim: str, payload_bytes: int, out_bytes: int,
                          n: int) -> int:
    """Serialized bytes over the slowest ICI link for ONE execution of a
    collective over an ``n``-way axis, under the standard ring schedules:
    ring all-reduce moves ``2(n-1)/n`` of the payload (reduce-scatter +
    all-gather halves), all-gather ``(n-1)/n`` of the GATHERED result,
    reduce-scatter and all-to-all ``(n-1)/n`` of the local payload, and
    ppermute exactly the payload (one neighbor hop).  ``payload_bytes``
    is the per-chip input, ``out_bytes`` the per-chip output."""
    n = max(int(n), 1)
    if n == 1:
        return 0
    if prim == "psum":
        return int(round(2 * (n - 1) / n * payload_bytes))
    if prim == "all_gather":
        return int(round((n - 1) / n * max(out_bytes, payload_bytes)))
    if prim in ("reduce_scatter", "all_to_all"):
        return int(round((n - 1) / n * payload_bytes))
    if prim == "ppermute":
        return int(payload_bytes)
    return 0


def collective_hops(prim: str, n: int) -> int:
    """Latency hops of the ring schedule: ``2(n-1)`` for the all-reduce,
    ``n-1`` for all-gather/reduce-scatter/all-to-all, one for ppermute."""
    n = max(int(n), 1)
    if n == 1:
        return 0
    if prim == "psum":
        return 2 * (n - 1)
    if prim == "ppermute":
        return 1
    return n - 1


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """{axis name: size} of a ``Mesh``/``AbstractMesh`` (none -> {})."""
    if mesh is None:
        return {}
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def _eqn_chip_flops(eqn, depth: int = 0) -> int:
    """Per-chip FLOPs of one eqn including sub-jaxpr bodies (scan bodies
    x trip count, cond's most expensive branch, while bodies once).
    Unlike the global accounting, shard_map bodies are NOT multiplied by
    the shard count: overlap compares against the time ONE chip spends
    computing."""
    if depth > 32:
        return 0
    try:
        subs = list(_sub_jaxprs(eqn.params))
        if not subs:
            return eqn_flops(eqn)
        prim = eqn.primitive.name
        if prim == "cond":
            return max((_jaxpr_chip_flops(s, depth + 1) for s in subs),
                       default=0)
        mult = 1
        if prim == "scan":
            mult = max(int(eqn.params.get("length", 1) or 1), 1)
        return mult * sum(_jaxpr_chip_flops(s, depth + 1) for s in subs)
    except Exception:  # noqa: BLE001
        return 0


def _jaxpr_chip_flops(jaxpr, depth: int = 0) -> int:
    return sum(_eqn_chip_flops(e, depth) for e in jaxpr.eqns)


def _first_consumer(eqns, i) -> Optional[int]:
    """Index of the first eqn after ``i`` consuming any of eqn i's
    outputs, or None when the result is only consumed at the jaxpr
    boundary (fully overlappable with everything after it)."""
    outs = {v for v in eqns[i].outvars if _is_var(v)}
    if not outs:
        return None
    for j in range(i + 1, len(eqns)):
        # sub-jaxpr consumption is visible through the call eqn's own
        # invars (jaxprs close over explicit operands), so scanning the
        # flat invars covers call-like eqns too
        for v in eqns[j].invars:
            if _is_var(v) and v in outs:
                return j
    return None


def _pending_indep_flops(eqns, i: int, j: Optional[int]) -> int:
    """Per-chip FLOPs of eqns after the first consumer ``j`` that do NOT
    transitively depend on eqn ``i``'s outputs — the independent work
    still pending when the program blocks on the collective (GL008's
    quantity; 0 when the result is consumed only at the boundary)."""
    if j is None:
        return 0
    tainted = {v for v in eqns[i].outvars if _is_var(v)}
    total = 0
    for k in range(j, len(eqns)):
        ek = eqns[k]
        if any(_is_var(v) and v in tainted for v in ek.invars):
            tainted.update(v for v in ek.outvars if _is_var(v))
        elif k > j:
            total += _eqn_chip_flops(ek)
    return total


@dataclasses.dataclass
class CollectiveCost:
    """One collective eqn's communication cost.  ``wire_bytes``/``hops``
    are per ONE execution; ``mult`` is the loop trip multiplier (scan
    bodies — never the shard count: every chip drives its links
    concurrently, so per-link serialized bytes are the wall-clock
    quantity).  ``overlap_flops`` is the per-chip compute statically
    scheduled between the issue point and the first consumer;
    ``pending_indep_flops`` the independent per-chip compute still
    pending AFTER the first consumer (the GL008 smell)."""

    primitive: str
    axes: Tuple[str, ...]
    axis_size: int
    payload_bytes: int
    wire_bytes: int
    hops: int
    mult: int
    overlap_flops: int
    pending_indep_flops: int
    consumed_in_body: bool
    out: str
    provenance: str = ""

    def comm_seconds(self, spec: Optional[HardwareSpec] = None) -> float:
        """Estimated wire seconds of ONE execution."""
        spec = spec or TARGET_SPEC
        return (self.wire_bytes / spec.ici_bw
                + self.hops * spec.ici_latency)

    def overlap_fraction(self, spec: Optional[HardwareSpec] = None) -> float:
        """min(1, available independent compute time / comm time): 1.0
        means the wire is fully hideable behind already-scheduled
        compute, 0.0 means the program blocks for the full transfer."""
        spec = spec or TARGET_SPEC
        t = self.comm_seconds(spec)
        if t <= 0:
            return 1.0
        return min(1.0, (self.overlap_flops / spec.peak_flops) / t)

    def render(self, spec: Optional[HardwareSpec] = None) -> str:
        spec = spec or TARGET_SPEC
        mult = f" x{self.mult}" if self.mult != 1 else ""
        where = f" @ {self.provenance}" if self.provenance else ""
        return (f"{self.primitive}[{','.join(self.axes)}:{self.axis_size}]"
                f"{mult} -> {self.out}: wire "
                f"{self.wire_bytes / 2**20:.3f} MiB, est "
                f"{self.comm_seconds(spec) * 1e3:.4f} ms, overlap "
                f"{self.overlap_fraction(spec):.3f}" + where)


def _collective_cost(eqn, eqns, i: int, axis_sizes: Dict[str, int],
                     loop_mult: int) -> Optional["CollectiveCost"]:
    """Build the CollectiveCost of ``eqns[i]`` (or None when its mesh
    axes cannot be resolved from the enclosing shard_map context)."""
    try:
        prim = _norm_prim(eqn.primitive.name)
        axes = collective_axis_names(eqn)
        if not axes:
            return None
        n = 1
        for a in axes:
            s = axis_sizes.get(a)
            if s is None:
                return None
            n *= int(s)
        payload = sum(_nbytes(v) for v in eqn.invars)
        out_b = sum(_nbytes(v) for v in eqn.outvars)
        j = _first_consumer(eqns, i)
        end = j if j is not None else len(eqns)
        overlap = sum(_eqn_chip_flops(eqns[k]) for k in range(i + 1, end))
        return CollectiveCost(
            primitive=prim,
            axes=axes,
            axis_size=n,
            payload_bytes=payload,
            wire_bytes=collective_wire_bytes(prim, payload, out_b, n),
            hops=collective_hops(prim, n),
            mult=max(int(loop_mult), 1),
            overlap_flops=int(overlap),
            pending_indep_flops=_pending_indep_flops(eqns, i, j),
            consumed_in_body=j is not None,
            out="/".join(_fmt_aval(v) for v in eqn.outvars),
            provenance=_provenance(eqn),
        )
    except Exception:  # noqa: BLE001 — cost model must never crash a walk
        return None


# ---------------------------------------------------------------------------
# per-equation FLOPs
# ---------------------------------------------------------------------------

def _elems(v) -> int:
    aval = _aval(v)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    try:
        return int(np.prod(aval.shape, dtype=np.int64))
    except Exception:
        return 0


def dot_flops(eqn, padded: bool = False) -> int:
    """Exact MXU FLOPs of a ``dot_general`` eqn: 2 · out_elems · K, with K
    the product of the contraction dims.  ``padded=True`` computes the
    same product over (8, 128)-tile-padded operand/output shapes — the
    MXU work the hardware actually issues; the difference is GL002's
    "FLOPs at risk"."""
    try:
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        lhs_shape = _shape_of(eqn.invars[0])
        out_shape = _shape_of(eqn.outvars[0])
        if padded:
            from .codes import padded_shape

            lhs_shape = padded_shape(lhs_shape)
            out_shape = padded_shape(out_shape)
        k = 1
        for ax in lhs_c:
            k *= int(lhs_shape[ax])
        out = 1
        for d in out_shape:
            out *= int(d)
        return 2 * out * k
    except Exception:
        return 2 * _elems(eqn.outvars[0])


def _conv_flops(eqn) -> int:
    """conv_general_dilated ≈ 2 · out_elems · K, K = rhs elements per
    output feature (window · in_features)."""
    try:
        dn = eqn.params["dimension_numbers"]
        rhs_shape = _shape_of(eqn.invars[1])
        out_feat = int(rhs_shape[dn.rhs_spec[0]])
        k = 1
        for d in rhs_shape:
            k *= int(d)
        k //= max(out_feat, 1)
        return 2 * sum(_elems(v) for v in eqn.outvars) * k
    except Exception:
        return 2 * sum(_elems(v) for v in eqn.outvars)


# pure data movement / bookkeeping: bytes, no flops
_MOVEMENT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "rev", "copy", "slice", "dynamic_slice", "dynamic_update_slice",
    "gather", "scatter", "concatenate", "pad", "iota", "convert_element_type",
    "bitcast_convert_type", "select_n", "stop_gradient", "device_put",
    "split", "squeeze", "rng_bit_generator", "random_seed", "random_wrap",
    "random_unwrap", "random_bits", "reduce_precision",
}

_REDUCE_FLOP_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax",
    "cummin", "cumprod", "sort",
}

# operands whose (8,128) padding waste we charge — same scope as GL002
_TILED_OPERAND_PRIMS = {
    "dot_general", "conv_general_dilated", "ragged_dot",
} | _REDUCE_FLOP_PRIMS


def eqn_flops(eqn) -> int:
    """FLOPs of one equation under this model's counting rules (see
    module docstring): exact for dots/convs, element-count heuristics
    elsewhere."""
    prim = eqn.primitive.name
    if prim in ("dot_general", "ragged_dot"):
        return dot_flops(eqn)
    if prim == "conv_general_dilated":
        return _conv_flops(eqn)
    if prim in _REDUCE_FLOP_PRIMS:
        return sum(_elems(v) for v in eqn.invars)
    if prim in _MOVEMENT_PRIMS:
        return 0
    # arithmetic / transcendental / comparison: 1 flop per output element
    return sum(_elems(v) for v in eqn.outvars)


def _eqn_padding_waste(eqn) -> int:
    """Bytes of (8,128) partial-tile padding across the eqn's tiled
    operands (dot/reduce scope — where the MXU/VPU layout actually pays)."""
    if eqn.primitive.name not in _TILED_OPERAND_PRIMS:
        return 0
    waste = 0
    for v in eqn.invars[:2]:
        dt = _dtype_of(v)
        if dt is None:
            continue
        try:
            itemsize = np.dtype(dt).itemsize
        except TypeError:
            continue  # extended dtypes (RNG keys) have no tile layout here
        waste += padding_waste_elems(_shape_of(v)) * itemsize
    return waste


def ragged_padding_waste(n_tokens: int, n_blocks: int, n_items: int,
                         token_block: int, page_size: int, head_dim: int,
                         dtype="bfloat16", *, wide_block: int = 0,
                         wide_tokens: int = 0, wide_blocks: int = 0,
                         wide_items: int = 0) -> dict:
    """The ragged fused step's HOST-PACKED padding cost — the GL002-style
    annotation for waste the jaxpr-level pass cannot see, because the
    padding lives in the kernel's work-list layout, not in any array's
    (8, 128) tile shape.

    A work item computes one ``[block rows, page_size]`` score tile and
    one ``[block rows, head_dim]`` accumulator pass whether or not every
    block row carries a real token; decode tokens fill 1 row of
    ``token_block``.  Given one step's plan stats (``n_tokens`` real query
    tokens, ``n_blocks`` packed blocks, ``n_items`` work items: ALL of
    them) this quotes the padded-away MXU work and the padded q-row bytes
    with the SAME units GL002's dot annotation uses
    (``dot_flops(padded=True)`` delta), so lint output and serving metrics
    describe one quantity.  The part of the step that rode WIDE blocks
    (``wide_tokens`` in ``wide_blocks`` of ``wide_block`` rows, over
    ``wide_items``) is reckoned by that width, the rest by ``token_block``.

    Returns ``{"padded_rows", "wasted_flops", "wasted_q_bytes"}``."""
    padded_rows = wasted_flops = 0
    for width, tokens, blocks, items in (
            (int(token_block), n_tokens - wide_tokens,
             n_blocks - wide_blocks, n_items - wide_items),
            (int(wide_block), wide_tokens, wide_blocks, wide_items)):
        padded = blocks * width - int(tokens)
        if padded < 0:
            raise ValueError(f"n_tokens={tokens} exceeds "
                             f"{blocks} x {width} block rows")
        # rows are padded uniformly across a block's work items; each item
        # pays 2·D·page_size MXU flops per row (QK^T) + 2·D·page_size (P·V)
        rows_frac = padded / max(blocks * width, 1)
        item_flops = 4 * int(head_dim) * int(page_size) * width
        padded_rows += padded
        wasted_flops += int(round(items * item_flops * rows_frac))
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 2
    if str(dtype) == "int8":
        # int8 KV pools: only the PAGES are int8 — the padded q rows ride
        # fp32 (the public kernel API casts q up so the dequant epilogue
        # and softmax accumulate in fp32)
        itemsize = 4
    return {
        "padded_rows": padded_rows,
        "wasted_flops": wasted_flops,
        "wasted_q_bytes": padded_rows * int(head_dim) * itemsize,
    }


def paged_pool_bytes(num_pages: int, num_heads: int, page_size: int,
                     head_dim: int, num_layers: int = 1,
                     dtype="bfloat16") -> int:
    """Total HBM bytes of one paged KV pool (K + V across layers) —
    the admission-capacity denominator serving_bench's fixed-byte sweeps
    compare precision regimes against.  In the int8 regime this counts
    the int8 pages PLUS the per-(page, head) fp32 absmax scale buffers
    (serving/paged_cache.py), not a fp32-equivalent."""
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 2
    page = int(num_heads) * int(page_size) * int(head_dim) * itemsize
    total = 2 * int(num_layers) * int(num_pages) * page          # K + V
    if str(dtype) == "int8":
        # fp32 [P, H] scale buffer per pool, per layer, for K and V
        total += 2 * int(num_layers) * int(num_pages) * int(num_heads) * 4
    return total


def page_transfer_bytes(num_pages: int, num_heads: int, page_size: int,
                        head_dim: int, num_layers: int = 1,
                        dtype="bfloat16") -> int:
    """Exact wire bytes of a disaggregated page hand-off moving
    ``num_pages`` FILLED pool pages between two replicas
    (serving/disagg.py PageTransfer): K + V for every page across
    layers, plus — in the int8 regime — the per-(page, head) fp32 absmax
    scale sidecars that ride along (a dequantizable page is page bytes
    AND its scales; shipping one without the other is a wrong answer).
    The geometry is identical to a ``num_pages``-page pool, so this
    delegates to :func:`paged_pool_bytes` — one formula, no drift."""
    return paged_pool_bytes(num_pages, num_heads, page_size, head_dim,
                            num_layers=num_layers, dtype=dtype)


def page_transfer_cost(num_pages: int, num_heads: int, page_size: int,
                       head_dim: int, num_layers: int = 1,
                       dtype="bfloat16",
                       provenance: str = "serving/disagg.PageTransfer"
                       ) -> "CollectiveCost":
    """The hand-off as ICI traffic, in the mesh-lint cost vocabulary: a
    point-to-point ``ppermute``-shaped transfer (wire == payload, one
    hop), so ``comm_seconds``/``overlap_fraction`` and the GL008/GL010
    overlap machinery apply to it exactly as to a compiled collective —
    serving_bench reports transfer seconds vs decode compute from this.
    The copy runs OUTSIDE any compiled step program (device-to-device
    gather/scatter between two pools), so there is no in-graph consumer:
    ``consumed_in_body=False`` and the decode work both replicas keep
    dispatching meanwhile is the overlap budget callers may add."""
    payload = page_transfer_bytes(num_pages, num_heads, page_size,
                                  head_dim, num_layers=num_layers,
                                  dtype=dtype)
    return CollectiveCost(
        primitive="ppermute",
        axes=("dp",),
        axis_size=2,                    # source chip -> destination chip
        payload_bytes=payload,
        wire_bytes=collective_wire_bytes("ppermute", payload, payload, 2),
        hops=collective_hops("ppermute", 2),
        mult=1,
        overlap_flops=0,
        pending_indep_flops=0,
        consumed_in_body=False,
        out=f"{int(num_pages)} pages x{int(num_layers)}L {dtype}",
        provenance=provenance,
    )


def decode_step_kv_bytes(context_tokens: int, num_heads: int,
                         head_dim: int, page_size: int,
                         num_layers: int = 1, dtype="bfloat16") -> int:
    """HBM-upper bound on KV bytes streamed for ONE decode token over a
    ``context_tokens``-position context: the ragged/paged kernels read
    each valid K and V row exactly once per layer (scalar-prefetched
    index maps elide everything past the clamped tail), plus — in the
    int8 regime — one fp32 scale per touched (page, head).  The decode
    step is memory-bound, so this bound tracks its wall-clock; int8
    pages halve it twice over vs fp32 (the cost-model golden pins
    int8 <= fp32 / 2)."""
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 2
    total = (2 * int(num_layers) * int(context_tokens) * int(num_heads)
             * int(head_dim) * itemsize)
    if str(dtype) == "int8":
        pages = -(-int(context_tokens) // int(page_size))    # ceil
        total += 2 * int(num_layers) * pages * int(num_heads) * 4
    return total


# ---------------------------------------------------------------------------
# report datatypes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EqnCost:
    """One equation's contribution (already multiplied by its loop trip
    count)."""

    primitive: str
    flops: int
    bytes: int
    padding_waste_bytes: int
    mult: int
    out: str
    provenance: str = ""

    def render(self) -> str:
        mult = f" x{self.mult}" if self.mult != 1 else ""
        where = f" @ {self.provenance}" if self.provenance else ""
        return (f"{self.primitive}{mult} -> {self.out}: "
                f"{self.flops / 1e9:.3f} GFLOP, "
                f"{self.bytes / 2**20:.1f} MiB"
                + (f", {self.padding_waste_bytes / 2**20:.2f} MiB pad waste"
                   if self.padding_waste_bytes else "")
                + where)


class CostReport:
    """Static cost of one program.  ``bytes_upper`` is the per-equation
    sum (nothing fuses), ``boundary_bytes`` the program inputs+outputs
    (everything fuses); roofline verdicts use the conservative upper
    bound."""

    def __init__(self, program: str, eqns: List[EqnCost],
                 boundary_bytes: int, has_unbounded_loops: bool = False,
                 collectives: Optional[List[CollectiveCost]] = None):
        self.program = program
        self.eqns = eqns
        self.boundary_bytes = int(boundary_bytes)
        self.has_unbounded_loops = has_unbounded_loops
        self.collectives: List[CollectiveCost] = list(collectives or [])
        self.flops = sum(e.flops for e in eqns)
        self.bytes_upper = sum(e.bytes for e in eqns)
        self.padding_waste_bytes = sum(e.padding_waste_bytes for e in eqns)
        self.by_primitive: Dict[str, Dict[str, int]] = {}
        for e in eqns:
            agg = self.by_primitive.setdefault(
                e.primitive, {"flops": 0, "bytes": 0, "count": 0,
                              "padding_waste_bytes": 0})
            agg["flops"] += e.flops
            agg["bytes"] += e.bytes
            agg["count"] += 1
            agg["padding_waste_bytes"] += e.padding_waste_bytes

    # -- roofline ----------------------------------------------------------
    @property
    def intensity(self) -> float:
        """flops/byte against the conservative (upper) byte bound."""
        return self.flops / max(self.bytes_upper, 1)

    @property
    def boundary_intensity(self) -> float:
        return self.flops / max(self.boundary_bytes, 1)

    def attainable_flops(self, spec: HardwareSpec) -> float:
        return spec.attainable_flops(self.intensity)

    def est_seconds(self, spec: HardwareSpec) -> float:
        """Static lower-bound step time: max of the compute roof and the
        memory roof (upper byte bound)."""
        return max(self.flops / spec.peak_flops,
                   self.bytes_upper / spec.hbm_bw)

    def roofline_fraction(self, spec: HardwareSpec,
                          measured_seconds: float) -> float:
        """Achieved / roofline-attainable FLOP/s for one measured
        execution of this program."""
        if measured_seconds <= 0:
            return 0.0
        attainable = self.attainable_flops(spec)
        if attainable <= 0:
            return 0.0
        return (self.flops / measured_seconds) / attainable

    # -- communication (the v3 comm model) --------------------------------
    @property
    def comm_bytes(self) -> int:
        """Total per-link ICI wire bytes across every collective, already
        x loop trips (never x shard count — all links run concurrently)."""
        return sum(c.wire_bytes * c.mult for c in self.collectives)

    def comm_bytes_by_axis(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for c in self.collectives:
            key = ",".join(c.axes)
            out[key] = out.get(key, 0) + c.wire_bytes * c.mult
        return out

    def comm_seconds(self, spec: Optional[HardwareSpec] = None) -> float:
        """Modelled serialized ICI time: every collective's wire time +
        per-hop latency, summed (worst case: nothing overlaps with other
        collectives)."""
        spec = spec or TARGET_SPEC
        return sum(c.comm_seconds(spec) * c.mult for c in self.collectives)

    def comm_seconds_by_axis(self, spec: Optional[HardwareSpec] = None
                             ) -> Dict[str, float]:
        spec = spec or TARGET_SPEC
        out: Dict[str, float] = {}
        for c in self.collectives:
            key = ",".join(c.axes)
            out[key] = out.get(key, 0.0) + c.comm_seconds(spec) * c.mult
        return out

    def overlap_fraction(self, spec: Optional[HardwareSpec] = None
                         ) -> float:
        """Comm-time-weighted fraction of modelled collective time that
        independent compute between issue point and first consumer can
        hide.  1.0 = every collective fully overlappable; 0.0 = every
        result consumed immediately (fully serialized)."""
        spec = spec or TARGET_SPEC
        total = 0.0
        hidden = 0.0
        for c in self.collectives:
            t = c.comm_seconds(spec) * c.mult
            total += t
            hidden += min(t, (c.overlap_flops / max(spec.peak_flops, 1.0))
                          * c.mult)
        if total <= 0:
            return 1.0
        return hidden / total

    def comm_roofline_fraction(self, spec: HardwareSpec,
                               measured_seconds: float) -> float:
        """Modelled ICI comm seconds / one measured execution — the comm
        analogue of :meth:`roofline_fraction` (how much of the wall clock
        the static comm model accounts for)."""
        if measured_seconds <= 0:
            return 0.0
        return self.comm_seconds(spec) / measured_seconds

    # -- presentation ------------------------------------------------------
    def summary(self, spec: Optional[HardwareSpec] = None) -> Dict[str, Any]:
        spec = spec or TARGET_SPEC
        out = {
            "program": self.program,
            "gflops": round(self.flops / 1e9, 3),
            "hbm_mib_upper": round(self.bytes_upper / 2**20, 2),
            "hbm_mib_boundary": round(self.boundary_bytes / 2**20, 2),
            "intensity_flops_per_byte": round(self.intensity, 3),
            "padding_waste_mib": round(self.padding_waste_bytes / 2**20, 4),
            "bound": ("compute" if self.intensity >= spec.ridge
                      else "memory"),
            "est_step_seconds": self.est_seconds(spec),
            "chip": spec.name,
            "unbounded_loops": self.has_unbounded_loops,
        }
        if self.collectives:
            out["comm_mib"] = round(self.comm_bytes / 2**20, 3)
            out["comm_seconds"] = self.comm_seconds(spec)
            out["comm_seconds_by_axis"] = self.comm_seconds_by_axis(spec)
            out["overlap_fraction"] = round(self.overlap_fraction(spec), 4)
            out["collective_count"] = len(self.collectives)
        return out

    def render(self, spec: Optional[HardwareSpec] = None,
               top: int = 5) -> str:
        spec = spec or TARGET_SPEC
        s = self.summary(spec)
        lines = [
            f"cost: {self.program}: {s['gflops']} GFLOP, "
            f"{s['hbm_mib_upper']} MiB HBM (boundary "
            f"{s['hbm_mib_boundary']} MiB), intensity "
            f"{s['intensity_flops_per_byte']} flop/B -> {s['bound']}-bound "
            f"on {spec.name} (ridge {spec.ridge:.0f}), est >= "
            f"{s['est_step_seconds'] * 1e3:.3f} ms/step, pad waste "
            f"{s['padding_waste_mib']} MiB"
            + (" [has unbounded while loops]"
               if self.has_unbounded_loops else "")
        ]
        hot = sorted(self.eqns, key=lambda e: -e.flops)[:top]
        if hot:
            lines.append("  hottest by FLOPs:")
            lines += ["    " + e.render() for e in hot if e.flops]
        heavy = sorted(self.eqns, key=lambda e: -e.bytes)[:top]
        if heavy:
            lines.append("  heaviest by bytes:")
            lines += ["    " + e.render() for e in heavy if e.bytes]
        if self.collectives:
            by_axis = self.comm_seconds_by_axis(spec)
            axis_txt = ", ".join(
                f"{k or '?'}: {v * 1e6:.1f} us" for k, v in
                sorted(by_axis.items()))
            lines.append(
                f"  comm: {self.comm_bytes / 2**20:.3f} MiB wire, "
                f"{self.comm_seconds(spec) * 1e6:.1f} us ICI "
                f"({axis_txt}), overlap fraction "
                f"{self.overlap_fraction(spec):.2f}")
            hot_c = sorted(self.collectives,
                           key=lambda c: -(c.wire_bytes * c.mult))[:top]
            lines += ["    " + c.render(spec) for c in hot_c]
        return "\n".join(lines)

    __str__ = render


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------

def _branch_jaxprs(params: Dict[str, Any]):
    out = []
    for v in params.get("branches", ()):
        out.append(v.jaxpr if isinstance(v, _CLOSED_JAXPR) else v)
    return out


class _Acc:
    def __init__(self):
        self.eqns: List[EqnCost] = []
        self.collectives: List[CollectiveCost] = []
        self.unbounded = False


def _shard_count(eqn) -> int:
    """Shards a ``shard_map`` eqn's body runs as: the product of the mesh
    axes the body handles manually (the eqn's ``manual_axes``; every mesh
    axis where it names none).  The body's jaxpr has PER-SHARD shapes, so its
    costs multiply by this to stay in global units.  Defensive: any
    unreadable params count as 1 (never crash a lint/cost pass on an odd
    jax version — the satellite contract of ISSUE 14)."""
    try:
        mesh = eqn.params.get("mesh")
        if mesh is None:
            return 1
        shape = dict(mesh.shape)
        manual = eqn.params.get("manual_axes") or frozenset(shape)
        n = 1
        for name, size in shape.items():
            if name in manual:
                n *= int(size)
        return max(n, 1)
    except Exception:  # noqa: BLE001 — cost model must never crash a walk
        return 1


def _eqn_bytes(eqn) -> int:
    return (sum(_nbytes(v) for v in eqn.invars)
            + sum(_nbytes(v) for v in eqn.outvars))


def _cost_walk(jaxpr, acc: _Acc, mult: int, depth: int = 0,
               axis_sizes: Optional[Dict[str, int]] = None,
               loop_mult: int = 1):
    """``mult`` keeps flops/bytes in GLOBAL units (loop trips x shard
    count); ``loop_mult`` is the trips-only multiplier collectives use
    (per-link wire time is concurrent across shards, never x shards).
    ``axis_sizes`` carries the enclosing shard_map mesh's axis sizes so
    collective eqns can resolve their axis names."""
    if depth > 32:  # defensive: malformed/cyclic params
        return
    axis_sizes = axis_sizes or {}
    eqns = list(jaxpr.eqns)
    for i, eqn in enumerate(eqns):
        prim = eqn.primitive.name
        subs = list(_sub_jaxprs(eqn.params))
        if subs:
            # call-like eqns contribute their bodies only (counting both
            # the call's operands and the body would double-count)
            if prim == "scan":
                length = int(eqn.params.get("length", 1) or 1)
                for sub in subs:
                    _cost_walk(sub, acc, mult * max(length, 1), depth + 1,
                               axis_sizes, loop_mult * max(length, 1))
            elif prim == "shard_map":
                # per-shard body shapes x shard count = global totals
                shards = _shard_count(eqn)
                child_axes = dict(axis_sizes)
                child_axes.update(mesh_axis_sizes(eqn.params.get("mesh")))
                for sub in subs:
                    _cost_walk(sub, acc, mult * shards, depth + 1,
                               child_axes, loop_mult)
            elif prim == "while":
                acc.unbounded = True
                for sub in subs:
                    _cost_walk(sub, acc, mult, depth + 1, axis_sizes,
                               loop_mult)
            elif prim == "cond":
                # worst case: the most FLOP-expensive branch
                best: Optional[_Acc] = None
                for sub in _branch_jaxprs(eqn.params) or subs:
                    probe = _Acc()
                    _cost_walk(sub, probe, mult, depth + 1, axis_sizes,
                               loop_mult)
                    if best is None or (sum(e.flops for e in probe.eqns)
                                        > sum(e.flops for e in best.eqns)):
                        best = probe
                if best is not None:
                    acc.eqns.extend(best.eqns)
                    acc.collectives.extend(best.collectives)
                    acc.unbounded = acc.unbounded or best.unbounded
            else:
                for sub in subs:
                    _cost_walk(sub, acc, mult, depth + 1, axis_sizes,
                               loop_mult)
            continue
        if prim in COLLECTIVE_PRIMS:
            cc = _collective_cost(eqn, eqns, i, axis_sizes, loop_mult)
            if cc is not None:
                acc.collectives.append(cc)
        flops = eqn_flops(eqn)
        nbytes = _eqn_bytes(eqn)
        waste = _eqn_padding_waste(eqn)
        if flops == 0 and nbytes == 0:
            continue
        acc.eqns.append(EqnCost(
            primitive=prim,
            flops=flops * mult,
            bytes=nbytes * mult,
            padding_waste_bytes=waste * mult,
            mult=mult,
            out="/".join(_fmt_aval(v) for v in eqn.outvars),
            provenance=_provenance(eqn),
        ))


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def cost_jaxpr(closed, program: str = "<program>") -> CostReport:
    """Cost a ``ClosedJaxpr`` (or ``Jaxpr``)."""
    jaxpr = closed.jaxpr if isinstance(closed, _CLOSED_JAXPR) else closed
    acc = _Acc()
    _cost_walk(jaxpr, acc, 1)
    boundary = (sum(_nbytes(v) for v in jaxpr.invars)
                + sum(_nbytes(v) for v in jaxpr.outvars))
    return CostReport(program, acc.eqns, boundary,
                      has_unbounded_loops=acc.unbounded,
                      collectives=acc.collectives)


def cost(fn, *args, static_argnums=(), program: Optional[str] = None,
         **kwargs) -> CostReport:
    """Trace ``fn(*args, **kwargs)`` abstractly (args may be
    ``jax.ShapeDtypeStruct``s — nothing executes) and cost the jaxpr."""
    closed = jax.make_jaxpr(fn, static_argnums=tuple(static_argnums))(
        *args, **kwargs)
    return cost_jaxpr(closed,
                      program=program or getattr(fn, "__name__", "<fn>"))


# -- the jit.to_static hook registry (mirrors graph_lint.reports()) --------

_COST_LOCK = threading.Lock()
_COST_REPORTS: List[CostReport] = []
_MAX_COST_REPORTS = 256


def cost_reports() -> List[CostReport]:
    """CostReports collected by the ``FLAGS_graph_cost`` compile hook."""
    with _COST_LOCK:
        return list(_COST_REPORTS)


def clear_cost_reports():
    with _COST_LOCK:
        _COST_REPORTS.clear()


def _record(report: CostReport):
    with _COST_LOCK:
        _COST_REPORTS.append(report)
        del _COST_REPORTS[:-_MAX_COST_REPORTS]


def cost_static_program(pure_fn, arg_structs, mut_structs, ro_structs,
                        program: str, jaxpr=None) -> CostReport:
    """Cost one ``jit.to_static`` compiled entry (same calling convention
    as ``graph_lint.lint_static_program``) and record it in
    :func:`cost_reports`.  Pass an already-traced ``jaxpr`` to skip the
    abstract trace (the compile hook shares one trace with the linter)."""
    closed = (jaxpr if jaxpr is not None
              else jax.make_jaxpr(pure_fn)(arg_structs, mut_structs,
                                           ro_structs))
    report = cost_jaxpr(closed, program=program)
    _record(report)
    return report
