"""Trace-and-compile: the dy2static analog, TPU-first.

Reference: python/paddle/jit/api.py:233 ``to_static`` +
dy2static/program_translator.py (StaticFunction/ConcreteProgram/
PartialProgramLayer executing a captured ProgramDesc via run_program op).

TPU-native redesign: instead of AST-rewriting python into a ProgramDesc and
interpreting it, we *functionalize* the imperative program into a single
jitted XLA computation:

1. A first "scout" call runs eagerly while logging (a) every leaf Tensor the
   function reads (captured state: parameters, buffers, RNG keys, optimizer
   moments) and (b) every Tensor whose value is re-bound (mutations:
   optimizer updates, RNG advance, buffer writes).
2. Subsequent calls execute a cached ``jax.jit`` program whose inputs are
   (example args + captured state) and whose outputs are (results + mutated
   state), written back after each call.

The whole train step — forward, ``loss.backward()``'s VJP chain, and the
optimizer update — traces into ONE fused program: XLA sees the entire graph,
so there is no per-op dispatch, no interpreter, and remat/fusion apply
globally. This is why eager-mode overhead does not bound performance
(SURVEY.md §7 "hard parts" (a)).
"""
from __future__ import annotations

import functools
import gc
import os
import sys
import threading
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..tensor import Tensor
from ..ops import dispatch
from ..telemetry import trace as _ttrace


class AbstractScoutUnsupported(RuntimeError):
    """Raised when the zero-compute capture pass cannot represent the traced
    function (data-dependent python control flow, host reads of tensor
    values, lazily-created state with data-dependent init).  jit.to_static
    falls back to the eager warmup+scout protocol — unless ``poisoned`` is
    set, meaning restore could not scrub a leaked tracer out of persistent
    state and an eager re-run would crash on it."""

    def __init__(self, msg, poisoned: bool = False):
        super().__init__(msg)
        self.poisoned = poisoned


class _JitState(threading.local):
    def __init__(self):
        self.tracing = False


_jit_state = _JitState()


def in_tracing() -> bool:
    return _jit_state.tracing


def _tree_flatten(obj, tensors: List[Tensor]):
    """Flatten nested python containers, extracting Tensors; returns a spec."""
    if isinstance(obj, Tensor):
        tensors.append(obj)
        return ("t", len(tensors) - 1)
    if isinstance(obj, (list, tuple)):
        specs = [_tree_flatten(o, tensors) for o in obj]
        return ("seq", type(obj).__name__, specs)
    if isinstance(obj, dict):
        keys = list(obj.keys())
        specs = [_tree_flatten(obj[k], tensors) for k in keys]
        return ("dict", keys, specs)
    return ("leaf", obj)


def _tree_unflatten(spec, raws):
    kind = spec[0]
    if kind == "t":
        return Tensor(raws[spec[1]])
    if kind == "seq":
        seq = [_tree_unflatten(s, raws) for s in spec[2]]
        return tuple(seq) if spec[1] == "tuple" else seq
    if kind == "dict":
        return {k: _tree_unflatten(s, raws) for k, s in zip(spec[1], spec[2])}
    return spec[1]


def _sig_of(tensors: List[Tensor], static_repr: str):
    return (
        tuple((tuple(t._value.shape), str(t._value.dtype)) for t in tensors),
        static_repr,
    )


def _lowering_struct(t: Tensor):
    """Abstract stand-in for a Tensor's value.  A committed array keeps its
    sharding, so lowering the stand-ins reproduces the call (and finds its
    trace in jit's cache); an uncommitted one goes where the rest go."""
    v = t._value
    committed = isinstance(v, jax.Array) and v.committed
    return jax.ShapeDtypeStruct(
        tuple(v.shape), v.dtype, sharding=v.sharding if committed else None)


class _CompiledEntry:
    __slots__ = (
        "jitted",
        "captured",
        "mut_caps",
        "ro_caps",
        "mutated_order",
        "out_spec",
        "n_args",
        "arg_structs",
        "gen_threshold",
        "stale_ordinals",
        "_scout_result",
        "lint_report",
        "cost_report",
        "span_args",
        "__weakref__",      # the tracer remembers dispatched entries weakly
    )

    def __init__(self):
        self.jitted = None
        # creation ordinals (within fn's run) of per-call "result attribute"
        # tensors — created fresh each call with trace-dependent values
        # (e.g. layer.aux_loss) — functionalized as extra program outputs
        self.stale_ordinals: List[tuple] = []
        self.captured: List[Tensor] = []
        # captured state split by the scout pass: tensors the function
        # re-binds (params, moments, RNG state) vs read-only state.  The
        # mutated ones are DONATED to XLA (jax.jit donate_argnums) so the
        # update aliases into the same HBM buffers instead of
        # double-buffering params+moments across the step — the analog of
        # the reference's inplace op outputs (paddle inplace pass).
        self.mut_caps: List[Tensor] = []
        self.ro_caps: List[Tensor] = []
        self.mutated_order: List[Tensor] = []
        self.out_spec = None
        self.n_args = 0
        self.arg_structs: List[Any] = []
        self.gen_threshold = 0
        self._scout_result = None
        # LintReport from the FLAGS_graph_lint compile hook (None when the
        # flag is off or the lint itself failed)
        self.lint_report = None
        # CostReport from the FLAGS_graph_cost compile hook (same contract)
        self.cost_report = None
        # cached telemetry span metadata (the CostReport digest attached
        # to this program's dispatch spans; built lazily on first traced
        # dispatch — see _span_args)
        self.span_args = None


# every StaticFunction ever built (weak): the GL007 retrace-churn pass
# reads each fn's code-cache size to spot shape-churning to_static calls
_STATIC_REGISTRY: "weakref.WeakSet[StaticFunction]" = weakref.WeakSet()

# HardwareSpec for the roofline estimate attached to dispatch spans
# (resolved once per process; None = the device is not a known chip)
_SPAN_SPEC: List[Any] = []


def _span_spec():
    if not _SPAN_SPEC:
        from ..analysis import chip_spec

        try:
            _SPAN_SPEC.append(chip_spec(jax.devices()[0].device_kind))
        except ValueError:
            # not a chip in the table (the CPU backend): the spans carry
            # no roofline estimate rather than another chip's
            _SPAN_SPEC.append(None)
    return _SPAN_SPEC[0]


def _span_args(entry) -> dict:
    """Telemetry metadata for one compiled program's dispatch span: the
    static CostReport digest + the roofline-estimated step time, so a
    span's measured duration can be read against the model's bound
    directly in the trace viewer.  Empty when FLAGS_graph_cost was off
    at compile time.  Cached on the entry."""
    a = entry.span_args
    if a is None:
        a = {}
        c = entry.cost_report
        if c is not None:
            a = {"program": c.program,
                 "gflop": round(c.flops / 1e9, 3),
                 "hbm_mib_upper": round(c.bytes_upper / 2 ** 20, 2),
                 "intensity": round(c.intensity, 2)}
            spec = _span_spec()
            if spec is not None:
                try:
                    a["roofline_est_ms"] = round(c.est_seconds(spec) * 1e3, 4)
                    a["chip"] = spec.name
                except Exception:  # noqa: BLE001 — best-effort
                    pass
        entry.span_args = a
    return a


def _lowered(entry: _CompiledEntry):
    return entry.jitted.lower(
        entry.arg_structs,
        [_lowering_struct(t) for t in entry.mut_caps],
        [_lowering_struct(t) for t in entry.ro_caps])


def _compiled_text(lowered) -> str:
    """The optimized HLO text of ``lowered`` with THIS build's ``op_name``s.
    The executable that runs may carry another build's: jax leaves metadata
    out of the persistent compile cache's key, so a cache filled before a
    scope was added or renamed hands the old text back.  So compile anew:
    a compiler option (this one at XLA's default) makes jax pass over the
    executable it holds, and for this one compile the cache's key takes
    the metadata in.  Metadata changes no instruction: the names are those
    of the program that runs.  A miss is a whole compile, a second ask of
    the same build a read of the cache."""
    name = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        return lowered.compile(compiler_options={"xla_dump_to": ""}).as_text()
    finally:
        jax.config.update(name, was)


def _entry_op_scopes(entry: _CompiledEntry) -> Dict[str, Any]:
    from .. import sysconfig
    from ..telemetry import scopes as _scopes

    lowered = _lowered(entry)
    found = _scopes.scopes_of_hlo_text(_compiled_text(lowered))
    if (not any(s.rule == "own" for s in found.values())
            and _scopes.has_scopes(lowered.as_text(debug_info=True))):
        # what _compiled_text guards against, should a cache hand it back
        # all the same
        raise RuntimeError(
            "the program has named scopes and its compiled text has none: "
            "the executable came from a compile cache filled by a build "
            "without them (the cache's key leaves metadata out); clear "
            f"{sysconfig.compile_cache_dir()!r} and run again")
    return found


class StaticFunction:
    """Callable wrapping a compiled imperative function
    (reference program_translator.py:305)."""

    def __init__(self, fn, input_spec=None, build_strategy=None, backend=None):
        # AST dy2static pass (reference program_translator.py:305 applies
        # DygraphToStaticAst before tracing): native if/while over traced
        # Tensors become runtime-dispatched cond/while_loop sites
        from .dy2static import convert_to_static

        self._fn = convert_to_static(fn)
        self._cache: Dict[Any, _CompiledEntry] = {}
        # compiled-program executions (shared holder so bound copies from
        # __get__ keep one count); bench/gates read dispatch_count to
        # assert "one program dispatch per train step"
        self._dispatches: List[int] = [0]
        functools.update_wrapper(self, fn)
        _STATIC_REGISTRY.add(self)

    @property
    def code_cache(self):
        return self._cache

    @property
    def dispatch_count(self) -> int:
        return self._dispatches[0]

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = StaticFunction.__new__(StaticFunction)
        bound._fn = self._fn.__get__(instance, owner)
        bound._cache = self._cache  # share compiled programs per class fn
        bound._dispatches = self._dispatches
        return bound

    def __call__(self, *args, **kwargs):
        arg_tensors: List[Tensor] = []
        arg_spec = _tree_flatten((args, kwargs), arg_tensors)
        key = _sig_of(arg_tensors, repr(arg_spec))
        bound_self = getattr(self._fn, "__self__", None)
        if bound_self is not None:
            key = (key, id(bound_self))

        entry = self._cache.get(key)
        if entry is None:
            if os.environ.get("PADDLE_TPU_EAGER_SCOUT"):
                # forced legacy protocol: eager warmup, then eager scout
                entry = _CompiledEntry()
                self._cache[key] = entry
                return self._fn(*args, **kwargs)
            # default: ABSTRACT scout — capture reads/mutations under
            # jax.eval_shape (zero FLOPs, zero intermediate HBM), compile,
            # and run the compiled program.  No eager step of the model is
            # ever executed, so peak residency never exceeds the compiled
            # step's (critical for models near the HBM limit; round-3
            # postmortem: two eager 1.3B steps OOMed a v5e before the
            # donated compiled path existed).
            try:
                return self._abstract_compile_and_run(
                    key, args, kwargs, arg_tensors)
            except AbstractScoutUnsupported as e:
                from .dy2static import Dy2StaticUnsupported

                if isinstance(e.__cause__, Dy2StaticUnsupported):
                    # a tensor-dependent control-flow site that cannot be
                    # functionalized will fail at compile regardless of the
                    # scout protocol — surface the precise error now
                    raise e.__cause__ from None
                if e.poisoned:
                    # a tracer is stuck in persistent state the restore
                    # could not scrub; an eager re-run would crash on it
                    raise RuntimeError(
                        "jit.to_static abstract scout failed and left "
                        f"unrecoverable state ({e}); run the whole program "
                        "with PADDLE_TPU_EAGER_SCOUT=1") from e
                # NOTE: the scout already executed the function's python
                # body once (tensor effects restored, python-level effects
                # like counters are not) — the eager fallback re-runs it.
                sys.stderr.write(
                    f"[paddle_tpu.jit] abstract scout unavailable for "
                    f"{getattr(self._fn, '__name__', '?')} ({e}); falling "
                    "back to eager warmup+scout\n")
                entry = _CompiledEntry()
                self._cache[key] = entry
                return self._fn(*args, **kwargs)
        if entry.jitted is None:
            entry = self._scout_and_compile(key, args, kwargs, arg_tensors)
            # scout call already produced results eagerly
            return entry._scout_result
        tracer = _ttrace._tracer
        if tracer is not None:
            # telemetry span per compiled dispatch, carrying the program's
            # static CostReport digest (when FLAGS_graph_cost was on at
            # compile) so the exported trace shows measured-vs-roofline
            # per fused step.  The tracer also keeps the entry, so that
            # Tracer.program_scopes() can map the device trace's operations
            # to scopes AFTER the window.  Disabled path: ONE module-global
            # read.
            name = self._span_name()
            tracer.saw_program(name, entry, _entry_op_scopes)
            with _ttrace.span(name, **_span_args(entry)):
                return self._run_compiled(entry, arg_tensors)
        return self._run_compiled(entry, arg_tensors)

    def _span_name(self) -> str:
        return f"jit.{getattr(self._fn, '__name__', 'program')}"

    def _run_compiled(self, entry, arg_tensors):
        self._dispatches[0] += 1
        raw_args = [t._value for t in arg_tensors]
        raw_mut = [t._value for t in entry.mut_caps]
        raw_ro = [t._value for t in entry.ro_caps]
        out_raws, new_states = entry.jitted(raw_args, raw_mut, raw_ro)
        for t, v in zip(entry.mutated_order, new_states):
            t._value = v  # direct write; no re-logging
        return _tree_unflatten(entry.out_spec, list(out_raws))

    # -- compilation -------------------------------------------------------
    def _abstract_compile_and_run(self, key, args, kwargs, arg_tensors):
        """Zero-compute capture: trace the function under ``jax.eval_shape``
        (every op abstract — no FLOPs, no intermediate HBM), discover the
        captured/mutated state exactly like the eager scout, restore all
        python-visible effects, then compile and RUN the jitted program.

        This replaces the legacy eager warmup+scout protocol (two full eager
        steps before the donated compiled path exists) — on a model near the
        HBM limit the eager steps' activation residency (no remat applies in
        eager mode) is what OOMs, not the compiled step."""
        from .. import tensor as _tensor_mod

        entry = _CompiledEntry()
        _tensor_mod._GENERATION[0] += 1
        threshold = _tensor_mod._GENERATION[0]
        entry.gen_threshold = threshold

        read_log: Dict[int, Tensor] = {}
        mut_log: Dict[int, Tensor] = {}
        creation_log: Dict[int, tuple] = {}
        orig_vals: Dict[int, Any] = {}
        orig_grads: Dict[int, tuple] = {}
        out_state: Dict[str, Any] = {}
        ts = dispatch._trace_state
        arg_snap = [(t, t._value, t.grad) for t in arg_tensors]

        def scout(raw_args):
            prev = (ts.read_log, ts.read_epoch, ts.mutation_log)
            st = _tensor_mod._SCOUT_STATE
            prev_scout = (st.creation_log, st.orig_values, st.orig_grads)
            ts.read_log, ts.read_epoch, ts.mutation_log = (
                read_log, threshold, mut_log)
            st.creation_log, st.orig_values, st.orig_grads = (
                creation_log, orig_vals, orig_grads)
            try:
                for t, rv in zip(arg_tensors, raw_args):
                    t._value = rv
                res = self._fn(*args, **kwargs)
                outs: List[Tensor] = []
                out_state["out_spec"] = _tree_flatten(res, outs)
                return tuple(o._value for o in outs)
            finally:
                ts.read_log, ts.read_epoch, ts.mutation_log = prev
                st.creation_log, st.orig_values, st.orig_grads = prev_scout

        structs = tuple(
            jax.ShapeDtypeStruct(tuple(t._value.shape), t._value.dtype)
            for t in arg_tensors)
        try:
            jax.eval_shape(scout, structs)
        except Exception as e:
            # Restore-only (no persistence detection): the in-flight
            # exception's traceback frames pin scout-created tensors alive,
            # so an aliveness check here would misclassify temporaries as
            # persistent state.  Genuine bugs re-raise cleanly on the eager
            # fallback call.  Known limitation: lazily-created persistent
            # state with a trace-dependent init cannot be scrubbed here and
            # would surface as an UnexpectedTracerError in the fallback.
            self._restore_after_scout(arg_snap, read_log, mut_log,
                                      creation_log, orig_vals, orig_grads,
                                      threshold, check_persistent=False)
            raise AbstractScoutUnsupported(f"{type(e).__name__}: {e}") from e

        persistents, mut_pre, stale = self._restore_after_scout(
            arg_snap, read_log, mut_log, creation_log, orig_vals, orig_grads,
            threshold)
        entry.stale_ordinals = stale

        arg_ids = {id(t) for t in arg_tensors}
        captured = [t for tid, t in read_log.items() if tid not in arg_ids]
        created_ids = {id(t) for t in persistents}
        # pre-existing mutated tensors must be carried even if never read
        for tid, t in mut_pre.items():
            if tid not in arg_ids and not any(t is c for c in captured):
                captured.append(t)
        captured.extend(persistents)
        entry.captured = captured
        mut_ids = set(mut_pre.keys()) | created_ids
        entry.mut_caps = [t for t in captured if id(t) in mut_ids]
        entry.ro_caps = [t for t in captured if id(t) not in mut_ids]
        entry.n_args = len(arg_tensors)
        entry.out_spec = out_state["out_spec"]

        self._install_jitted(entry, args, kwargs)
        self._cache[key] = entry
        self._commit_captured(entry, arg_tensors)
        return self._run_compiled(entry, arg_tensors)

    @staticmethod
    def _commit_captured(entry, arg_tensors):
        """jit runs a program where its committed inputs live and moves
        the uncommitted ones there on every call; donated state then comes
        back committed, and the second call — same shapes, new input
        shardings — compiles a second executable.  When the committed
        inputs share one multi-device mesh, replicate the captured state
        still sitting uncommitted on its creation device onto that mesh
        once, before the first run."""
        from jax.sharding import NamedSharding, PartitionSpec

        def arrays(tensors):
            return [(t, t._value) for t in tensors
                    if isinstance(t._value, jax.Array)]

        meshes = {v.sharding.mesh
                  for _, v in arrays([*arg_tensors, *entry.captured])
                  if v.committed and isinstance(v.sharding, NamedSharding)}
        if len(meshes) != 1:
            return
        mesh = meshes.pop()
        if mesh.size < 2:
            return
        replicated = NamedSharding(mesh, PartitionSpec())
        for t, v in arrays(entry.captured):
            if not v.committed:
                t._value = jax.device_put(v, replicated)

    @staticmethod
    def _restore_after_scout(arg_snap, read_log, mut_log, creation_log,
                             orig_vals, orig_grads, threshold,
                             check_persistent=True):
        """Undo every python-visible effect of the abstract scout: re-bind
        original values into arg + mutated tensors, restore pre-trace grad
        bindings exactly (a param's accumulated eager grad must survive the
        capture pass), and return (persistents, mut_pre): the
        created-and-persistent tensors (lazily-created state) restored to
        their concrete init values, and the pre-existing mutated tensors
        (id -> Tensor).  CONSUMES mut_log, orig_vals and orig_grads — their
        strong references must be gone before the aliveness gc below, or
        every trace-created tensor that was mutated in place (e.g. grads
        under clip_grad_norm_) reads as persistent.  Raises when a
        persistent created tensor has a trace-dependent init — it cannot be
        materialized without running the function for real."""
        def is_tracer(v):
            return isinstance(v, jax.core.Tracer)

        for t, v in orig_vals.values():
            t._value = v
        # every grad rebind during the scout was recorded with its
        # pre-trace binding (Tensor.grad setter hook): restore exactly —
        # concrete accumulated grads survive, tracer grads vanish
        for t, g in orig_grads.values():
            t._grad = g
        # args AFTER orig_vals/orig_grads: a mutated arg's "pre-mutation"
        # value is the bound tracer — the snapshot holds its true values
        for t, v, g in arg_snap:
            t._value = v
            t._grad = g
        created = list(creation_log.values())
        creation_log.clear()
        orig_grads.clear()
        # drop loop bindings: a leftover reference in THIS frame would
        # survive the gc.collect() below and misclassify the last created
        # temporary as persistent state
        t = g = None
        if not check_persistent:
            # failure path: re-bind concrete init values where known and
            # stop — no aliveness classification (see caller)
            for t, fv in created:
                rv = orig_vals.get(id(t), (None, fv))[1]
                if not is_tracer(rv):
                    t._value = rv
            mut_log.clear()
            orig_vals.clear()
            return [], {}, []
        refs = [(i, weakref.ref(t), orig_vals.get(id(t), (None, fv))[1])
                for i, (t, fv) in enumerate(created)]
        mut_pre = {tid: t for tid, t in mut_log.items()
                   if t._gen < threshold}
        mut_log.clear()
        orig_vals.clear()
        del created
        t = None
        gc.collect()
        persistents = []
        stale: List[tuple] = []
        for i, r, fv in refs:
            t = r()
            if t is None:
                continue
            if is_tracer(fv):
                # per-call "result attribute" (layer.aux_loss style): a
                # tensor CREATED each call with a trace-dependent value and
                # stashed on a python object.  Functionalized as an extra
                # program output keyed by its creation ordinal — the
                # compiled trace recreates it at the same ordinal and the
                # writeback keeps the attribute fresh after every call.
                stale.append((i, tuple(fv.shape), str(fv.dtype)))
                continue
            t._value = fv
            persistents.append(t)
        return persistents, mut_pre, stale

    def _scout_and_compile(self, key, args, kwargs, arg_tensors):
        entry = self._cache.get(key) or _CompiledEntry()

        # 1. scout: run eagerly, log reads of leaf tensors + mutations
        from .. import tensor as _tensor_mod

        _tensor_mod._GENERATION[0] += 1
        threshold = _tensor_mod._GENERATION[0]
        entry.gen_threshold = threshold

        read_log: Dict[int, Tensor] = {}
        mut_log: Dict[int, Tensor] = {}
        prev_read = dispatch._trace_state.read_log
        prev_epoch = dispatch._trace_state.read_epoch
        prev_mut = dispatch._trace_state.mutation_log
        dispatch._trace_state.read_log = read_log
        dispatch._trace_state.read_epoch = threshold
        dispatch._trace_state.mutation_log = mut_log
        try:
            result = self._fn(*args, **kwargs)
        finally:
            dispatch._trace_state.read_log = prev_read
            dispatch._trace_state.read_epoch = prev_epoch
            dispatch._trace_state.mutation_log = prev_mut

        arg_ids = {id(t) for t in arg_tensors}
        captured = [t for tid, t in read_log.items() if tid not in arg_ids]
        # pre-existing mutated tensors must be carried even if never read
        for tid, t in mut_log.items():
            if tid not in arg_ids and t._gen < threshold and not any(
                t is c for c in captured
            ):
                captured.append(t)
        entry.captured = captured
        # split: state the scout saw re-bound is donated; read-only is not
        mut_ids = set(mut_log.keys())
        entry.mut_caps = [t for t in captured if id(t) in mut_ids]
        entry.ro_caps = [t for t in captured if id(t) not in mut_ids]
        entry.n_args = len(arg_tensors)

        out_tensors: List[Tensor] = []
        entry.out_spec = _tree_flatten(result, out_tensors)
        entry._scout_result = result  # type: ignore[attr-defined]

        self._install_jitted(entry, args, kwargs)
        self._cache[key] = entry
        return entry

    def _install_jitted(self, entry, args, kwargs):
        """Build the pure function over (args, mut-captured, ro-captured)
        and jit it with the mutated state donated."""
        fn = self._fn
        mut_list = entry.mut_caps
        ro_list = entry.ro_caps
        arg_list: List[Tensor] = []
        arg_spec = _tree_flatten((args, kwargs), arg_list)
        # the trace rebuilds arg Tensors from raw values — preserve each
        # arg's stop_gradient so differentiating w.r.t. an input works
        arg_sgs = [t.stop_gradient for t in arg_list]
        arg_structs = [
            jax.ShapeDtypeStruct(tuple(t._value.shape), t._value.dtype)
            for t in arg_list]
        entry.arg_structs = [_lowering_struct(t) for t in arg_list]
        del arg_list

        def pure_fn(raw_args, raw_mut, raw_ro):
            from .. import tensor as _tensor_mod

            # bind tracers into the live Tensor objects, run, then restore
            cap_pairs = list(zip(mut_list, raw_mut)) + list(zip(ro_list, raw_ro))
            snapshot = [(t, t._value, t.grad) for t, _ in cap_pairs]
            mut: Dict[int, Tensor] = {}
            prev_m = dispatch._trace_state.mutation_log
            prev_t = _jit_state.tracing
            dispatch._trace_state.mutation_log = mut
            _jit_state.tracing = True
            st = _tensor_mod._SCOUT_STATE
            prev_cl = st.creation_log
            clog: Dict[int, tuple] = {}
            try:
                for t, rv in cap_pairs:
                    t._value = rv
                a, kw = _tree_unflatten(arg_spec, list(raw_args))
                rebuilt: List[Tensor] = []
                _tree_flatten((a, kw), rebuilt)
                for rt, sg in zip(rebuilt, arg_sgs):
                    rt.stop_gradient = sg
                if entry.stale_ordinals:
                    # track creations so per-call result attributes can be
                    # matched by ordinal (scout discovered them)
                    st.creation_log = clog
                res = fn(*a, **kw)
                st.creation_log = prev_cl
                outs: List[Tensor] = []
                _tree_flatten(res, outs)
                out_raws = tuple(o._value for o in outs)
                # stable mutation order: ALL donated tensors first (their
                # final values alias the donated input buffers — tensors the
                # trace didn't touch pass through unchanged), then any other
                # pre-existing mutated tensors discovered during the trace;
                # call-local tensors die with the call
                order = list(mut_list)
                extra = [
                    t
                    for t in mut.values()
                    if t._gen < entry.gen_threshold
                    and not any(t is o for o in order)
                    and not any(t is r for r in ro_list)
                ]
                order.extend(extra)
                ro_mutated = [t for t in ro_list if id(t) in mut]
                order.extend(ro_mutated)
                if entry.stale_ordinals:
                    created = list(clog.values())
                    for i, shape, dtype in entry.stale_ordinals:
                        if i >= len(created):
                            raise AbstractScoutUnsupported(
                                "per-call result attribute not recreated at "
                                f"creation ordinal {i} in the compiled "
                                "trace; set PADDLE_TPU_EAGER_SCOUT=1")
                        t_new = created[i][0]
                        if (tuple(t_new._value.shape) != shape
                                or str(t_new._value.dtype) != dtype):
                            raise AbstractScoutUnsupported(
                                f"creation ordinal {i} shape/dtype mismatch"
                                f" ({tuple(t_new._value.shape)}:"
                                f"{t_new._value.dtype} vs {shape}:{dtype});"
                                " set PADDLE_TPU_EAGER_SCOUT=1")
                        order.append(t_new)
                entry.mutated_order = order
                new_states = tuple(t._value for t in order)
                return out_raws, new_states
            finally:
                dispatch._trace_state.mutation_log = prev_m
                _jit_state.tracing = prev_t
                st.creation_log = prev_cl
                for t, v, g in snapshot:
                    t._value = v
                    t.grad = g

        entry.jitted = jax.jit(pure_fn, donate_argnums=(1,))
        self._maybe_analyze(entry, pure_fn, arg_structs)

    def _maybe_analyze(self, entry, pure_fn, arg_structs):
        """FLAGS_graph_lint / FLAGS_graph_cost compile hooks (env:
        PADDLE_TPU_GRAPH_LINT / PADDLE_TPU_GRAPH_COST): lint and/or
        roofline-cost the program being installed.  ONE shared abstract
        trace (zero compute) feeds both analyses — `tools/graph_lint.py
        --cost` turns both on and must not trace twice.  Reports land on
        the entry (`lint_report` / `cost_report`) + the analysis
        registries; bench.py reads cost reports for *_roofline_fraction
        lines."""
        from ..core import flags as _flags

        def _on(flag_name):
            try:
                return bool(_flags.flag(flag_name))
            except KeyError:  # pragma: no cover - registry always has them
                return False

        want_lint = _on("FLAGS_graph_lint")
        want_cost = _on("FLAGS_graph_cost")
        if not (want_lint or want_cost):
            return
        name = getattr(self._fn, "__name__", None) or "to_static_fn"
        mk = lambda t: jax.ShapeDtypeStruct(  # noqa: E731
            tuple(t._value.shape), t._value.dtype)
        try:
            mut_structs = [mk(t) for t in entry.mut_caps]
            ro_structs = [mk(t) for t in entry.ro_caps]
            closed = jax.make_jaxpr(pure_fn)(arg_structs, mut_structs,
                                             ro_structs)
        except Exception as e:  # noqa: BLE001 — analysis must never break compile
            sys.stderr.write(
                f"[paddle_tpu.graph_lint] abstract trace of '{name}' "
                f"failed: {type(e).__name__}: {e}\n")
            return
        if want_lint:
            from .. import analysis as _analysis

            try:
                entry.lint_report = _analysis.lint_static_program(
                    pure_fn, arg_structs, mut_structs, ro_structs,
                    program=name, jaxpr=closed)
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(
                    f"[paddle_tpu.graph_lint] lint of '{name}' failed: "
                    f"{type(e).__name__}: {e}\n")
        if want_cost:
            from ..analysis import cost_static_program as _cost_static

            try:
                entry.cost_report = _cost_static(
                    pure_fn, arg_structs, mut_structs, ro_structs,
                    program=name, jaxpr=closed)
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(
                    f"[paddle_tpu.graph_cost] cost of '{name}' failed: "
                    f"{type(e).__name__}: {e}\n")

    def _entries(self) -> List[_CompiledEntry]:
        return [e for e in self._cache.values() if e.jitted is not None]

    def lowered_texts(self) -> List[str]:
        """StableHLO text of every compiled entry — the program XLA was
        handed, Mosaic custom calls included, each operation with the
        ``named_scope`` path that made it (``loc("jit(..)/train.forward/
        ..")``).  Lowers from jit's cached trace: no compile, nothing
        runs."""
        return [_lowered(e).as_text(debug_info=True) for e in self._entries()]

    def op_scopes(self) -> List[Dict[str, Any]]:
        """For every compiled entry, the OPTIMIZED program's instructions by
        name (what a device trace prints after ``%``: ``copy.117``,
        ``fusion.375``, ``all-reduce.41``), each with the
        ``telemetry.scopes.OpScope`` it falls under.  Compiles each entry's
        text (a read of the compile cache where one is on): seconds for a
        large step, so never inside one."""
        return [_entry_op_scopes(e) for e in self._entries()]

    def lint_reports(self):
        """LintReports of every compiled entry (FLAGS_graph_lint runs)."""
        return [e.lint_report for e in self._cache.values()
                if e.lint_report is not None]

    def cost_reports(self):
        """CostReports of every compiled entry (FLAGS_graph_cost runs)."""
        return [e.cost_report for e in self._cache.values()
                if e.cost_report is not None]


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """Decorator/wrapper compiling an imperative function
    (reference jit/api.py:233)."""

    def decorate(fn):
        if isinstance(fn, StaticFunction):
            return fn
        # wrapping a Layer: compile its forward
        from ..nn.layer import Layer

        if isinstance(fn, Layer):
            layer = fn
            layer.forward = StaticFunction(layer.forward)
            return layer
        return StaticFunction(fn, input_spec, build_strategy, backend)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._paddle_tpu_not_to_static = True
    return fn
