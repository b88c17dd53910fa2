"""Program scopes: from what a device trace calls an operation (``copy.117``,
``fusion.375``) to the part of the program that made it.  The compiled
programs wrap their parts in ``jax.named_scope`` under the fixed names of
:data:`SCOPES` (an interface: docs/observability.md "Program scopes");
:func:`scopes_of_hlo_text` reads them back from the ``op_name`` metadata of a
compiled module's text.  Nothing here imports jax."""
from __future__ import annotations

import re
from collections import namedtuple
from typing import Dict, List, NamedTuple, Optional

__all__ = ["SCOPES", "UNSCOPED", "OpScope", "scope_of_op_name",
           "scopes_of_hlo_text", "has_scopes"]

#: the vocabulary; a path keeps only these components, outermost first
SCOPES = (
    "train.forward", "train.backward", "train.optimizer",
    "serve.unpack", "serve.sample",
    "embed", "layers", "attn.qkv", "attn.core", "attn.pool_write",
    "attn.out", "mlp", "lm_head",
    "shard.flash",
    "kernel.flash_fwd", "kernel.flash_bwd_dkv", "kernel.flash_bwd_dq",
    "kernel.ragged", "kernel.paged_decode", "kernel.decode",
    "kernel.fused_adamw", "kernel.rms_norm",
    "attn.rope", "conv.proj", "conv.mix", "conv.state_write",
    "moe.route", "moe.experts", "kernel.gmm",
    "ssm.proj", "ssm.conv", "ssm.scan", "ssm.state_write", "kernel.ssm_scan",
    "gmu", "attn.window", "attn.shared", "attn.diff",
    "kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.norm",
    "kda.state_write", "kernel.kda", "attn.gqa", "moe.shared",
    "kernel.kept_rows", "attn.full",
)
UNSCOPED = "unscoped"
_VOCABULARY = frozenset(SCOPES)


class OpScope(NamedTuple):
    """Where one compiled instruction comes from.  ``scope``: the vocabulary's
    part of its path (``train.backward/layers/attn.qkv``) or ``unscoped``;
    ``backward``: made by differentiation; ``recompute``: forward work done
    again inside the backward pass; ``carry``: the innermost scope is
    ``layers`` itself, so this is the layer loop's machinery and no block's
    arithmetic; ``rule``: ``own`` metadata, or what gave a scope to an
    instruction that had none (``copied``, ``consumer``, ``carry``, ``none``)."""

    scope: str
    backward: bool = False
    recompute: bool = False
    carry: bool = False
    rule: str = "own"


_NONE = OpScope(UNSCOPED, rule="none")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def scope_of_op_name(op_name: str) -> Optional[OpScope]:
    """The scope an ``op_name`` path spells, or None.  jax's wrappers
    (``jvp(layers)``, ``transpose(jvp(attn.qkv))``) are looked through and its
    own components (``while/body``, ``closed_call``, ``checkpoint``) dropped;
    a ``transpose`` marks backward work, ``rematted_computation`` recompute."""
    kept: List[str] = []
    transposed = remat = False
    # a fused instruction may list several paths (``a/b;c``): the first is whole
    for part in op_name.split(";", 1)[0].split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            transposed |= m.group(1) == "transpose"
            part = m.group(2)
        remat |= part == "rematted_computation"
        if part in _VOCABULARY and kept[-1:] != [part]:
            kept.append(part)
    if not kept:
        return None
    backward = transposed or "train.backward" in kept
    return OpScope("/".join(kept), backward=backward,
                   recompute=remat and backward, carry=kept[-1] == "layers")


_Instr = namedtuple("_Instr", "name opcode operands callees op_name root")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)="
    r"%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
#: what only renames or regroups a value: the rules look through it
_TRANSPARENT = frozenset(("get-tuple-element", "bitcast", "tuple",
                          "opt-barrier", "copy-start", "copy-done"))
#: what takes no time of its own: left out of the map
_NEVER_SHOWN = frozenset(("get-tuple-element", "bitcast", "tuple",
                          "parameter", "constant"))
#: instructions whose called computations run as operations of their own
_RUNS_CALLEES = frozenset(("while", "conditional", "call", "async-start"))


def _closing(text: str, start: int) -> int:
    """Where the parenthesis that opens at ``start`` closes."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i
    return len(text) - 1


def _parse(text: str):
    """``{computation: [instructions]}`` of an HLO module's text."""
    computations: Dict[str, List[_Instr]] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and "=" not in line.split("(", 1)[0]:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)           # result type (a tuple nests), opcode(operands), attributes
        body = (rest[_closing(rest, 0) + 1:] if rest.startswith("(")
                else rest.partition(" ")[2]).lstrip()
        op = _OPCODE.match(body)
        if not op:
            continue
        end = _closing(body, op.end() - 1)
        attrs = body[end:]
        callees = _CALLEE.findall(attrs)
        for group in _BRANCHES.findall(attrs):
            callees += _OPERAND.findall(group)
        named = _OP_NAME.search(attrs)
        current.append(_Instr(m.group(2), op.group(1),
                              _OPERAND.findall(body[op.end():end]), callees,
                              named.group(1) if named else None,
                              bool(m.group(1))))
    return computations


_ANY_SCOPE = re.compile(
    r'["/(](?:' + "|".join(re.escape(s) for s in SCOPES) + r')[/)"]')


def has_scopes(text: str) -> bool:
    """Whether a module's text (StableHLO with locations, or HLO) names any."""
    return _ANY_SCOPE.search(text) is not None


def scopes_of_hlo_text(text: str) -> Dict[str, OpScope]:
    """By name, every instruction of a compiled module's text that a device
    trace can show (the entry computation's and those of the computations that
    loops, conditionals and calls run).  Each takes the scope its ``op_name``
    spells.  What the compiler made and gave none (copy insertion, layout
    assignment, the loop's tuple shuffling) takes, in this order: the scope of
    the instruction whose result it copies (through tuples, bitcasts and the
    halves of an asynchronous copy), of its one consumer, else of the
    innermost ``layers`` loop it feeds or sits in, else ``unscoped``."""
    computations = _parse(text)
    by_name = {i.name: i for instrs in computations.values() for i in instrs}
    home = {i.name: comp for comp, instrs in computations.items()
            for i in instrs}
    shown, todo = [], list(computations)[-1:]
    while todo:
        comp = todo.pop()
        if comp in shown or comp not in computations:
            continue
        shown.append(comp)
        todo += [c for i in computations[comp] if i.opcode in _RUNS_CALLEES
                 for c in i.callees]
    loop_of = {c: i for comp in shown for i in computations[comp]
               if i.opcode == "while" for c in i.callees}
    users: Dict[str, List[_Instr]] = {}
    for comp in shown:
        for i in computations[comp]:
            for a in i.operands:
                users.setdefault(a, []).append(i)

    own: Dict[str, Optional[OpScope]] = {}

    def own_scope(instr: _Instr) -> Optional[OpScope]:
        if instr.name not in own:
            got = scope_of_op_name(instr.op_name) if instr.op_name else None
            if instr.opcode == "fusion" and (got is None or got.carry):
                # no name: take the root's.  Named after the loop's stacking
                # of an output that a matmul was fused into: the matmul's
                inner = [i for c in instr.callees
                         for i in computations.get(c, [])]
                work = [i for i in inner if i.opcode in ("convolution", "dot")]
                roots = [] if got else [i for i in inner if i.root] + inner[::-1]
                for i in work + roots:
                    found = scope_of_op_name(i.op_name) if i.op_name else None
                    if found is not None:
                        got = found
                        break
            own[instr.name] = got
        return own[instr.name]

    def loop_scope(loop: Optional[_Instr]) -> Optional[OpScope]:
        """The ``layers`` scope of a ``while`` or of the loop around it."""
        while loop is not None:
            inner = [i for c in loop.callees for i in computations.get(c, [])]
            got = next((s for s in map(own_scope, [loop] + inner) if s), None)
            parts = got.scope.split("/") if got else []
            if "layers" in parts:
                upto = len(parts) - parts[::-1].index("layers")
                return got._replace(scope="/".join(parts[:upto]), carry=True,
                                    recompute=False, rule="carry")
            loop = loop_of.get(home[loop.name])
        return None

    def scope_through(instr: _Instr, rule: str) -> Optional[OpScope]:
        if instr.opcode == "while":
            return loop_scope(instr)
        got = own_scope(instr)
        return got._replace(rule=rule) if got else None

    def producer(name: str) -> Optional[_Instr]:
        i = by_name.get(name)
        while i is not None and i.opcode in _TRANSPARENT and i.operands:
            i = by_name.get(i.operands[0])
        return i

    def consumers(name: str) -> List[_Instr]:
        found = []
        for u in users.get(name, []):
            found += consumers(u.name) if u.opcode in _TRANSPARENT else [u]
        return found

    out: Dict[str, OpScope] = {}
    for comp in shown:
        for i in computations[comp]:
            if i.opcode in _NEVER_SHOWN:
                continue
            got = own_scope(i) or (loop_scope(i) if i.opcode == "while"
                                   else None)
            if got is None and i.operands:
                src = producer(i.operands[0])
                if src is not None:
                    got = scope_through(src, "copied")
            if got is None:
                used = {u.name: u for u in consumers(i.name)}
                if len(used) == 1:
                    got = scope_through(next(iter(used.values())), "consumer")
                else:
                    got = next((s for s in map(loop_scope, (
                        u for u in used.values() if u.opcode == "while"))
                                if s), None)
            if got is None:
                got = loop_scope(loop_of.get(comp))
            out[i.name] = got if got is not None else _NONE
    return out
