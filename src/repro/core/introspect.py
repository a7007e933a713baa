"""Program introspection: primitives of a traced program, layers of a
compiled one.

The one-wire-tensor shuffle's acceptance contract is structural — exactly
one ``all_to_all`` per flat hop, two per hierarchical hop (times ``chunks``)
— so the tests and the CI smoke step assert it directly on the jaxpr rather
than trusting byte accounting. Works on any traceable callable, including
``shard_map``-wrapped shuffles (the collectives sit inside the shard_map
sub-jaxpr; the walk recurses through every sub-jaxpr it finds in equation
params: pjit bodies, cond branches, scan/while carries, shard_map, ...).

:func:`op_layers` reads the other end: the optimised program's
instructions, each mapped to the layer scope (:mod:`repro.obs.layers`) its
``op_name`` metadata names. A profiler trace names device ops by
instruction, so this map turns a trace's op times into layer times.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.obs.layers import SCOPES

#: primitives that move bytes between devices.
COLLECTIVE_PRIMITIVES = (
    "all_to_all", "all_gather", "psum", "ppermute", "reduce_scatter",
    "pmax", "pmin",
)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):   # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):                           # Jaxpr
                yield x


def primitive_counts(fn: Callable, *args, **kwargs) -> Dict[str, int]:
    """Trace ``fn(*args, **kwargs)`` and count every primitive, recursing
    through sub-jaxprs. Returns ``{primitive_name: count}``."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs).jaxpr
    acc: Dict[str, int] = {}

    def walk(j):
        for eqn in j.eqns:
            acc[eqn.primitive.name] = acc.get(eqn.primitive.name, 0) + 1
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return acc


def collective_counts(fn: Callable, *args, **kwargs) -> Dict[str, int]:
    """Like :func:`primitive_counts`, filtered to cross-device collectives
    (every name in :data:`COLLECTIVE_PRIMITIVES`, 0 when absent)."""
    counts = primitive_counts(fn, *args, **kwargs)
    return {name: counts.get(name, 0) for name in COLLECTIVE_PRIMITIVES}


#: one instruction line of ``Compiled.as_text()``: its name, then (when it
#: has one) the ``op_name`` and the ``stack_frame_id`` of its metadata.
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'[{ ]op_name="((?:[^"\\]|\\.)*)"')
_FRAME = re.compile(r' stack_frame_id=(\d+)')
_OPCODE = re.compile(r' ([a-z][\w\-]*)\(')
_REF = re.compile(r'%([\w.\-]+)')
#: instructions many layers read, which pass no layer on: a constant, and
#: an argument of the program (a called computation's parameters take the
#: layer of its callers).
_LEAVES = ("constant", "parameter")
#: opcodes of optimised HLO that move data between devices.
COLLECTIVE_OPCODES = ("all-to-all", "all-gather", "all-reduce",
                      "collective-permute", "reduce-scatter")
#: a computation's first line (``ENTRY`` for the entry computation).
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%([\w.\-]+) .*\{$')
#: the computations an instruction calls: a while's body and condition, a
#: fusion's or call's computation, a reduction's, a conditional's branches.
_CALLS = re.compile(r'(?:calls|body|condition|to_apply)=%([\w.\-]+)'
                    r'|branch_computations=\{([^}]*)\}')


def layer_of(op_name: str) -> Optional[str]:
    """The layer of the innermost layer scope (:data:`repro.obs.layers.
    SCOPES`) on an ``op_name`` path (``"jit(f)/sphere.move/jit(_p)/
    partition.rank/pallas_call"`` -> ``"sphere.rank"``), or None when the
    path holds none."""
    for part in reversed(op_name.split("/")):
        layer = SCOPES.get(part)
        if layer is not None:
            return layer
    return None


def _hlo_text(compiled: Any) -> str:
    return compiled if isinstance(compiled, str) else compiled.as_text()


def _operands(line: str, m: "re.Match") -> List[str]:
    """The instructions an instruction line reads: the ``%`` references in
    the parenthesised operand list after its opcode (``m``)."""
    depth, i = 1, m.end()
    while i < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        i += 1
    return _REF.findall(line, m.end(), i)


def _is_collective(opcode: str) -> bool:
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            opcode = opcode[:-len(suffix)]
    return opcode in COLLECTIVE_OPCODES


def op_layers(compiled: Any) -> Dict[str, str]:
    """``{instruction name: layer}`` for the instructions of every
    computation of a compiled program (``jit(...).lower(...).compile()``,
    or its ``as_text()``), fusions' bodies and ``while`` loops included.
    Instruction names are unique within the module, and they are the names
    a device trace gives its op events.

    An instruction's layer is the one its ``op_name`` metadata names
    (:func:`layer_of`). The compiler also makes instructions the program
    never wrote. Those are resolved by the rules below, in this order; a
    rule that finds more than one layer leaves the instruction unscoped,
    and none depends on where an instruction sits in the text:

    1. a pass that rewrites an instruction may keep only its opcode as
       ``op_name`` (the TPU compiler does so for the gathers it gives
       bound assumptions) but keeps its ``stack_frame_id``, the Python
       call stack it was traced from: it takes the layer of the scoped
       instructions of that call stack;
    2. what carries a collective's data: walking from each collective
       (``all-to-all`` and the like) back through what it reads and on
       through what reads it, over instructions of its layer or of none,
       the unscoped ones take the collective's layer (the loops the TPU
       compiler writes to lay a byte wire out for the exchange);
    3. in rounds, until nothing more resolves: an instruction of a
       computation that others call (a loop's body, a fusion's parameters)
       takes the layer of its callers; any other, the layer of the
       resolved instructions it reads and that read it (a layout copy, a
       broadcast the partitioner adds), leaving aside constants, the
       program's arguments and the tuple of its outputs. Each round reads
       only what the rounds before it resolved.
    """
    own: Dict[str, str] = {}
    frame_of: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    frames: Dict[str, set] = {}            # stack frame -> layers seen
    computation_of: Dict[str, str] = {}    # instruction -> its computation
    callers: Dict[str, List[str]] = {}     # computation -> its callers
    computation = ""
    for line in _hlo_text(compiled).splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                computation = head.group(1)
            continue
        name = m.group(1)
        computation_of[name] = computation
        for one, many in _CALLS.findall(line):
            for callee in [one] if one else _REF.findall(many):
                callers.setdefault(callee, []).append(name)
        op = _OPCODE.search(line, m.end())
        opcode[name] = op.group(1) if op else ""
        operands[name] = _operands(line, op) if op else []
        meta = line.find("metadata={", m.end())
        if meta < 0:
            continue
        path = _OP_NAME.search(line, meta)
        frame = _FRAME.search(line, meta)
        layer = layer_of(path.group(1)) if path else None
        if layer is not None:
            own[name] = layer
            if frame:
                frames.setdefault(frame.group(1), set()).add(layer)
        elif frame:
            frame_of[name] = frame.group(1)
    out = dict(own)
    for name, frame in frame_of.items():                       # rule 1
        seen = frames.get(frame, ())
        if len(seen) == 1:
            out[name] = next(iter(seen))
    readers: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for o in ops:
            readers.setdefault(o, []).append(name)
    wire: Dict[str, set] = {}                                  # rule 2
    for name, code in opcode.items():
        layer = out.get(name)
        if layer is None or not _is_collective(code):
            continue
        for step in (operands, readers):
            todo, seen = [name], {name}
            while todo:
                for nxt in step.get(todo.pop(), ()):
                    if (nxt in seen or opcode.get(nxt) in _LEAVES
                            or out.get(nxt, layer) != layer):
                        continue
                    seen.add(nxt)
                    todo.append(nxt)
                    if nxt not in out:
                        wire.setdefault(nxt, set()).add(layer)
    out.update({n: ls.pop() for n, ls in wire.items() if len(ls) == 1})
    # a tuple that nothing reads gathers a computation's outputs
    outputs = {n for n, code in opcode.items()
               if code == "tuple" and n not in readers}
    rest = [n for n in operands if n not in out]
    while rest:                                                # rule 3
        found = {}
        for name in rest:
            calling = callers.get(computation_of[name])
            if calling:
                if all(c in out for c in calling):
                    seen = {out[c] for c in calling}
                else:
                    continue
            elif opcode[name] in _LEAVES:
                continue
            else:
                near = operands[name] + readers.get(name, [])
                seen = {out[n] for n in near if n in out
                        and n not in outputs
                        and opcode.get(n) not in _LEAVES}
            if len(seen) == 1:
                found[name] = seen.pop()
        if not found:
            break
        out.update(found)
        rest = [n for n in rest if n not in out]
    return out


def module_name(compiled: Any) -> str:
    """The HLO module name of a compiled program (``jit_<function>``)."""
    head = _hlo_text(compiled).split("\n", 1)[0].split()
    return head[1].rstrip(",") if head[:1] == ["HloModule"] else ""
