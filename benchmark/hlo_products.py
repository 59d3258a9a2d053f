"""Which element types a compiled program's products take, read from the
program's HLO text and from nothing the program says of itself.

``products(text, scopes)`` lists every ``convolution`` and ``dot`` of an HLO
module (``jitted.lower(...).compiler_ir("hlo").as_hlo_text()``: the module the
compiler is handed, the same on every backend; an executable's own
``as_text()`` parses too) with its operands' and its result's element type
and the scope it stands in: the LAST of ``scopes`` in the instruction's
``op_name`` (``jit(step)/.../transpose(jvp(raft.fnet))/.../conv_general_
dilated``: forward, rematerialised and backward instructions all carry their
scope's name), ``None`` outside all of them.

``narrow_ops(text, scopes)`` counts, per scope, the instructions OTHER than
``convert`` that read or write a float type narrower than float32: what a
pinned float32 region may not hold (a ``convert`` is how a narrow value
enters or leaves it, data movement of a narrow value before its ``convert``
is arithmetic on nothing, and ``add_any`` is jax adding up the cotangents of
a value read in several places: a narrow value the region reads, the hidden
state under NCUP, has its cotangents summed where the region's is made).
"""

from __future__ import annotations

import re

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\w+)\[[^\]]*\](?:\{[^}]*\})?\s+([\w\-]+)\(([^)]*)\)"
)
# an operand: its name, after its shape where the text prints one ("f32[2,3]{1,0} %x")
_OPERAND = re.compile(r"(?:(\w+)\[[\d,]*\](?:\{[\d,]*\})?\s+)?(%?[A-Za-z_][\w.\-]*)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NARROW = {"bf16", "f16", "f8e4m3fn", "f8e5m2"}
# An instruction that moves values and computes none.
_MOVES = {
    "convert", "parameter", "get-tuple-element", "tuple", "reshape", "bitcast", "copy",
    "transpose", "broadcast", "slice", "dynamic-slice", "dynamic-update-slice", "pad",
    "concatenate", "gather", "reverse", "constant", "iota", "select", "while", "call",
    "conditional", "fusion", "opt-barrier", "custom-call",
}


def _scope_of(op_name: str, scopes) -> str | None:
    found = [(op_name.rfind(s), s) for s in scopes if s in op_name]
    return max(found)[1] if found else None


def _instructions(text: str, scopes):
    """(scope, opcode, operand element types, result element type, op_name)
    of every array-valued instruction; names resolve inside their own
    computation."""
    types: dict = {}
    for line in text.splitlines():
        if line.rstrip().endswith("{"):  # a computation opens
            types = {}
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, result, opcode, operands = m.groups()
        types[name.lstrip("%")] = result
        operand_types = [
            typed or types.get(operand.lstrip("%")) for typed, operand in _OPERAND.findall(operands)
        ]
        found = _OP_NAME.search(line)
        op_name = found.group(1) if found else ""
        yield _scope_of(op_name, scopes), opcode, tuple(operand_types), result, op_name


def products(text: str, scopes) -> list:
    return [
        {"scope": scope, "operands": list(operands), "result": result, "op_name": op_name}
        for scope, opcode, operands, result, op_name in _instructions(text, scopes)
        if opcode in ("convolution", "dot")
    ]


def narrow_ops(text: str, scopes) -> dict:
    counts = {scope: 0 for scope in scopes}
    for scope, opcode, operands, result, op_name in _instructions(text, scopes):
        if scope is None or opcode in _MOVES or op_name.endswith("/add_any"):
            continue
        if result in _NARROW or _NARROW.intersection(operands):
            counts[scope] += 1
    return counts
