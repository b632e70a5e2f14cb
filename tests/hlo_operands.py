"""What a compiled program hands to its control flow, read from its HLO text.

A value that a conditional, a loop or a custom call takes as an operand
cannot be fused with the ops that consume it inside: XLA materialises it.
:func:`control_flow_operands` lists those operands with their array
shapes, so that a test can say which values a program copies whole.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

#: the ops whose operands XLA materialises
CONTROL_FLOW = ("conditional", "while", "custom-call")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _balanced(s: str, i: int) -> int:
    """The index just past the bracket group that opens at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "([{":
            depth += 1
        elif s[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    raise ValueError(f"unbalanced: {s[i:i + 80]}")


def _instructions(hlo: str) -> Dict[str, Tuple[str, str, str]]:
    """name -> (shape, opcode, operand text) of every instruction."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        end = _balanced(rest, 0) if rest.startswith("(") else rest.index(" ")
        shape, rest = rest[:end], rest[end:].lstrip()
        op = re.match(r"([\w\-]+)\(", rest)
        if not op:
            continue
        start = op.end() - 1
        out[name] = (shape, op.group(1),
                     rest[start + 1:_balanced(rest, start) - 1])
    return out


def arrays(shape: str) -> List[Tuple[int, ...]]:
    """The array shapes in an HLO shape, a tuple's elements one by one."""
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in _ARRAY.findall(shape)]


def control_flow_operands(hlo: str) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """(op, operand, array shape) for every array that a conditional, a
    loop or a custom call takes, a tuple operand's elements each."""
    instrs = _instructions(hlo)
    found = []
    for name, (_, op, operands) in instrs.items():
        if op not in CONTROL_FLOW:
            continue
        for operand in re.findall(r"%([\w.\-]+)", operands):
            if operand in instrs:
                found += [(f"{op} {name}", operand, a)
                          for a in arrays(instrs[operand][0])]
    return found
