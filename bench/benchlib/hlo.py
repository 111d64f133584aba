"""What the benchmark needs from a compiled program's HLO text: the
module's name and the names of its instructions by kind, so that the
trace's device events can be told apart (the program gives its layers no
``named_scope`` and its Pallas call no ``name=``; the sweep kernel is the
``tpu_custom_call`` custom call)."""

from __future__ import annotations

import re
from typing import Dict, Set

# one instruction of HLO text, or a device event a trace names after one:
# (name, opcode)
INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")

COLLECTIVES = frozenset({
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "all-gather", "all-gather-start", "all-gather-done",
    "all-to-all", "reduce-scatter", "collective-broadcast",
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "send", "send-done", "recv", "recv-done",
})


def module_name(text: str) -> str:
    m = re.search(r"^HloModule\s+([\w.\-]+)", text, re.M)
    return m.group(1) if m else ""


def instructions(text: str) -> Dict[str, str]:
    """Instruction name -> opcode, over every computation."""
    out = {}
    for line in text.splitlines():
        m = INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def kernel_names(text: str) -> Set[str]:
    """Custom calls into a Mosaic (Pallas) kernel."""
    names = set()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = INSTR.match(line)
            if m:
                names.add(m.group(1))
    return names


def names_by_opcode(text: str, opcodes) -> Set[str]:
    return {n for n, op in instructions(text).items() if op in opcodes}
