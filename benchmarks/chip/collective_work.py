"""Least bytes of a collective collapse, from the program's counts only, and
the device time of the collapse program, from a trace's operations.

A collapse reduces D shard states of S bytes each.  Whatever the
collective, each chip's links must carry at least (D - 1) / D of a state:
the part of the result that the other D - 1 chips hold.  The count
describes the reduction, not how the butterfly does it (log2 D rounds of a
whole state), so a cheaper collective reads a higher roofline share.

The time is that of every operation of the collapse program on a device:
the collectives and the merge's work between them, over which the
transfers overlap.  A trace names an operation by its HLO instruction
(``%name = shape opcode(operands), attributes``, operand shapes printed);
the compiled program's HLO text names the same instructions (operand
shapes left out).  ``op_key`` reduces both to the instruction's name,
shape, opcode and operand names, so the trace's operations of the collapse
are those whose key is one of its entry computation's instructions (the
other computations are fusions' and sorts' bodies, which run inside those
instructions).  The collapse program has no loop or call, so every
operation it runs is an entry instruction.
"""
from __future__ import annotations

import json
import os
import re

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "ici_peaks.json")

_OPERAND = re.compile(r"%[\w.\-]+")
_OPEN, _CLOSE = "([{", ")]}"


def _group_end(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in _OPEN:
            depth += 1
        elif text[j] in _CLOSE:
            depth -= 1
            if depth == 0:
                return j + 1
    raise ValueError(f"unbalanced brackets in {text!r}")


def op_key(text: str):
    """(name, shape, opcode, operand names) of one HLO instruction, printed
    with or without operand shapes; None for a line that is not one."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    name, sep, rest = text.partition(" = ")
    if not sep or not name.startswith("%") or " " in name:
        return None
    if rest.startswith("("):                  # a tuple shape
        end = _group_end(rest, 0)
    else:
        end = rest.find(" ")
        if end < 0:
            return None
    shape, rest = rest[:end], rest[end:].lstrip()
    paren = rest.find("(")
    if paren <= 0:
        return None
    opcode = rest[:paren]
    operands = rest[paren:_group_end(rest, paren)]
    return name, shape, opcode, tuple(_OPERAND.findall(operands))


def program_keys(hlo_text: str) -> set:
    """The keys of the instructions of a compiled program's entry
    computation, from its HLO text."""
    keys, entry = set(), False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif entry and line.startswith("}"):
            break
        elif entry:
            k = op_key(line)
            if k is not None:
                keys.add(k)
    return keys


def program_s(op_s: dict, hlo_text: str) -> float:
    """Seconds of the trace's operations (``op_s``: operation name to
    seconds) that are instructions of the program ``hlo_text``."""
    keys = program_keys(hlo_text)
    return sum(v for n, v in op_s.items() if op_key(n) in keys)


def least_ici_bytes(devices: int, state_bytes: int) -> float:
    """Bytes one chip's links carry at the least in one collapse of
    ``devices`` shard states of ``state_bytes`` each."""
    return (devices - 1) * state_bytes / devices


def ici_peak(device_kind: str) -> float:
    """ICI bytes per second of one chip of ``device_kind``; a device not in
    the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no ICI peak for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind]["ici_bytes_per_s"])
