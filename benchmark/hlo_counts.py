"""Exact counts from a compiled step's HLO text: collective operations,
the bytes per chip they move, and Mosaic (Pallas) kernel calls.

Counts, not times: they repeat exactly and a CPU rehearsal can print
them. The names matched are data (``trace_names.json``).
"""

from __future__ import annotations

import json
import os
import re

_NAMES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "trace_names.json")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
# `%name = <result type> opcode(operands...)`; async pairs carry the
# payload in `-start`, so `-done` is not counted again.
_INSTR = re.compile(r"=\s*(\(?[^=]*?)\s([a-z][a-z\-]*)\(")


def load_names() -> dict:
    with open(_NAMES) as f:
        return json.load(f)


def _shape_bytes(text: str) -> list:
    out = []
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n * _DTYPE_BYTES.get(dtype, 4))
    return out


def count(hlo_text: str, names: dict = None) -> dict:
    """``{"collectives": {opcode: {"ops", "bytes"}}, "mosaic_calls": n}``.

    Bytes are those of each collective's result on one chip. An async
    ``-start`` returns (operands, results): only the results half is
    counted."""
    names = names or load_names()
    opcodes = tuple(names["collective_opcodes"])
    collectives = {}
    for line in hlo_text.splitlines():
        m = _INSTR.search(line)
        if not m:
            continue
        result, opcode = m.group(1), m.group(2)
        base = opcode[:-len("-start")] if opcode.endswith("-start") \
            else opcode
        if base not in opcodes or opcode.endswith("-done"):
            continue
        sizes = _shape_bytes(result)
        if opcode.endswith("-start") and len(sizes) > 1 \
                and len(sizes) % 2 == 0:
            sizes = sizes[len(sizes) // 2:]
        entry = collectives.setdefault(base, {"ops": 0, "bytes": 0})
        entry["ops"] += 1
        entry["bytes"] += sum(sizes)
    return {"collectives": collectives,
            "mosaic_calls": hlo_text.count(names["mosaic_call_marker"])}
