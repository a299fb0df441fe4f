"""Exact counts from a compiled step's HLO text: collective operations,
the bytes per chip they move, and Mosaic (Pallas) kernel calls, all of
them and those that carry a flash attention kernel's name.

Counts, not times: they repeat exactly and a CPU rehearsal can print
them. The names matched are data, read by ``load_names``.
"""

from __future__ import annotations

import json
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))
# What a cell's own file of names may add to: the lists a later PR's kernel
# or scope name belongs in, and the phases its parts make up.
_EXTENDED = ("flash_kernels", "dense_markers", "program_scopes")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
# `%name = <result type> opcode(operands...)`; async pairs carry the
# payload in `-start`, so `-done` is not counted again.
_INSTR = re.compile(r"=\s*(\(?[^=]*?)\s([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def load_names(more=()) -> dict:
    """``trace_names.json`` (classes, planes, lines) with
    ``phase_names.json`` (kernels, markers, phases) laid over it: the names
    of every cell. ``more``: the files of names a cell's own file lists
    (``"names"``, found under ``names/``), laid over both in the order
    given, for that cell alone: a later PR brings its kernels and scopes
    with its cell and cannot move a millisecond in a cell that is there.
    Such a file extends the lists of ``_EXTENDED``, its ``dense_markers``
    going before those already there so that a more specific scope wins,
    and gives ``phases`` new keys. Anything else in it but a ``comment``
    is an error, as is a phase that exists."""
    names = {}
    for name in ("trace_names.json", "phase_names.json"):
        with open(os.path.join(_HERE, name)) as f:
            names.update(json.load(f))
    for extra in more:
        extra = {k: v for k, v in extra.items() if k != "comment"}
        phases = extra.pop("phases", {})
        unknown = sorted(set(extra) - set(_EXTENDED)) \
            + sorted(set(phases) & set(names["phases"]))
        if unknown:
            raise ValueError(f"a cell's file of names may not set {unknown}")
        for key, entries in extra.items():
            names[key] = entries + names[key] if key == "dense_markers" \
                else names[key] + entries
        names["phases"] = {**names["phases"], **phases}
    return names


def _shape_bytes(text: str) -> list:
    out = []
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n * _DTYPE_BYTES.get(dtype, 4))
    return out


def count(hlo_text: str, names: dict = None) -> dict:
    """``{"collectives": {opcode: {"ops", "bytes"}}, "mosaic_calls": n,
    "flash_mosaic_calls": n}``.

    Bytes are those of each collective's result on one chip. An async
    ``-start`` returns (operands, results): only the results half is
    counted. A Mosaic call is a flash kernel's where its own instruction
    name or its ``op_name`` holds a name of ``flash_kernels``, as
    ``trace_reduce.classify`` tells a trace's events."""
    names = names or load_names()
    opcodes = tuple(names["collective_opcodes"])
    collectives = {}
    mosaic = flash = 0
    for line in hlo_text.splitlines():
        if names["mosaic_call_marker"] in line:
            mosaic += 1
            own = " ".join([line.split(" = ", 1)[0],
                            *_OP_NAME.findall(line)])
            flash += any(k in own for k, _ in names["flash_kernels"])
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
    return {"collectives": collectives, "mosaic_calls": mosaic,
            "flash_mosaic_calls": flash}
