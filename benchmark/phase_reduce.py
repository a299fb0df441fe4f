"""From a profiler trace to the parts of ``dense_ms`` and ``flash_ms``.

``trace_reduce`` gives a step's device time in three classes. This module
splits two of them by the names the program gives its work
(``horovod_tpu/common/scopes.py``; the strings are quoted as data in
``phase_names.json``):

    flash_s = flash_fwd + flash_dkv
    dense_s = bucket_copy + lm_head + optimizer + bwd + fwd + other_kernel
              + unattributed

One rule for both: a device event belongs to the layer whose name its
``op_name`` or its own instruction name holds, whether XLA compiled it or
Mosaic did. A flash event (``trace_reduce.classify``: a Mosaic call that
carries an attention kernel's name) goes to that kernel's part. A dense
event, an XLA operation or any other Mosaic call, goes to the first
marker of ``dense_markers`` that its ``op_name`` or its instruction name
(``%hvd_kda_bwd.9``) holds; else a Mosaic call goes to ``kernel_default``
(XLA's ``ragged-dot`` kernels, whose ``op_name`` is the kernel's own), an
XLA operation to ``dense_default`` where it has an ``op_name`` and to
``no_op_name`` where nothing names it. So a later PR's kernel counts
under its layer where its ``pallas_call`` is named with the layer's scope
string as a prefix (``scopes.KDA + "_fwd"``: the name is enough where a
backward's ``op_name`` has lost the forward's scope) or is called under
the scope, and nothing here needs an edit. A fusion is
one event and carries one ``op_name``: a fusion that holds instructions
of two scopes counts whole under the one XLA gave the fusion (on the v5e
AdamW's update rides in the weight-gradient matmuls' fusions, which are
named for the matmul).

Where the ``op_name`` comes from, in this order:

1. the stat ``tf_op`` of the event's *metadata*. ``jax.profiler
   .ProfileData`` gives an event's own stats only, so the metadata of the
   ``.xplane.pb`` are read here from the wire: ``read_metadata`` knows the
   message types of ``xplane.proto`` that matter and nothing else, and
   needs no package. The events themselves still come from
   ``trace_reduce.load_xplane``, joined by their names (on the chip an
   event's name is its whole HLO instruction);
2. for an event XLA left nameless (a copy it added, a multi-output
   fusion whose root is a tuple), the optimized ``HloProto`` the profiler
   keeps in the file's ``/host:metadata`` plane: the names of the
   instructions inside the fusion or, for a plain instruction, of its
   nearest named consumers, or producers where only the program's result
   consumes it (``read_hlo``, ``candidates``). The event goes to the part
   most of those names give.

Arithmetic as in ``trace_reduce``: per device, parts claim intervals in
the order of the marker lists, each the union of its events less the
flash intervals and less what an earlier part claimed, so the parts sum
to ``dense_s`` and to ``flash_s`` exactly, also where two events
overlap. Mean over devices.

A reader is given the run's record and nothing else, so ``phases``
finds the trace itself: the newest ``.xplane.pb`` under the benchmark's
scratch directory (the job wipes a cell's scratch before a traced run).
"""

from __future__ import annotations

import collections
import functools
import glob
import json
import os

from benchmark.hlo_counts import load_names
from benchmark.trace_reduce import (classify, is_mosaic_call, load_xplane,
                                    short_name, subtract, total, union)

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
CONSUMER_DEPTH = 8      # how far ``candidates`` follows nameless neighbours


# -- protocol buffers, from the wire ---------------------------------------

def _varint(buf, pos):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a ``memoryview`` for a length-delimited one."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = int.from_bytes(buf[pos:pos + size], "little")
            pos += size
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield key >> 3, value


def _message(buf, wanted):
    """``{field number: [values]}`` for the numbers in ``wanted``."""
    out = {no: [] for no in wanted}
    for no, value in _fields(buf):
        if no in out:
            out[no].append(value)
    return out


def _text(views) -> str:
    return bytes(views[-1]).decode("utf-8", "replace") if views else ""


def _last(values, default=0):
    """A singular field's value: the last one on the wire."""
    return values[-1] if values else default


def _ints(values):
    """A repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            pos = 0
            while pos < len(v):
                i, pos = _varint(v, pos)
                out.append(i)
    return out


# -- the XSpace file -----------------------------------------------------
#
# XSpace         1: planes*
# XPlane         2: name  4: event_metadata  5: stat_metadata
#                (maps: an entry is a message, 1: key  2: value)
# XEventMetadata 2: name  5: stats*
# XStatMetadata  2: name
# XStat          1: metadata_id  3: uint64  4: int64  5: str  6: bytes
#                7: ref (the value is a stat metadata's name)

def _stats(stats, stat_names):
    """``{stat name: value}``: strings (a ref resolved), integers and
    ``memoryview``s of bytes."""
    out = {}
    for buf in stats:
        m = _message(buf, (1, 3, 4, 5, 6, 7))
        if m[5]:
            value = _text(m[5])
        elif m[7]:
            value = stat_names.get(m[7][-1], "")
        else:
            value = (m[3] + m[4] + m[6] + [None])[0]
        out[stat_names.get(_last(m[1]), "")] = value
    return out


def read_metadata(data: bytes, names: dict) -> dict:
    """``{"events": {plane: {event name: (op_name, program id)}}, "hlo":
    {program id: bytes}}``: for each device plane, what the metadata of
    its events say (``op_name`` the first of ``names["op_name_stats"]``
    that is a string, else ``""``), and each program's serialized
    ``HloProto`` from the metadata plane. No line and no event is read:
    ``ProfileData`` does that faster."""
    events, hlo = {}, {}
    for no, plane in _fields(memoryview(data)):
        if no != 1:
            continue
        m = _message(plane, (2, 4, 5))
        plane_name = _text(m[2])
        device = plane_name.startswith(names["device_plane_prefix"])
        if not device and plane_name != names["hlo_plane"]:
            continue
        stat_names = {}
        for entry in m[5]:
            e = _message(entry, (1, 2))
            stat_names[_last(e[1])] = _text(_message(e[2][-1], (2,))[2])
        for entry in m[4]:
            e = _message(entry, (1, 2))
            meta = _message(e[2][-1], (2, 5))
            stats = _stats(meta[5], stat_names)
            if device:
                op_name = next((stats[k] for k in names["op_name_stats"]
                                if isinstance(stats.get(k), str)), "")
                events.setdefault(plane_name, {}).setdefault(
                    _text(meta[2]),
                    (op_name, stats.get(names["program_id_stat"], 0)))
            elif stats.get(names["hlo_stat"]) is not None:
                hlo[_last(e[1])] = bytes(stats[names["hlo_stat"]])
    return {"events": events, "hlo": hlo}


def read_trace(path: str, names: dict) -> dict:
    """``{"devices": {plane: [event, ...]}, "hlo": {program id: bytes}}``,
    an event ``[name, start_ns, dur_ns, own_text, op_name, program_id]``:
    the events exactly as ``trace_reduce.load_xplane`` gives them, so
    that both reductions classify and sum alike, each with what the
    metadata of that name says."""
    with open(path, "rb") as f:
        found = read_metadata(f.read(), names)
    devices = {}
    for plane, events in load_xplane(path, names)["devices"].items():
        meta = found["events"].get(plane, {})
        devices[plane] = [ev + list(meta.get(ev[0], ("", 0)))
                          for ev in events]
    return {"devices": devices, "hlo": found["hlo"]}


# -- the HloProto of a program ---------------------------------------------
#
# HloProto            1: hlo_module
# HloModuleProto      3: computations*
# HloComputationProto 2: instructions*  5: id
# HloInstructionProto 1: name  7: metadata  35: id  36: operand_ids*
#                     38: called_computation_ids*
# OpMetadata          2: op_name

def read_hlo(raw: bytes) -> dict:
    """``{instruction name: {"op_name", "inner", "users", "operands"}}``
    over every computation of the module: ``inner`` the ``op_name``s of
    the instructions of the computations it calls (a fusion's body),
    ``users`` and ``operands`` the names of the instructions that take
    it as an operand and that it takes."""
    module = _message(memoryview(raw), (1,))[1]
    if not module:
        return {}
    computations, instructions, by_id = {}, {}, {}
    for comp in _message(module[-1], (3,))[3]:
        c = _message(comp, (2, 5))
        body = []
        for ins in c[2]:
            i = _message(ins, (1, 7, 35, 36, 38))
            meta = _message(i[7][-1], (2,)) if i[7] else {2: []}
            entry = {"op_name": _text(meta[2]), "operands": _ints(i[36]),
                     "called": _ints(i[38]), "users": []}
            name = _text(i[1])
            instructions[name] = entry
            by_id[_last(i[35])] = name
            body.append(entry)
        computations[_last(c[5])] = body
    for name, entry in instructions.items():
        entry["operands"] = [by_id[o] for o in entry["operands"]
                             if o in by_id]
        for operand in entry["operands"]:
            instructions[operand]["users"].append(name)
        entry["inner"] = [i["op_name"] for c in entry.pop("called")
                          for i in computations.get(c, ()) if i["op_name"]]
    return instructions


def candidates(hlo: dict, name: str) -> list:
    """The ``op_name``s that stand for instruction ``name``: its own; else
    those inside the computations it calls; else those of its nearest
    consumers that have any, breadth first to ``CONSUMER_DEPTH``; else,
    for what only the program's result consumes, of its nearest
    producers, likewise."""
    def direct(n):
        entry = hlo.get(n)
        if not entry:
            return []
        return [entry["op_name"]] if entry["op_name"] else entry["inner"]

    found = direct(name)
    for towards in ("users", "operands"):
        seen, frontier = {name}, [name]
        for _ in range(CONSUMER_DEPTH):
            if found or not frontier:
                break
            near = [n for f in frontier for n in hlo.get(f, {towards: ()})[
                towards] if n not in seen]
            seen.update(near)
            found = [op for n in near for op in direct(n)]
            frontier = [n for n in near if not direct(n)]
    return found


# -- the reduction ---------------------------------------------------------

def _part(markers, default, texts):
    for marker, part in markers:
        if any(marker in t for t in texts):
            return part
    return default


def reduce_phases(trace: dict, names: dict) -> dict:
    """``{"seconds": {part: s}, "named": {"flash": bool, "dense": bool},
    "devices": n, "from_hlo_s": s}`` for ``trace`` as ``read_trace``
    returns it: each part's device time, mean over devices, and how much
    of the dense time was named through the ``HloProto``. ``named`` says
    whether any event carried one of the program's kernel names, and one
    of its scope names: a program that has no names (the parent of the PR
    that brought them) gives nothing to read, which is not the same as a
    part that took no time."""
    flash_markers = [tuple(m) for m in names["flash_kernels"]]
    dense_markers = [tuple(m) for m in names["dense_markers"]]
    flash_parts = [p for _, p in flash_markers]
    # A kernel's interval is the kernel's: it claims before the markers.
    dense_parts = ([names["kernel_default"]] + [p for _, p in dense_markers]
                   + [names["dense_default"], names["no_op_name"]])
    seconds = dict.fromkeys(flash_parts + dense_parts, 0.0)
    named = {"flash": False, "dense": False}
    programs = {}               # program id -> read_hlo(...), when needed
    n, from_hlo_ns = 0, 0.0

    def dense_part(*texts):
        return _part(dense_markers, None, texts)

    @functools.cache            # an instruction runs once a step
    def part_of(name, own_text, op_name, program):
        """``(part or None, named through the HLO)`` of one event."""
        cls = classify(name, own_text, names)
        bare = short_name(name).lstrip("%")
        if cls == "flash":
            # The instruction's own name, never its operands'.
            named["flash"] = True
            return _part(flash_markers, None, (bare, own_text, op_name)), False
        if cls != "dense":
            return None, False
        named["dense"] |= any(s in op_name or s in bare
                              for s in names["program_scopes"])
        part = dense_part(op_name, bare)
        if part:
            return part, False
        if is_mosaic_call(name, own_text, names):
            return names["kernel_default"], False
        if op_name:
            return names["dense_default"], False
        if program not in programs:
            programs[program] = read_hlo(
                trace.get("hlo", {}).get(program, b""))
        votes = collections.Counter(
            dense_part(op) or names["dense_default"] for op in candidates(
                programs[program], bare))
        if not votes:
            return names["no_op_name"], False
        return votes.most_common(1)[0][0], True

    for _, events in sorted(trace["devices"].items()):
        if not events:
            continue
        n += 1
        by_part = {p: [] for p in seconds}
        for name, start, dur, own_text, op_name, program in events:
            part, via_hlo = part_of(name, own_text, op_name, program)
            if part:
                by_part[part].append((start, start + dur))
                from_hlo_ns += dur if via_hlo else 0.0
        claimed = []                # flash first: dense lies outside it
        for p in flash_parts + dense_parts:
            own = subtract(union(by_part[p]), claimed)
            seconds[p] += total(own) / 1e9
            claimed = union(claimed + own)
    if not n:
        return {}
    return {"seconds": {p: s / n for p, s in seconds.items()},
            "named": named, "devices": n, "from_hlo_s": from_hlo_ns / 1e9 / n}


def newest_trace(root: str = ROOT):
    files = glob.glob(os.path.join(root, ".bench_scratch", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def phases(record: dict, root: str = ROOT):
    """The run's phases, ``{"ms": {phase: ms a step}, "named", "kind"}``, or
    ``None`` where the run was not traced or left no file. Computed once
    a record; the first call prints the ``phases`` note. The names are the
    record's, which the job read for its cell; a record without any is
    read by the names of every cell."""
    trace = record.get("trace")
    if not trace or not trace.get("steps"):
        return None
    if "phases" not in record:
        record["phases"] = None
        path = newest_trace(root)
        names = record.get("names") or load_names()
        reduced = {}
        if path:
            reduced = reduce_phases(read_trace(path, names), names)
        if reduced:
            steps = trace["steps"]
            parts = {p: 1e3 * s / steps
                     for p, s in reduced["seconds"].items()}
            flash = {p for _, p in names["flash_kernels"]}
            record["phases"] = {
                "ms": {phase: sum(parts[p] for p in members)
                       for phase, members in names["phases"].items()},
                "named": reduced["named"],
                "kind": {phase: "flash" if set(members) <= flash else "dense"
                         for phase, members in names["phases"].items()}}
            print(json.dumps({
                "note": "phases", "ms_a_step": record["phases"]["ms"],
                "parts_ms": parts, "named": reduced["named"],
                "devices": reduced["devices"],
                "op_name_from": names["op_name_stats"] + [names["hlo_stat"]],
                "named_through_hlo_ms": 1e3 * reduced["from_hlo_s"] / steps,
                "file": os.path.relpath(path, root)}), flush=True)
    return record["phases"]


def per_step_ms(record: dict, phase: str, root: str = ROOT):
    """Milliseconds a step in ``phase``, or ``None`` where there is
    nothing to read: no trace, or a program without the names."""
    found = phases(record, root)
    if not found or not found["named"][found["kind"][phase]]:
        return None
    return found["ms"][phase]
