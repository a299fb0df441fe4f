"""An "of which" reading of a traced run: the device time, a step, of
the dense events that carry one of a layer's markers: XLA operations and
Mosaic calls alike, each by its ``op_name`` or by its own instruction
name (``phase_reduce``'s rule). A layer's scope finds the fusions under
it and a kernel called under it; a kernel whose ``pallas_call`` is named
with the scope's string as a prefix (``%hvd_kda_bwd.9``, whose
``op_name`` may have lost the forward's scope) is found by that name; a
kernel XLA names itself (``%ragged-dot-none.N``, whose
``op_name`` is the kernel's own and not the scope's it was traced under)
by a marker of its own beside the scope. A flash attention kernel
(``trace_reduce.classify``) is in no such reading: a flash forward run
again under ``rematted_computation`` stays in ``flash_fwd_ms``.

The parts of ``phase_reduce`` sum to ``dense_ms`` exactly and stay as
they are. A reading made here lies inside them (the exit gate's backward
is part of ``bwd_ms``, the recomputed forward too) and is no new part:
the reader lays its markers before those of every cell
(``hlo_counts.load_names``) and reduces the same trace again, so they
claim their events first and what the other parts then get is not
looked at. A cell's own file of names would do the same for the
cell's whole reduction; this leaves the cell's partition alone.

A ``while`` is left out of the reading: the trace shows a loop as one
event over the events of its body, XLA gives it no ``op_name``, and the
names of the instructions inside it would be put to the vote
(``phase_reduce.candidates``): a scan whose body is mostly recomputation
would then count whole, its backward matmuls too. Its body's events are
on the same line and carry their own names.
"""

from __future__ import annotations

from benchmark import hlo_counts, phase_reduce
from benchmark.trace_reduce import short_name

PART = "of_which"


def _without_loops(trace: dict) -> dict:
    return {**trace, "devices": {
        plane: [ev for ev in events
                if not short_name(ev[0]).lstrip("%").startswith("while")]
        for plane, events in trace["devices"].items()}}


def per_step_ms(record: dict, *markers: str, root: str = phase_reduce.ROOT):
    """Milliseconds a step of dense device time under ``markers``, mean
    over devices, or ``None`` where there is nothing to read: no trace,
    or a program none of whose events carries any of them."""
    trace = record.get("trace")
    if not trace or not trace.get("steps"):
        return None
    names = hlo_counts.load_names([{
        "dense_markers": [[m, PART] for m in markers],
        "phases": {PART: [PART]}}])
    names["program_scopes"] = list(markers)     # "named" speaks of them alone
    if "of_which_trace" not in record:      # read once for all such readers
        path = phase_reduce.newest_trace(root)
        record["of_which_trace"] = _without_loops(
            phase_reduce.read_trace(path, names)) if path else None
    if not record["of_which_trace"]:
        return None
    reduced = phase_reduce.reduce_phases(record["of_which_trace"], names)
    if not reduced or not reduced["named"]["dense"]:
        return None
    return 1e3 * reduced["seconds"][PART] / trace["steps"]
