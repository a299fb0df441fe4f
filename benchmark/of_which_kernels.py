"""An "of which" reading (``of_which.py``) of work that is partly a
Mosaic call: the device time, a step, of the dense events whose
``op_name`` holds a scope AND of the Mosaic calls whose own instruction
name holds a kernel's name.

``jax.lax.ragged_dot`` reaches the TPU as XLA's own Mosaic kernels
(``%ragged-dot-none.N`` and its ``%ragged-dot-metadata.N``), whose
``op_name`` XLA sets to the kernel's and not to the scope they were
traced under: the scope finds the gathers, scatters and casts around
them, the instruction name the kernels. The cell's own partition counts
such a call under ``flash_ms`` as ``other_kernel`` (a Mosaic call that
carries no flash kernel's name); this reading lies inside the partition
and leaves it alone.
"""

from __future__ import annotations

from benchmark import hlo_counts, of_which, phase_reduce

PART = of_which.PART


def per_step_ms(record: dict, scope: str, kernel: str,
                root: str = phase_reduce.ROOT):
    """Milliseconds a step, mean over devices, or ``None`` where there is
    nothing to read: no trace, or a program no event of which carries the
    scope or the kernel's name."""
    trace = record.get("trace")
    if not trace or not trace.get("steps"):
        return None
    names = hlo_counts.load_names([{"dense_markers": [[scope, PART]],
                                    "flash_kernels": [[kernel, PART]],
                                    "phases": {PART: [PART]}}])
    names["program_scopes"] = [scope]
    if "of_which_trace" not in record:      # shared with of_which's readers
        path = phase_reduce.newest_trace(root)
        record["of_which_trace"] = of_which._without_loops(
            phase_reduce.read_trace(path, names)) if path else None
    if not record["of_which_trace"]:
        return None
    reduced = phase_reduce.reduce_phases(record["of_which_trace"], names)
    if not reduced or not (reduced["named"]["dense"]
                           or reduced["seconds"][PART] > 0.0):
        return None
    return 1e3 * reduced["seconds"][PART] / trace["steps"]
