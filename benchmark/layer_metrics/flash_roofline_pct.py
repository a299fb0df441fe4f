"""``flash_roofline_pct`` (%, device trace): the least time one chip
could take for a step's attention calls — for forward and backward each,
the larger of operations over the bf16 peak and bytes over the HBM peak
(``benchmark/flops.py``, causal half counted, head width as published and
not as padded) — over ``flash_ms``. Layer: attention kernel. Moves
``train_tokens_per_s`` through ``flash_ms``."""

from benchmark import flops
from benchmark.trace_reduce import per_step_ms


def read(record):
    flash_ms = per_step_ms(record, "flash_s")
    cell = record.get("cell", {})
    if not flash_ms or not cell.get("peaks"):
        return None
    least, _ = flops.attention_step_roofline(cell["attention"], cell["peaks"])
    return 100.0 * 1e3 * least / flash_ms
