"""``ssd_roofline_pct`` (%, device trace): the least time one chip could
take for a step's state-space scans, forward and backward (the larger of
operations over the bf16 peak and bytes over the HBM peak;
``benchmark/granite_cost.py``, from the cell's shapes), over ``ssd_ms``:
the same events, XLA's or a kernel's. Layer: state-space scan. Moves
``train_tokens_per_s`` through ``ssd_ms``."""

from benchmark import granite_cost, moe_kda_cost
from benchmark.layer_metrics import ssd_ms


def read(record):
    ms = ssd_ms.read(record)
    cell = record.get("cell", {})
    if not ms or not cell.get("peaks"):
        return None
    cost = granite_cost.ssd_step_cost(
        moe_kda_cost.config_of_metric("ssd_roofline_pct"),
        cell["tokens_per_step"] // cell["chips"])
    return 100.0 * moe_kda_cost.least_ms(cost, cell["peaks"])[0] / ms
