"""``kda_roofline_pct`` (%, device trace): the least time one chip could
take for a step's KDA recurrences, forward and backward (the larger of
operations over the bf16 peak and bytes over the HBM peak;
``benchmark/moe_kda_cost.py``, from the cell's shapes), over ``kda_ms``:
the same events, XLA's or a kernel's. Layer: linear attention. Moves
``train_tokens_per_s`` through ``kda_ms``."""

from benchmark import moe_kda_cost
from benchmark.layer_metrics import kda_ms


def read(record):
    ms = kda_ms.read(record)
    cell = record.get("cell", {})
    if not ms or not cell.get("peaks"):
        return None
    cost = moe_kda_cost.kda_step_cost(
        moe_kda_cost.config_of_metric("kda_roofline_pct"),
        cell["tokens_per_step"] // cell["chips"])
    return 100.0 * moe_kda_cost.least_ms(cost, cell["peaks"])[0] / ms
