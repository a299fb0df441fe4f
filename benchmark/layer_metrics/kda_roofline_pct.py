"""``kda_roofline_pct`` (%, device trace): the least time one chip could
take for a step's KDA recurrences, forward and backward (the larger of
operations over the bf16 peak and bytes over the HBM peak;
``benchmark/moe_kda_cost.py``, from the cell's shapes), over ``kda_ms``.
Layer: linear attention. Moves ``train_tokens_per_s`` through ``kda_ms``."""

from benchmark import moe_kda_cost
from benchmark.of_which import per_step_ms


def read(record):
    kda_ms = per_step_ms(record, "hvd_kda")
    cell = record.get("cell", {})
    if not kda_ms or not cell.get("peaks"):
        return None
    cost = moe_kda_cost.kda_step_cost(
        moe_kda_cost.config_of_metric("kda_roofline_pct"),
        cell["tokens_per_step"] // cell["chips"])
    return 100.0 * moe_kda_cost.least_ms(cost, cell["peaks"])[0] / kda_ms
