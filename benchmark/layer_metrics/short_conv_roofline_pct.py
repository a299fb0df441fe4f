"""``short_conv_roofline_pct`` (%, device trace): the least time one chip
could take for a step's gated short convolutions, forward and backward
(bytes over the HBM peak: the chain has no matmul;
``benchmark/lfm2_cost.py``, from the cell's shapes), over
``short_conv_ms``: the same events, XLA's or a kernel's. Layer: short
convolution. Moves ``train_tokens_per_s`` through ``short_conv_ms``."""

from benchmark import lfm2_cost, moe_kda_cost
from benchmark.layer_metrics import short_conv_ms


def read(record):
    ms = short_conv_ms.read(record)
    cell = record.get("cell", {})
    if not ms or not cell.get("peaks"):
        return None
    cost = lfm2_cost.short_conv_step_cost(
        moe_kda_cost.config_of_metric("short_conv_roofline_pct"),
        cell["tokens_per_step"] // cell["chips"])
    return 100.0 * moe_kda_cost.least_ms(cost, cell["peaks"])[0] / ms
