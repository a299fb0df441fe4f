"""``gdn_roofline_pct`` (%, device trace): the least time one chip could
take for a step's scalar-gated delta-rule recurrences, forward and
backward (the larger of operations over the bf16 peak and bytes over the
HBM peak; ``benchmark/olmo_hybrid_cost.py``, from the cell's shapes), over
``gdn_ms``: the same events, XLA's or a kernel's. Nothing where the scope
is absent. Layer: linear attention. Moves ``train_tokens_per_s`` through
``gdn_ms``."""

from benchmark import moe_kda_cost, olmo_hybrid_cost
from benchmark.layer_metrics import gdn_ms


def read(record):
    ms = gdn_ms.read(record)
    cell = record.get("cell", {})
    if not ms or not cell.get("peaks"):
        return None
    cost = olmo_hybrid_cost.gdn_step_cost(
        moe_kda_cost.config_of_metric("gdn_roofline_pct"),
        cell["tokens_per_step"] // cell["chips"])
    return 100.0 * moe_kda_cost.least_ms(cost, cell["peaks"])[0] / ms
