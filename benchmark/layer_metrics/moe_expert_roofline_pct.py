"""``moe_expert_roofline_pct`` (%, device trace): the least time one chip
could take for a step's grouped expert matmuls, forward and backward
(``benchmark/moe_kda_cost.py``: from the banks' shapes and from the routes
the program counted in its last step, ``hvd_tpu_moe_local_routes``), over
``moe_expert_ms``. Layer: expert layer. Moves ``train_tokens_per_s``
through ``moe_expert_ms``."""

from benchmark import moe_kda_cost
from benchmark.layer_metrics import moe_expert_ms


def read(record):
    expert_ms = moe_expert_ms.read(record)
    peaks = record.get("cell", {}).get("peaks")
    routes = moe_kda_cost.counted("hvd_tpu_moe_local_routes")
    if not expert_ms or not peaks or routes is None:
        return None
    cost = moe_kda_cost.expert_step_cost(
        moe_kda_cost.config_of_metric("moe_expert_roofline_pct"), routes)
    return 100.0 * moe_kda_cost.least_ms(cost, peaks)[0] / expert_ms
