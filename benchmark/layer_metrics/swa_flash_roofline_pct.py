"""``swa_flash_roofline_pct`` (%, device trace): the least time one chip
could take for a step's window-attention calls — every window layer,
forward and backward each the larger of operations over the bf16 peak and
bytes over the HBM peak, the operations those of the (query, key) pairs
the window allows (``benchmark/laguna_cost.py``: 2 + 5 products of 2 P_w
d a head) and not of the tiles the kernels visit — over ``swa_flash_ms``.
What stands between the reading and a full layer's ``flash_roofline_pct``
is the rim: the masked-away part of the partial tiles at the window's two
edges. Layer: attention kernel. Moves ``train_tokens_per_s`` through
``swa_flash_ms``."""

from benchmark import flops, laguna_cost, moe_kda_cost
from benchmark.layer_metrics import swa_flash_ms


def read(record):
    ms = swa_flash_ms.read(record)
    cell = record.get("cell", {})
    if not ms or not cell.get("peaks"):
        return None
    attention = cell["attention"]
    cost = laguna_cost.swa_step_cost(
        moe_kda_cost.config_of_metric("swa_flash_roofline_pct"),
        attention["batch"], attention["seq_len"])
    least = sum(flops.roofline_seconds(*part, cell["peaks"])[0]
                for part in cost.values())
    return 100.0 * 1e3 * least / ms
