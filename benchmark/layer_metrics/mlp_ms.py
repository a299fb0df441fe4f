"""``mlp_ms`` (ms/step, device trace): device time of the feed-forward
that every token meets: the dense one (in / gate / up, the activation,
out, biases) under the scope ``hvd_mlp`` and, in an expert model, the
shared expert under ``hvd_moe_shared``; forward, the forward run again
and backward. Not the routed experts (``moe_expert_ms``). An "of which"
reading inside ``fwd_ms`` and ``bwd_ms`` (``benchmark/of_which.py``).
Layer: model blocks. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKERS = ("hvd_mlp", "hvd_moe_shared")


def read(record):
    return per_step_ms(record, *MARKERS)
