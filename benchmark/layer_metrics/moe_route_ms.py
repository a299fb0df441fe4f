"""``moe_route_ms`` (ms/step, device trace): device time of the routing of
an expert layer (``parallel/moe.py`` ``held_experts_layer``): the router's
scores over all experts, the top-k, the normalised weights, the sort of
the routes by held expert and their counts, forward, run again under
rematerialisation, and backward (the router's two gradient matmuls):
every dense event, an XLA operation or a Mosaic call, whose ``op_name``
or own instruction name holds the scope ``hvd_moe_route``
(``horovod_tpu/common/scopes.py``); a later PR's kernel is named with the
scope's string as a prefix or called under the scope. An "of which"
reading inside ``fwd_ms`` and ``bwd_ms`` (``benchmark/of_which.py``).
Layer: expert layer. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_moe_route"


def read(record):
    return per_step_ms(record, MARKER)
