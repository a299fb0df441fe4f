"""``moe_expert_ms`` (ms/step, device trace): device time of the routed
experts held here, forward, run again under rematerialisation, and
backward: every dense event, an XLA operation or a Mosaic call, whose
``op_name`` or own instruction name holds the scope ``hvd_moe_experts``
(the banks' casts, the gather of the routes' rows, the weighted
scatter-add, the SwiGLU's elementwise part, and a grouped-matmul kernel
of the repo's own: name its ``pallas_call`` with the scope's string as a
prefix or call it under the scope) or ``ragged-dot`` (XLA's own Mosaic
kernels for ``jax.lax.ragged_dot``, whose ``op_name`` XLA sets to the
kernel's and not to the scope they were traced under). Not the shared
expert (``hvd_moe_shared``). An "of which" reading
(``benchmark/of_which.py``) inside ``fwd_ms``, ``bwd_ms`` and, for XLA's
kernels, ``other_kernel``. Layer: expert layer. Moves
``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

SCOPE = "hvd_moe_experts"
KERNEL = "ragged-dot"


def read(record):
    return per_step_ms(record, SCOPE, KERNEL)
