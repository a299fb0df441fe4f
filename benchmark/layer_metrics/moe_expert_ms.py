"""``moe_expert_ms`` (ms/step, device trace): device time of the routed
experts held here: the grouped matmuls (XLA's Mosaic kernels for
``jax.lax.ragged_dot``, told by ``ragged-dot`` in their instruction
names) and the dense work under the scope ``hvd_moe_experts`` around them
(the banks' casts, the gather of the routes' rows, the weighted
scatter-add, the SwiGLU's elementwise part), forward, run again under
rematerialisation, and backward. Not the shared expert
(``hvd_moe_shared``). An "of which" reading
(``benchmark/of_which_kernels.py``): the cell's partition counts the
Mosaic part under ``flash_ms`` as ``other_kernel``. Layer: expert layer.
Moves ``train_tokens_per_s``."""

from benchmark.of_which_kernels import per_step_ms

SCOPE = "hvd_moe_experts"
KERNEL = "ragged-dot"


def read(record):
    return per_step_ms(record, SCOPE, KERNEL)
