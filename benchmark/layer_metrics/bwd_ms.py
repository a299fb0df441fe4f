"""``bwd_ms`` (ms/step, device trace): dense device time of the backward pass,
JAX's own ``transpose(`` in the ``op_name``; the vocabulary head
(``lm_head_ms``) and the flash backward (``flash_dkv_ms``) are not in it,
the part of AdamW's update that XLA fused into the weight-gradient
matmuls is, mean over devices
(``benchmark/phase_reduce.py``). Layer: model blocks. Moves
``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "bwd")
