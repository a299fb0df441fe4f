"""``fwd_ms`` (ms/step, device trace): dense device time of the forward pass,
JAX's own ``jvp(`` in the ``op_name``; the vocabulary head (``lm_head_ms``)
and the flash kernels (``flash_fwd_ms``) are not in it, mean over devices
(``benchmark/phase_reduce.py``). Layer: model blocks. Moves
``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "fwd")
