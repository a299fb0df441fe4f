"""``flash_ms`` (ms/step, device trace): device time in the three flash
attention kernels of ``ops/flash_attention.py`` (forward, dq, dk/dv),
mean over devices. Layer: attention kernel. Moves
``train_tokens_per_s``."""

from benchmark.trace_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "flash_s")
