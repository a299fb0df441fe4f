"""``flash_ms`` (ms/step, device trace): device time in the flash
attention kernels of ``ops/flash_attention.py`` (the forward, and the one
backward that gives dq, dk and dv): the Mosaic calls that carry one of
``phase_names.json``'s ``flash_kernels`` names, and no other Mosaic call
(``trace_reduce.classify``), mean over devices. ``flash_fwd_ms`` +
``flash_dkv_ms``, exactly. Layer: attention kernel. Moves
``train_tokens_per_s``."""

from benchmark.trace_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "flash_s")
