"""``flash_dq_ms`` (ms/step, device trace): device time in the flash backward
kernel for dq, the ``pallas_call`` named ``hvd_flash_dq`` in
``ops/flash_attention.py``, mean over devices
(``benchmark/phase_reduce.py``). Layer: attention kernel. Moves
``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "flash_dq")
