"""``flash_dkv_ms`` (ms/step, device trace): device time in the whole flash
backward: the ``pallas_call`` named ``hvd_flash_dkv`` in
``ops/flash_attention.py``, which gives dq, dk and dv since PR 30 (and,
in an older program's trace, the call named ``hvd_flash_dq`` with it),
mean over devices (``benchmark/phase_reduce.py``). Layer: attention
kernel. Moves ``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "flash_dkv")
