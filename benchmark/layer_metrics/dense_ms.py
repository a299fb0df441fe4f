"""``dense_ms`` (ms/step, device trace): device time in XLA's own
operations (fusions, matmuls, the optimizer's update) outside the flash
kernels and collectives, mean over devices. Layer: model blocks. Moves
``train_tokens_per_s``."""

from benchmark.trace_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "dense_s")
