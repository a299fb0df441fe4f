"""``dense_ms`` (ms/step, device trace): device time outside the flash
attention kernels and the collectives: XLA's own operations (fusions,
matmuls, the optimizer's update) and every Mosaic call that is no
attention kernel (a layer's own kernel, counted in that layer's part, or
XLA's ``ragged-dot``, the part ``other_kernel``), mean over devices.
Layer: model blocks. Moves ``train_tokens_per_s``."""

from benchmark.trace_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "dense_s")
