"""``quantize_ms`` (ms/step, device trace): device time of the int8
quantise kernels of the compressed reduction
(``horovod_tpu/ops/pallas_kernels.py``: every bucket once on the way to
the all-to-all, the chunk a rank owns once more on the way to the
all-gather; where the error-feedback residual is asked for, the kernel
that writes it): every dense event whose own instruction name or
``op_name`` holds ``hvd_int8_quantize``, the name of both Mosaic calls
(``hvd_int8_quantize`` round-to-nearest, ``hvd_int8_quantize_sr``
stochastic; ``horovod_tpu/common/scopes.py``), as ``short_conv_ms`` finds
its kernels. Not the thresholds' draw, which is XLA's fusion beside the
call. An "of which" reading inside ``bucket_copy_ms``
(``benchmark/of_which.py``); nothing to read where no step quantises.
Layer: optimizer and reduction. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_int8_quantize"


def read(record):
    return per_step_ms(record, MARKER)
