"""``dequantize_ms`` (ms/step, device trace): device time of the int8
dequantise kernel (``horovod_tpu/ops/pallas_kernels.py``
``dequantize_int8``) on the gathered result of the compressed reduction,
one call a bucket on the (ranks x blocks, 32, 128) view with the mean
folded into the scales: every dense event whose own instruction name or
``op_name`` holds ``hvd_int8_dequantize``
(``horovod_tpu/common/scopes.py``). The counter that says the call
engaged: a program that dequantises the gathered int8 in XLA's own
fusions (every commit before PR 45) has no such event and the reader
gives nothing. An "of which" reading inside ``bucket_copy_ms``
(``benchmark/of_which.py``). Layer: optimizer and reduction. Moves
``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_int8_dequantize"


def read(record):
    return per_step_ms(record, MARKER)
