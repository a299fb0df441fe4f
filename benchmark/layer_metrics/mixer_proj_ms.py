"""``mixer_proj_ms`` (ms/step, device trace): device time of everything
of the token mixers that is not their kernel: the q / k / v (or fused
qkv) and output projections with biases, head reshapes, QK-norms, gates
and the rotation (``rope_ms`` lies inside it); around a linear-attention
recurrence its projections, short convolutions, gates, decay and output
norm; forward, the forward run again under rematerialisation, and
backward: every dense event whose ``op_name`` holds the scope
``hvd_mixer_proj`` (``horovod_tpu/common/scopes.py``). The flash kernels,
``hvd_kda`` and ``hvd_short_conv`` lie outside the scope. An "of which"
reading inside ``fwd_ms`` and ``bwd_ms`` (``benchmark/of_which.py``).
Layer: model blocks. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_mixer_proj"


def read(record):
    return per_step_ms(record, MARKER)
