"""``rope_ms`` (ms/step, device trace): device time of the rotary position
embedding alone (``horovod_tpu/models/gpt.py`` ``rope``: the angles, the
two halves' products, the concatenate, the cast), forward, the forward
run again and backward: every dense event whose ``op_name`` holds the
scope ``hvd_rope``, which is always nested in ``hvd_mixer_proj``: an "of
which" reading inside ``mixer_proj_ms``. ``None`` in a cell whose model
rotates nothing. Layer: model blocks. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_rope"


def read(record):
    return per_step_ms(record, MARKER)
