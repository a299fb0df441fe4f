"""``loss_ms`` (ms/step, device trace): device time of the cross-entropy
after the vocabulary matmul where the loss is the program's own
(``horovod_tpu/models/looplm.py`` ``head_losses``): the log-sum-exp over
the fp32 logits and the pick of the label, forward, the forward run again
and backward: every dense event whose ``op_name`` holds the scope
``hvd_loss``, which lies beside ``hvd_lm_head`` and not inside it
(``lm_head_ms`` is the matmuls'). ``None`` in a cell whose family computes
the loss from the model's logits itself. An "of which" reading inside
``fwd_ms`` and ``bwd_ms`` (``benchmark/of_which.py``). Layer: model
blocks. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_loss"


def read(record):
    return per_step_ms(record, MARKER)
