"""``lm_head_ms`` (ms/step, device trace): dense device time under the scope
``hvd_lm_head``: the vocabulary matmul of ``models/gpt.py`` /
``models/bert.py``, forward and backward, with what XLA fused into it, mean
over devices (``benchmark/phase_reduce.py``). Layer: model blocks. Moves
``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "lm_head")
