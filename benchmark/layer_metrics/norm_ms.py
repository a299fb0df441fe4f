"""``norm_ms`` (ms/step, device trace): device time of the block-level
norms (before the mixer, before the feed-forward, a sandwich-normed
layer's two more) and the final norm, forward, the forward run again and
backward: every dense event whose ``op_name`` holds the scope
``hvd_norm``. A fusion is one event under the one name XLA gave it: the
part of a norm that XLA fused into the matmul that reads it counts with
that matmul, and this reads what ran as a pass of its own. A mixer's own
q / k / o norms are in ``mixer_proj_ms``. An "of which" reading inside
``fwd_ms`` and ``bwd_ms`` (``benchmark/of_which.py``). Layer: model
blocks. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_norm"


def read(record):
    return per_step_ms(record, MARKER)
