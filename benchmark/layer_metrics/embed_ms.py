"""``embed_ms`` (ms/step, device trace): device time of the token
embedding's lookup and its cast (BERT: with the position table's slice
and the add) and, backward, of the scatter-add into the table: every
dense event whose ``op_name`` holds the scope ``hvd_embed``. An "of
which" reading inside ``fwd_ms`` and ``bwd_ms``
(``benchmark/of_which.py``). Layer: model blocks. Moves
``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_embed"


def read(record):
    return per_step_ms(record, MARKER)
