"""``kda_ms`` (ms/step, device trace): dense device time under the scope
``hvd_kda`` (``horovod_tpu/common/scopes.py``, ``ops/linear_attention.py``):
the gated delta-rule recurrence of every KDA layer, chunked: the pair
matrices, the triangular solve, the scan over chunks, forward, the forward
run again under rematerialisation, and backward; not the projections,
convolutions and gates around it. An "of which" reading inside ``fwd_ms``
and ``bwd_ms`` (``benchmark/of_which.py``). Layer: linear attention. Moves
``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_kda"


def read(record):
    return per_step_ms(record, MARKER)
