"""``kda_ms`` (ms/step, device trace): device time of the gated delta-rule
recurrence of every KDA layer (``ops/linear_attention.py``), forward, the
forward run again under rematerialisation, and backward: every dense
event, an XLA operation or a Mosaic call, whose ``op_name`` holds the
scope ``hvd_kda`` (``horovod_tpu/common/scopes.py``) or whose own
instruction name holds it (``%hvd_kda_bwd.9``). So the recurrence counts
here whether XLA compiles it chunk by chunk (the pair matrices, the
triangular solve, the scan over chunks) or a Pallas kernel runs it: name
the ``pallas_call`` with the scope's string as a prefix (``scopes.KDA +
"_fwd"``) or call it under the scope, and nothing under ``benchmark/``
needs an edit. Not the projections, convolutions and gates around it. An
"of which" reading inside ``fwd_ms`` and ``bwd_ms``
(``benchmark/of_which.py``). Layer: linear attention. Moves
``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_kda"


def read(record):
    return per_step_ms(record, MARKER)
