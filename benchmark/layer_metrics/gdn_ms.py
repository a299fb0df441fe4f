"""``gdn_ms`` (ms/step, device trace): device time of the scalar-gated
delta-rule recurrence of every Gated DeltaNet layer
(``horovod_tpu/ops/linear_attention.py`` ``gated_delta_attention``: the
running sums and the decay mask, the chunks' pair matrices, the
unit-triangular solve, the pass over chunks and its reverse pass),
forward, the forward run again under rematerialisation, and backward:
every dense event, an XLA operation or a Mosaic call, whose ``op_name``
holds the scope ``hvd_gdn`` (``horovod_tpu/common/scopes.py``) or whose
own instruction name holds it. The naming contract of ``kda_ms`` and
``ssd_ms``: a later kernel is named with the scope's string as its prefix
(``hvd_gdn_fwd``) or called under the scope, and nothing under
``benchmark/`` needs an edit. Not the convolution before it
(``short_conv_ms``), nor the projections, the L2 norms, the decays' and
the gate's arithmetic around it (``mixer_proj_ms``). An "of which" reading
inside ``fwd_ms`` and ``bwd_ms`` (``benchmark/of_which.py``); a program
without the scope (the parent's) gives nothing. Layer: linear attention.
Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_gdn"


def read(record):
    return per_step_ms(record, MARKER)
