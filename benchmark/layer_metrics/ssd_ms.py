"""``ssd_ms`` (ms/step, device trace): device time of the selective
state-space recurrence of every Mamba-2 layer (``horovod_tpu/ops/ssd.py``:
the step size, the decays, the chunks' pair matrices and states, the
carry across chunks, the ``D`` skip), forward, the forward run again under
rematerialisation, and backward: every dense event, an XLA operation or a
Mosaic call, whose ``op_name`` holds the scope ``hvd_ssd``
(``horovod_tpu/common/scopes.py``) or whose own instruction name holds
it. The naming contract of ``kda_ms``: a later kernel is named with the
scope's string as its prefix (``hvd_ssd_fwd``) or called under the scope,
and nothing under ``benchmark/`` needs an edit. Not the convolution
before it (``short_conv_ms``), nor the gated norm and the projections
around it. An "of which" reading inside ``fwd_ms`` and ``bwd_ms``
(``benchmark/of_which.py``); a program without the scope (the parent's)
gives nothing. Layer: state-space scan. Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_ssd"


def read(record):
    return per_step_ms(record, MARKER)
