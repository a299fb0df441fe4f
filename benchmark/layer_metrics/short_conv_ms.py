"""``short_conv_ms`` (ms/step, device trace): device time of the gated
short convolution of every convolution layer
(``horovod_tpu/ops/short_conv.py``: the two gates and the taps, ``C *
conv(B * X)``), forward, the forward run again under rematerialisation,
and backward: every dense event, an XLA operation or a Mosaic call, whose
``op_name`` holds the scope ``hvd_short_conv``
(``horovod_tpu/common/scopes.py``) or whose own instruction name holds
it. The naming contract of ``kda_ms``: a later kernel is named with the
scope's string as its prefix (``hvd_short_conv_fwd``) or called under the
scope, and nothing under ``benchmark/`` needs an edit. Not the two
projections around the chain. An "of which" reading inside ``fwd_ms`` and
``bwd_ms`` (``benchmark/of_which.py``). Layer: short convolution. Moves
``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_short_conv"


def read(record):
    return per_step_ms(record, MARKER)
