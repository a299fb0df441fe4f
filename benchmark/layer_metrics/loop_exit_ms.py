"""``loop_exit_ms`` (ms/step, device trace): dense device time under the
scope ``hvd_loop_exit`` (``horovod_tpu/common/scopes.py``,
``models/looplm.py``): a looped model's exit gate, the exit distribution,
its entropy and the weighting of the exits' losses, forward and backward,
mean over devices. An "of which" reading inside ``fwd_ms`` and ``bwd_ms``
(``benchmark/of_which.py``), not a part beside them. Layer: model blocks.
Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "hvd_loop_exit"


def read(record):
    return per_step_ms(record, MARKER)
