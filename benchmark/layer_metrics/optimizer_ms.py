"""``optimizer_ms`` (ms/step, device trace): dense device time under the scope
``hvd_update`` (the inner optax update in ``optim.py`` ``core_update``) plus
what follows the backward outside ``hvd_reduce`` (``optax.apply_updates``);
only what runs as events of its own, not what XLA fused into the backward's
matmuls, mean over devices (``benchmark/phase_reduce.py``). Layer: optimizer
and reduction. Moves ``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "optimizer")
