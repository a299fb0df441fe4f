"""``recompute_ms`` (ms/step, device trace): dense device time in the
rematerialised forward that runs inside the backward, which JAX itself
marks in the ``op_name`` (``.../checkpoint/rematted_computation/...``):
what ``nn.remat`` / ``jax.checkpoint`` costs the step outside the flash
kernels (a flash forward run again is in ``flash_fwd_ms``), mean over
devices. An "of which" reading inside ``bwd_ms`` and ``lm_head_ms``
(``benchmark/of_which.py``), not a part beside them. Layer: step program.
Moves ``train_tokens_per_s``."""

from benchmark.of_which import per_step_ms

MARKER = "rematted_computation"


def read(record):
    return per_step_ms(record, MARKER)
