"""``swa_flash_ms`` (ms/step, device trace): device time in the flash
kernels' calls under ``SlidingWindowMask`` (``ops/flash_attention.py``):
the Mosaic calls named ``hvd_swa_fwd`` (a window layer's forward, and the
forward run again under the layer's rematerialisation) and ``hvd_swa_bwd``
(its one backward: dq, dk and dv), mean over devices. A phase of
``flash_ms`` of its own, which the cell's file of names
(``names/sliding-window.json``) gives the reduction: ``flash_fwd_ms`` and
``flash_dkv_ms`` then hold the full-attention layers' calls alone, and the
three sum to ``flash_ms``. ``None`` for a cell that lists no such file and
for a program without the kernels. Layer: attention kernel. Moves
``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms

PHASE = "swa_flash"


def read(record):
    if PHASE not in (record.get("names") or {}).get("phases", {}):
        return None
    return per_step_ms(record, PHASE)
