"""``bd_noise_ms`` (ms/step, device trace): dense device time under the
scope ``hvd_bd_noise`` (``horovod_tpu/common/scopes.py``,
``models/sdar.py`` ``sdar_loss``): block-diffusion training's noise, drawn
on the device inside the step from the batch's own seed column (a rate a
block, a uniform a token, the compare), the mask token put in, ``[noisy ;
clean]`` and its positions assembled, the weights ``m / t``. A part of
``dense_ms`` of its own, which the cell's file of names
(``names/block-diffusion.json``) gives the reduction; ``None`` for a cell
that lists no such file and for a program without the scope. Layer: block
diffusion. Moves ``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms

PHASE = "bd_noise"


def read(record):
    if PHASE not in (record.get("names") or {}).get("phases", {}):
        return None
    return per_step_ms(record, PHASE)
