"""``bucket_copy_ms`` (ms/step, device trace): dense device time under the
scope ``hvd_reduce``: the buckets' pack (``hvd_reduce/pack``) and unpack
(``hvd_reduce/unpack``) of ``common/fusion.py`` and the scaling and casts
between them; the collectives themselves are ``collective_ms``. The
``phases`` note gives pack and unpack apart. 0 where XLA cancels the copies
(one chip), mean over devices (``benchmark/phase_reduce.py``). Layer:
optimizer and reduction. Moves ``train_tokens_per_s``."""

from benchmark.phase_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "bucket_copy")
