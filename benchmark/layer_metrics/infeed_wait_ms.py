"""``infeed_wait_ms`` (ms/step): how long the loop blocked in
``hvd.infeed_pipeline``'s ``next``: the growth of the program's
``hvd_tpu_infeed_wait_seconds`` over the untraced window, a step. Layer:
input. Moves ``train_tokens_per_s``."""


def read(record):
    host = record.get("host", {})
    if not host.get("steps"):
        return None
    return 1e3 * host["infeed_wait_s"] / host["steps"]
