"""``collective_ms`` (ms/step, device trace): time a device spends in
all-reduce / reduce-scatter / all-gather / all-to-all /
collective-permute operations, a step, mean over devices. Exactly 0 on
one chip. Layer: optimizer and reduction. Moves ``train_tokens_per_s``
across chips."""

from benchmark.trace_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "collective_s")
