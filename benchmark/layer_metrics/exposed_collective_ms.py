"""``exposed_collective_ms`` (ms/step, device trace): the part of
``collective_ms`` during which nothing else ran on that device. Layer:
optimizer and reduction. Moves ``train_tokens_per_s`` across chips."""

from benchmark.trace_reduce import per_step_ms


def read(record):
    return per_step_ms(record, "exposed_collective_s")
