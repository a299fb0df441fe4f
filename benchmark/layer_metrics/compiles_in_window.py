"""``compiles_in_window`` (count): JAX's ``backend_compile`` events
between the window's start and its end. Must read 0. Layer: step program.
Moves ``train_tokens_per_s``."""


def read(record):
    return record.get("host", {}).get("compiles_in_window")
