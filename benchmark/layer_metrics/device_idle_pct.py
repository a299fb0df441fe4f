"""``device_idle_pct`` (%, device trace): 1 - (union of all
device-operation intervals / traced window), mean over devices. Layer:
device. Moves ``train_tokens_per_s``."""


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["mean"]["busy_s"] / trace["window_s"])
