"""``step_ms_p50`` (ms, host clock): the median interval between
consecutive step completions in the untraced window. Layer: step
program. Moves ``train_tokens_per_s``."""

import statistics


def read(record):
    intervals = record.get("host", {}).get("step_intervals_s")
    if not intervals:
        return None
    return 1e3 * statistics.median(intervals)
