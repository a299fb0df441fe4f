"""The one traffic generator: an open stream of token batches.

A traffic mix is a data file (``traffic/<name>.json``) of parameters this
generator reads: ``batch`` (global, all chips together), ``seq_len``, and
optionally ``score_rate`` (the share of positions a masked-LM job scores).
The same ``seed`` gives the same batches, on the host, in numpy; the
program receives only the batches.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np

_STREAM_TAG = 0x686F726F  # keeps this stream apart from other uses of a seed


def token_stream(seed: int, traffic: dict,
                 vocab_size: int) -> Iterator[Dict[str, np.ndarray]]:
    """Yields ``{"tokens": int32 (batch, seq_len + 1)}`` for ever, tokens
    uniform over the vocabulary: ``seq_len`` inputs and, for a causal job,
    the next token of each. With ``score_rate``, also ``"scored"``:
    float32 (batch, seq_len), 1.0 where the position is scored."""
    batch, seq_len = int(traffic["batch"]), int(traffic["seq_len"])
    rate = traffic.get("score_rate")
    rng = np.random.default_rng([_STREAM_TAG, int(seed)])
    while True:
        out = {"tokens": rng.integers(0, vocab_size, (batch, seq_len + 1),
                                      dtype=np.int32)}
        if rate is not None:
            out["scored"] = (rng.random((batch, seq_len)) < rate).astype(
                np.float32)
        yield out


def tokens_per_step(traffic: dict) -> int:
    return int(traffic["batch"]) * int(traffic["seq_len"])
