"""libtpu flag tuning for collective/compute overlap.

The overlap layer (common/overlap.py) shapes the program's DATAFLOW so
per-bucket collectives *can* start early; whether they actually run
asynchronously under compute is the compiler's call. On TPU that call is
gated by compiler flags: the latency-hiding scheduler (cost-model-driven
instruction scheduling that hoists collective-starts and sinks
collective-dones) and the async-collective-fusion passes (which split
``all-reduce`` into ``all-reduce-start``/``-done`` pairs so compute can
run in between). This module turns them on WITHOUT clobbering anything
the user already set — user-set values always win, and re-applying is a
no-op (idempotent), so init-time wiring can call it unconditionally.

The flags belong to the TPU compiler, so they travel in the variable
libtpu itself reads, ``LIBTPU_INIT_ARGS``. They do not go into
``XLA_FLAGS``: jaxlib parses that variable on every backend and aborts
the process on a name it does not know, which is every one of these.
Nothing but libtpu reads ``LIBTPU_INIT_ARGS``, so on a CPU run the
variable is inert and needs no gate.

libtpu reads it once at backend initialization, and it too aborts on an
unknown name: call :func:`enable_overlap_scheduling` (or set
``HVD_TPU_OVERLAP_XLA_FLAGS=1`` so ``hvd.init()`` does) BEFORE the first
``jax.devices()`` / ``jax.jit`` dispatch.
"""

from __future__ import annotations

import os
from typing import Mapping, MutableMapping, Optional, Tuple

LIBTPU_ENV = "LIBTPU_INIT_ARGS"

# (flag, value) pairs applied by enable_overlap_scheduling. The set
# follows the MLPerf TPU-pod recipe (arXiv:1909.09756) as carried by
# current large-scale JAX trainers: latency-hiding scheduling plus async
# collective fusion for the reduce/gather families.
TPU_OVERLAP_FLAGS: Tuple[Tuple[str, str], ...] = (
    ("--xla_tpu_enable_latency_hiding_scheduler", "true"),
    ("--xla_tpu_enable_async_collective_fusion", "true"),
    ("--xla_tpu_enable_async_collective_fusion_fuse_all_gather", "true"),
    ("--xla_tpu_enable_async_collective_fusion_multiple_steps", "true"),
    ("--xla_tpu_overlap_compute_collective_tc", "true"),
    ("--xla_enable_async_all_gather", "true"),
    ("--xla_enable_async_collective_permute", "true"),
)


def flag_name(token: str) -> str:
    """``--xla_foo=bar`` -> ``--xla_foo`` (bare ``--xla_foo`` unchanged)."""
    return token.split("=", 1)[0]


def merge_flags(existing: str,
                flags: Tuple[Tuple[str, str], ...]) -> str:
    """Append each flag not already present (by NAME — a user-set value
    for the same flag wins regardless of what it is). Existing tokens
    keep their order; merged output is stable under re-merging."""
    tokens = existing.split()
    present = {flag_name(t) for t in tokens}
    additions = [f"{name}={value}" for name, value in flags
                 if name not in present]
    return " ".join(tokens + additions)


def enable_overlap_scheduling(
        env: Optional[MutableMapping[str, str]] = None,
        extra_flags: Tuple[Tuple[str, str], ...] = ()) -> str:
    """Merge the TPU overlap flag set (plus ``extra_flags``) into
    ``env['LIBTPU_INIT_ARGS']`` and return the resulting string.

    Safe to call repeatedly — a second call changes nothing — and safe
    to call with user flags already present: only flags the user has NOT
    set are appended.
    """
    if env is None:
        env = os.environ
    merged = merge_flags(env.get(LIBTPU_ENV, ""),
                         TPU_OVERLAP_FLAGS + tuple(extra_flags))
    env[LIBTPU_ENV] = merged
    return merged


def overlap_flags_active(env: Optional[Mapping[str, str]] = None) -> bool:
    """True iff every overlap flag is present in ``LIBTPU_INIT_ARGS`` (by
    name — the user may have pinned different values)."""
    if env is None:
        env = os.environ
    present = {flag_name(t) for t in env.get(LIBTPU_ENV, "").split()}
    return all(name in present for name, _ in TPU_OVERLAP_FLAGS)
