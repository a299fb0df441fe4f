"""The device a run is on, as JAX reports it, and the table of peaks."""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class NoChip(SystemExit):
    """JAX did not find the chips the cell asks for."""


def require_devices(chips: int, rehearse: bool) -> dict:
    """``{"platform", "kind", "count"}`` of ``jax.devices()``, or exit
    non-zero: JAX falls to the CPU without a word when the TPU does not
    come up, and no number from there may carry a metric's name."""
    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    want = "cpu" if rehearse else "tpu"
    if found["platform"] != want or found["count"] != chips:
        raise NoChip(f"the cell needs {chips} {want} device(s), "
                     f"JAX reports {found}")
    return found


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``. A device that is not in
    the table is an error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{_PEAKS}: add a row with its source")
    return table[kind]


def memory_stats() -> list:
    """``memory_stats()`` of every local device (``{}`` where the backend
    reports none, as the CPU does)."""
    import jax

    return [d.memory_stats() or {} for d in jax.local_devices()]
