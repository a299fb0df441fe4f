"""``mfu_pct`` (%): model FLOP/s utilization — the FLOPs a token needs
(``benchmark/flops.py``: causal counted as causal, nothing recomputed)
times this run's tokens per second in the untraced window, over chips
times the chip's published bf16 peak. A reading of ``train_tokens_per_s``
against the chip, not a kernel's roofline share. Layer: step program."""


def read(record):
    cell, host = record.get("cell", {}), record.get("host", {})
    if not cell.get("peaks") or not host.get("tokens_per_s"):
        return None
    return 100.0 * cell["flops_per_token"] * host["tokens_per_s"] / (
        cell["chips"] * cell["peaks"]["bf16_flops_per_s"])
