"""``swa_tiles_visited_pct`` (%, program counter): of the (query tile, key
tile) pairs of the squares the step's window-attention calls are laid out
in, the share the flash kernels visit: the static gauge
``hvd_tpu_flash_attention_tiles{mask_kind="sliding_window", tiles=
"visited" | "square"}`` that ``ops/flash_attention.py`` sets where a call
is traced, one sample a call shape (every window layer's call has the
step's one shape). A window of w keys allows P_w / S^2 of the square
(6.1% at w 512, S 8192): that is the floor, and what stands over it is
the partial tiles at the window's far edge and on the diagonal, which
shrink with the tile. Lower is better. ``None`` for a program that traced
no such call. Layer: attention kernel. Moves ``train_tokens_per_s``."""

GAUGE = "hvd_tpu_flash_attention_tiles"
KIND = "sliding_window"


def read(record):
    import horovod_tpu as hvd

    tiles = {"visited": 0.0, "square": 0.0}
    for sample in hvd.metrics().get(GAUGE, {}).get("samples", []):
        labels = sample["labels"]
        if labels.get("mask_kind") == KIND and labels.get("tiles") in tiles:
            tiles[labels["tiles"]] += float(sample["value"])
    if not tiles["square"]:
        return None
    return 100.0 * tiles["visited"] / tiles["square"]
