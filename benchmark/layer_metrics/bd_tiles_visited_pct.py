"""``bd_tiles_visited_pct`` (%, program counter): of the (query tile, key
tile) pairs of the squares the step's block-diffusion attention calls are
laid out in, the share the flash kernels visit: the static gauge
``hvd_tpu_flash_attention_tiles{mask_kind="block_diffusion", tiles=
"visited" | "square"}`` that ``ops/flash_attention.py`` sets where a call
is traced, one sample a call shape (every layer's call has the step's one
shape). The mask over ``[noisy ; clean]`` allows a quarter of the square
and the diagonals: 25% is the floor, and what stands over it is the
partial tiles of the three diagonals, which shrink with the tile. Lower
is better. ``None`` for a program that traced no such call. Layer: block
diffusion. Moves ``train_tokens_per_s``."""

GAUGE = "hvd_tpu_flash_attention_tiles"
KIND = "block_diffusion"


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
