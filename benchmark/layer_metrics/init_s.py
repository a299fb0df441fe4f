"""``init_s`` (s, host clock): ``hvd.init()`` plus weights and optimizer
state made on the device from the seed. Layer: entry points. Moves
``setup_s``."""


def read(record):
    return record.get("host", {}).get("init_s")
