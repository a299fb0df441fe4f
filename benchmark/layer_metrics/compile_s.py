"""``compile_s`` (s, host clock): trace + lower + compile of the step
program, or its read from the persistent cache (the ``setup`` note says
which). Layer: entry points. Moves ``setup_s``."""


def read(record):
    return record.get("host", {}).get("compile_s")
