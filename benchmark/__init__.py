"""The benchmark of horovod_tpu: the yardstick later PRs are held to.

Everything that decides a number lives here, apart from the program: the
traffic generator, the FLOP and byte counts, the table of peaks, the
reduction from a profiler trace to metrics, the plain references and the
comparison that decides ``correct``. ``run.py`` is the entry point;
``BENCHMARK.json`` at the root of the repo is the index. PERF.md says how
a later PR adds a configuration, a family, a traffic mix, a job kind, a
cell or a per-layer metric as files of its own.
"""
