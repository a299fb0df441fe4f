"""Lifecycle + topology tests (reference analog: init/rank/size checks at
the top of test/parallel/test_tensorflow.py and common/basics.py)."""

import numpy as np
import pytest


def test_init_idempotent(hvd):
    ctx1 = hvd.init()
    ctx2 = hvd.init()
    assert ctx1 is ctx2


def test_rank_size(hvd):
    assert hvd.size() == 8
    assert hvd.rank() == 0
    assert hvd.local_size() == 8
    assert hvd.cross_size() == 1
    assert hvd.is_homogeneous()


def test_mesh(hvd):
    m = hvd.mesh()
    assert m.devices.size == 8
    assert m.axis_names == (hvd.rank_axis(),)


def test_scatter_gather_roundtrip(hvd, rng):
    x = rng.standard_normal((8, 3, 5)).astype(np.float32)
    dt = hvd.scatter(x)
    assert dt.shape == (8, 3, 5)
    back = hvd.gather(dt)
    np.testing.assert_array_equal(back, x)


def test_scatter_wrong_size(hvd):
    with pytest.raises(Exception):
        hvd.scatter(np.zeros((5, 2), dtype=np.float32))


def test_not_initialized_error():
    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    if not hvd.is_initialized():
        with pytest.raises(hvd.NotInitializedError):
            basics.context()


def test_timeline_with_xprof_trace(hvd, tmp_path):
    """start_timeline(xprof_dir=...) bridges into jax.profiler so the
    device-side trace accompanies the collective lifecycle JSON."""
    import numpy as np

    tl = str(tmp_path / "tl.json")
    xprof = str(tmp_path / "xprof")
    hvd.start_timeline(tl, xprof_dir=xprof)
    out = hvd.allreduce(np.ones(4, np.float32), name="xp")
    import jax

    jax.block_until_ready(jax.tree.leaves(out))
    hvd.stop_timeline()
    import json
    import os

    events = json.load(open(tl))["traceEvents"]
    assert events
    assert os.listdir(xprof)  # jax.profiler wrote its trace directory


def test_capability_queries(hvd):
    """Reference basics.py:160-258 query surface: vendor backends are
    honestly absent, XLA is the (only) data plane, and the same answers
    are re-exported on every framework shim."""
    assert hvd.xla_built() is True
    assert hvd.mpi_built() is False and hvd.mpi_enabled() is False
    assert hvd.gloo_built() is False and hvd.gloo_enabled() is False
    assert hvd.nccl_built() == 0
    assert not hvd.ddl_built() and not hvd.ccl_built()
    assert not hvd.cuda_built() and not hvd.rocm_built()
    with pytest.raises(ValueError, match="XLA"):
        hvd.mpi_threads_supported()
    assert hvd.tpu_available() is False  # CPU loopback mesh

    import horovod_tpu.torch as hvd_torch

    assert hvd_torch.xla_built() is True and not hvd_torch.mpi_built()
    assert hvd_torch.join is not None


def test_init_says_what_it_runs_on(hvd, caplog):
    """JAX falls to the CPU silently when a TPU fails to initialise;
    init() logs platform, device_kind and count once at INFO."""
    import logging

    hvd.shutdown()
    try:
        with caplog.at_level(logging.INFO, logger="horovod_tpu"):
            hvd.init(log_level="info")
    finally:
        hvd.shutdown()
        hvd.init()
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("devices:")]
    assert said == ["devices: platform=cpu device_kind=cpu count=8"]


@pytest.fixture()
def cache_config(hvd):
    """Hands back jax's cache directory (and the runtime) as found."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    found = jax.config.jax_compilation_cache_dir
    yield
    hvd.shutdown()
    jax.config.update("jax_compilation_cache_dir", found)
    compilation_cache.reset_cache()
    hvd.init()


def test_compilation_cache_env_var_stands(tmp_path, hvd, monkeypatch,
                                          cache_config):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax's own reading of it
    stands: init() names no directory, and compiles land there."""
    import glob

    import jax
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache

    cache = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    # What jax does with the variable at import, for a process that
    # imported it long ago.
    jax.config.update("jax_compilation_cache_dir", cache)
    compilation_cache.reset_cache()
    # Entry thresholds down so CPU-fast compiles persist in the test.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        hvd.shutdown()
        hvd.init()
        assert jax.config.jax_compilation_cache_dir == cache
        out = hvd.allreduce(np.ones(12, np.float32), op=hvd.Sum,
                            name="cc_env")
        jax.block_until_ready(out)
        assert glob.glob(cache + "/*"), "no cache entries written"
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def test_compilation_cache_default_is_in_the_checkout(hvd, monkeypatch,
                                                      cache_config):
    """Unset, the cache is <checkout>/.jax_cache — derived from the
    package's location, the same on every init() of every process."""
    import os

    import jax

    from horovod_tpu.common import basics

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert basics.DEFAULT_COMPILATION_CACHE_DIR == os.path.join(
        repo, ".jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    seen = []
    for _ in range(2):
        hvd.shutdown()
        hvd.init()
        seen.append(jax.config.jax_compilation_cache_dir)
    assert seen == [basics.DEFAULT_COMPILATION_CACHE_DIR] * 2


def test_no_other_code_names_a_cache_directory():
    """One rule, one place: nothing else in the program may point jax's
    cache somewhere (bench.py and two tools each used to)."""
    import glob
    import os
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setter = re.compile(r"jax_compilation_cache_dir[\"'],")
    program = glob.glob(os.path.join(repo, "*.py")) + [
        os.path.join(root, f)
        for top in ("horovod_tpu", "tools", "examples")
        for root, _, files in os.walk(os.path.join(repo, top))
        for f in files if f.endswith(".py")]
    assert len(program) > 100
    hits = []
    for path in program:
        with open(path) as fh:
            if setter.search(fh.read()):
                hits.append(os.path.relpath(path, repo))
    assert hits == ["horovod_tpu/common/basics.py"]


def test_allgather_object_single_process(hvd):
    """Single-controller world: one object per PROCESS (not per rank) —
    the reference's per-rank gather collapses to [obj] here."""
    out = hvd.allgather_object({"r": 7, "x": [1, 2]}, name="ago")
    assert out == [{"r": 7, "x": [1, 2]}]


def test_core_broadcast_async_handle(hvd):
    import numpy as np

    x = np.arange(5, dtype=np.float32)
    h = hvd.broadcast_async(x, root_rank=0, name="core_bca")
    out = hvd.synchronize(h)
    np.testing.assert_array_equal(
        np.asarray(out.addressable_data(0))[0], x)


def test_topology_queries(hvd):
    """local/cross rank-size queries stay consistent with world size
    (reference basics.py local_rank/cross_rank surface)."""
    assert hvd.local_size() * hvd.cross_size() == hvd.size()
    assert 0 <= hvd.local_rank() < hvd.local_size()
    assert 0 <= hvd.cross_rank() < hvd.cross_size()
