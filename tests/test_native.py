"""Native C++ runtime tests: build, timeline writer, wire format, fusion
planner — and equivalence with the Python fallbacks (the reference's
native core is its most-tested layer; SURVEY.md §2.1)."""

import json
import os
import time

import numpy as np
import pytest

from horovod_tpu import native


pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def test_native_builds_and_loads():
    assert native.load() is not None
    assert os.path.exists(os.path.join(os.path.dirname(native.__file__),
                                       "libhvdtpu_native.so"))


def test_stale_binary_is_rebuilt_not_trusted():
    """A .so older than a source it is built from (a copied tree, a
    checkout over an old build) is rebuilt before it is loaded."""
    import subprocess
    import sys

    ndir = os.path.dirname(native.__file__)
    lib = os.path.join(ndir, "libhvdtpu_native.so")
    probe = [sys.executable, "-c",
             "from horovod_tpu import native; print(native.status())"]

    old = os.path.getmtime(os.path.join(ndir, "wire.cc")) - 10
    os.utime(lib, (old, old))
    assert subprocess.run(probe, capture_output=True, text=True,
                          timeout=120).stdout.strip() == "rebuilt"


# -- timeline --------------------------------------------------------------

def test_native_timeline_roundtrip(tmp_path):
    w = native.NativeTimelineWriter()
    path = str(tmp_path / "trace.json")
    assert w.start(path)
    for i in range(100):
        w.event(f"tensor_{i % 4}", "XLA_ALLREDUCE", "B", float(i * 10))
        w.event(f"tensor_{i % 4}", "", "E", float(i * 10 + 5))
    w.event("marker", "CYCLE", "i", 1000.0)
    w.stop()
    data = json.load(open(path))
    assert len(data["traceEvents"]) == 201
    assert data["traceEvents"][0]["ph"] == "B"
    assert w.dropped() == 0


def test_native_timeline_through_timeline_class(tmp_path):
    from horovod_tpu.common.timeline import Timeline

    path = str(tmp_path / "t.json")
    t = Timeline()
    t.start(path)
    assert t._native is not None, "Timeline must pick up native writer"
    t.begin("grad_0", "XLA_ALLREDUCE")
    t.end("grad_0")
    t.stop()
    data = json.load(open(path))
    assert len(data["traceEvents"]) == 2


def test_native_timeline_concurrent_producers(tmp_path):
    import threading

    w = native.NativeTimelineWriter()
    path = str(tmp_path / "c.json")
    assert w.start(path)

    def produce(tid):
        for i in range(500):
            w.event(f"t{tid}", "EV", "B", float(i))
            w.event(f"t{tid}", "", "E", float(i) + 0.5)

    threads = [threading.Thread(target=produce, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    w.stop()
    data = json.load(open(path))
    assert len(data["traceEvents"]) + w.dropped() == 4000


# -- wire format -----------------------------------------------------------

def test_wire_request_roundtrip():
    data = native.encode_request(3, "allreduce", 1, -1, "bfloat16",
                                 "grads/layer_7/kernel", (128, 1024))
    assert data is not None and len(data) < 64 + 32
    out = native.decode_request(data)
    assert out == (3, "allreduce", 1, -1, "bfloat16",
                   "grads/layer_7/kernel", (128, 1024))


def test_wire_request_scalar_shape():
    data = native.encode_request(0, "broadcast", 0, 2, "float32", "s", ())
    assert native.decode_request(data) == (0, "broadcast", 0, 2, "float32",
                                           "s", ())


def test_wire_response_roundtrip():
    data = native.encode_response(False, "t1", "shape mismatch on rank 2")
    ok, name, err = native.decode_response(data)
    assert (ok, name, err) == (False, "t1", "shape mismatch on rank 2")


def test_wire_decode_garbage():
    assert native.decode_request(b"\xff\x00\x01") is None
    assert native.decode_response(b"") is None


# -- fusion planner --------------------------------------------------------

def test_native_fusion_matches_python(rng):
    from horovod_tpu.common import fusion

    import jax.numpy as jnp

    leaves = [jnp.zeros(int(s), dtype=jnp.float32)
              for s in rng.integers(1, 5000, 200)]
    leaves += [jnp.zeros(int(s), dtype=jnp.int32)
               for s in rng.integers(1, 5000, 50)]
    threshold = 8192 * 4

    plan = fusion.plan_fusion(leaves, threshold)
    py_assignment = {}
    for b_id, b in enumerate(plan.buckets):
        for li in b.leaf_indices:
            py_assignment[li] = b_id

    counts = [int(np.prod(l.shape)) for l in leaves]
    codes = [0 if l.dtype == jnp.float32 else 4 for l in leaves]
    items = [4] * len(leaves)
    native_ids = native.plan_fusion_native(counts, codes, items, threshold)
    assert native_ids is not None

    # Same grouping structure: leaves share a native bucket iff they share
    # a python bucket.
    from collections import defaultdict

    py_groups = defaultdict(list)
    nat_groups = defaultdict(list)
    for i in range(len(leaves)):
        py_groups[py_assignment[i]].append(i)
        nat_groups[native_ids[i]].append(i)
    assert sorted(map(tuple, py_groups.values())) == \
        sorted(map(tuple, nat_groups.values()))


def test_native_fusion_threshold_respected():
    counts = [1000] * 10
    ids = native.plan_fusion_native(counts, [0] * 10, [4] * 10,
                                    threshold_bytes=4000 * 3)
    # 3 leaves per bucket (12000 bytes > threshold at 4th).
    assert ids == [0, 0, 0, 1, 1, 1, 2, 2, 2, 3]

# -- controller core (controller_core.cc) -----------------------------------

def test_negotiation_table_lifecycle():
    nt = native.NegotiationTable(3)
    assert nt.increment("t", 0) == 0
    assert nt.increment("t", 0) == -1           # duplicate rank
    assert nt.increment("t", 5) == -1           # out of range
    assert nt.missing_ranks("t") == [1, 2]
    assert nt.pending_count() == 1
    assert nt.increment("t", 1) == 0
    assert nt.increment("t", 2) == 1            # all in -> ready + cleared
    assert nt.pending_count() == 0
    assert nt.missing_ranks("t") is None
    # Entry resets: a new round renegotiates from scratch.
    assert nt.increment("t", 0) == 0


def test_negotiation_table_many_tensors():
    nt = native.NegotiationTable(2)
    for i in range(100):
        assert nt.increment(f"g{i}", 0) == 0
    assert nt.pending_count() == 100
    for i in range(100):
        assert nt.increment(f"g{i}", 1) == 1
    assert nt.pending_count() == 0


def test_lru_cache_eviction_order():
    c = native.ResponseCacheNative(2)
    assert not c.lookup("a")
    assert c.put("a") is None
    assert c.put("b") is None
    assert c.lookup("a")                        # refresh: b becomes LRU
    assert c.put("c") == "b"
    assert len(c) == 2
    assert c.lookup("a") and c.lookup("c") and not c.lookup("b")
    c.erase("a")
    assert not c.lookup("a") and len(c) == 1
    assert c.put("a") is None                   # reinsert after erase


def test_lru_cache_repeat_put_no_eviction():
    c = native.ResponseCacheNative(2)
    c.put("a")
    c.put("b")
    assert c.put("a") is None                   # refresh, not insert
    assert len(c) == 2


# -- GP/EI core (gp_core.cc) ------------------------------------------------

def test_gp_ei_native_matches_python():
    import math

    from horovod_tpu.common.autotune import (GaussianProcess,
                                             expected_improvement)

    rng = np.random.default_rng(0)
    xs = rng.uniform(0, 4, size=(6, 1))
    ys = -(xs[:, 0] - 2.0) ** 2 + rng.normal(0, 0.01, 6)
    ys_n = (ys - ys.mean()) / max(ys.std(), 1e-9)
    cand = np.linspace(0, 4, 9)[:, None]

    out = native.gp_ei_native(xs, ys_n, cand)
    assert out is not None
    idx, ei_native = out

    gp = GaussianProcess(length_scale=1.0)
    gp.fit(xs, ys_n)
    mu, var = gp.predict(cand)
    ei_py = expected_improvement(mu, var, ys_n.max())
    np.testing.assert_allclose(ei_native, ei_py, rtol=1e-5, atol=1e-7)
    assert idx == int(np.argmax(ei_py))


def test_gp_ei_native_prefers_peak_region():
    xs = np.array([[0.0], [1.0], [3.0], [4.0]])
    ys = -(xs[:, 0] - 2.0) ** 2
    cand = np.array([[0.5], [2.0], [3.5]])
    out = native.gp_ei_native(xs, ys, cand)
    assert out is not None and out[0] == 1


def test_negotiation_table_invalid_rank_no_phantom_entry():
    nt = native.NegotiationTable(2)
    assert nt.increment("x", -1) == -1
    assert nt.increment("x", 7) == -1
    assert nt.pending_count() == 0
    assert nt.missing_ranks("x") is None


def test_lru_put_without_evicted_key():
    c = native.ResponseCacheNative(1)
    assert c.put("a", want_evicted=False) is None
    c.put("b", want_evicted=False)          # evicts a silently
    assert len(c) == 1 and c.lookup("b") and not c.lookup("a")
