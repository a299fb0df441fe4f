"""Ray integration (reference ray/runner.py + test/single/test_ray.py)
exercised over the process-backed fake-ray substrate
(horovod_tpu/testing/fake_ray.py — real actor PROCESSES, so the
collective test builds a genuine 2-process jax.distributed world, like
the reference's local-mode ray tests do).

Worker fns are defined inside tests so cloudpickle ships them by value.
"""

import sys

import pytest

from horovod_tpu.testing import fake_ray

# The adapter resolves `import ray` lazily at call time; route it to the
# substrate for this whole module.
sys.modules.setdefault("ray", fake_ray)

from horovod_tpu.ray import (BaseHorovodWorker, Coordinator,  # noqa: E402
                             ElasticRayExecutor, MiniSettings,
                             RayExecutor, RayHostDiscovery)

pytestmark = pytest.mark.slow

# Each fake-ray worker must stay off the TPU and see exactly ONE
# CPU device so a 2-actor world has world size 2 (same override as
# test_run_api).
WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    "HVD_TPU_FORCE_CPU_DEVICES": "1",
}


@pytest.fixture()
def ray_ctx():
    fake_ray.init()
    yield fake_ray
    fake_ray.shutdown()


# -- Coordinator (reference ray/runner.py:178-248) --------------------------

def test_coordinator_hoststring_and_envs():
    c = Coordinator(MiniSettings())
    c.register("hostA", 0)
    c.register("hostA", 1)
    c.register("hostB", 2)
    assert c.world_size == 3
    assert c.hoststring == "hostA:2,hostB:1"
    envs = c.finalize_registration()
    assert set(envs) == {0, 1, 2}
    # Global ranks
    assert [envs[r]["HVD_TPU_PROC_ID"] for r in range(3)] == \
        ["0", "1", "2"]
    # Local ranks within each host
    assert envs[0]["HVD_TPU_LOCAL_RANK"] == "0"
    assert envs[1]["HVD_TPU_LOCAL_RANK"] == "1"
    assert envs[2]["HVD_TPU_LOCAL_RANK"] == "0"
    assert envs[0]["HVD_TPU_LOCAL_SIZE"] == "2"
    assert envs[2]["HVD_TPU_LOCAL_SIZE"] == "1"
    # Every rank agrees on the rank-0-hosted coordinator address.
    addrs = {envs[r]["HVD_TPU_COORDINATOR"] for r in range(3)}
    assert len(addrs) == 1 and addrs.pop().startswith("hostA:")


# -- RayExecutor lifecycle --------------------------------------------------

def test_executor_run_rank_order(ray_ctx):
    ex = RayExecutor(RayExecutor.create_settings(60), num_workers=2,
                     env=WORKER_ENV)
    ex.start()
    try:
        def probe():
            import os

            return (int(os.environ["HVD_TPU_PROC_ID"]),
                    int(os.environ["HVD_TPU_NUM_PROC"]),
                    int(os.environ["HVD_TPU_LOCAL_RANK"]))

        results = ex.run(probe)
        assert results == [(0, 2, 0), (1, 2, 1)]
    finally:
        ex.shutdown()


def test_executor_collective_world(ray_ctx):
    """The aha test: two Ray actors form ONE jax.distributed world and a
    cross-process allreduce runs through the engine (reference
    test_ray.py test_horovod_train analog, minus the model)."""
    ex = RayExecutor(num_workers=2, env=WORKER_ENV)
    ex.start()
    try:
        def work():
            import numpy as np

            import horovod_tpu as hvd

            hvd.shutdown()
            hvd.init(force_cpu_devices=1)
            assert hvd.size() == 2, hvd.size()
            out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum)
            return np.asarray(
                out.addressable_data(0)).reshape(-1).tolist()

        results = ex.run(work)
        assert results == [[2.0] * 4, [2.0] * 4]
    finally:
        ex.shutdown()


def test_executor_executable_cls_and_execute(ray_ctx):
    class Trainer:
        def __init__(self, base):
            self.base = base

        def bump(self, k):
            self.base += k
            return self.base

    ex = RayExecutor(num_workers=2, env=WORKER_ENV)
    ex.start(executable_cls=Trainer, executable_args=[10])
    try:
        assert ex.execute(lambda t: t.bump(5)) == [15, 15]
        # State persists across execute calls (persistent actors).
        assert ex.execute(lambda t: t.bump(1)) == [16, 16]
    finally:
        ex.shutdown()


def test_executor_execute_single_and_run_remote(ray_ctx):
    ex = RayExecutor(num_workers=2, env=WORKER_ENV)
    ex.start()
    try:
        def whoami():
            import os

            return int(os.environ["HVD_TPU_PROC_ID"])

        assert ex.execute_single(whoami, rank=1) == 1
        refs = ex.run_remote(whoami)
        assert fake_ray.get(refs) == [0, 1]
    finally:
        ex.shutdown()


def test_executor_propagates_worker_error(ray_ctx):
    ex = RayExecutor(num_workers=2, env=WORKER_ENV)
    ex.start()
    try:
        def boom():
            raise ValueError("worker exploded")

        with pytest.raises(Exception, match="worker exploded"):
            ex.run(boom)
    finally:
        ex.shutdown()


def test_executor_requires_start(ray_ctx):
    ex = RayExecutor(num_workers=1)
    with pytest.raises(RuntimeError, match="not started"):
        ex.run(lambda: 1)


def test_shutdown_kills_actors(ray_ctx):
    ex = RayExecutor(num_workers=2, env=WORKER_ENV)
    ex.start()
    procs = [w._proc for w in ex.workers]
    ex.shutdown()
    for p in procs:
        p.join(timeout=10)
        assert not p.is_alive()
    assert ex.workers == []


# -- elastic discovery (reference ray/elastic.py:34-74) ---------------------

def test_ray_host_discovery(ray_ctx):
    found = RayHostDiscovery(cpus_per_slot=1).\
        find_available_hosts_and_slots()
    assert len(found) == 1
    (host, slots), = found.items()
    assert slots >= 1


def test_ray_host_discovery_gpu_empty(ray_ctx):
    # CPU-only node: GPU discovery must come back empty, not error.
    assert RayHostDiscovery(use_gpu=True).\
        find_available_hosts_and_slots() == {}


def test_elastic_ray_executor_runs(ray_ctx, monkeypatch, tmp_path):
    """ElasticRayExecutor end-to-end: slots from ray.nodes(), workers
    launched by the elastic driver, per-rank results collected
    (reference ray/elastic.py run contract)."""
    monkeypatch.setenv("HVD_TPU_ELASTIC_FORCE_LOCAL", "1")
    settings = ElasticRayExecutor.create_settings(min_np=1, max_np=2)
    ex = ElasticRayExecutor(settings,
                            env_vars={**WORKER_ENV})
    ex.start()

    def work():
        import os

        return ("done", int(os.environ["HVD_TPU_PROC_ID"]))

    results = ex.run(work)
    assert 1 <= len(results) <= 2
    assert all(r[0] == "done" for r in results)
    assert sorted(r[1] for r in results) == list(range(len(results)))


def test_elastic_collect_results_final_topology(tmp_path):
    """Stale per-rank pickles from an aborted epoch (different world
    size) are excluded; ranks order numerically, not lexically."""
    import os
    import pickle
    import time

    d = str(tmp_path)

    def drop(rank, world, value, mtime_offset):
        p = os.path.join(d, f"rank_{rank}_of_{world}.pkl")
        with open(p, "wb") as f:
            pickle.dump(value, f)
        t = time.time() + mtime_offset
        os.utime(p, (t, t))

    # Aborted 4-world epoch leftovers (older)...
    for r in range(4):
        drop(r, 4, f"stale{r}", -100)
    # ...then the final 11-world epoch (newest), enough ranks to catch
    # lexicographic ordering (rank_10 before rank_2).
    for r in range(11):
        drop(r, 11, f"final{r}", 0)

    out = ElasticRayExecutor._collect_results(d)
    assert out == [f"final{r}" for r in range(11)]


def test_elastic_ray_executor_requires_capacity(ray_ctx):
    settings = ElasticRayExecutor.create_settings(min_np=10 ** 6)
    ex = ElasticRayExecutor(settings)
    with pytest.raises(RuntimeError, match="slots"):
        ex.start()


def test_elastic_ray_executor_scales_up(ray_ctx, monkeypatch,
                                        tmp_path):
    """Ray 'cluster' grows mid-run (discovery flips from 1 to 2 hosts
    once a worker drops a marker): with max_np=None (uncapped) the
    elastic driver must interrupt and restart with the larger world —
    the scale-up contract the reference's ElasticRayExecutor rides
    Ray autoscaling for."""
    import os

    monkeypatch.setenv("HVD_TPU_ELASTIC_FORCE_LOCAL", "1")
    marker = str(tmp_path / "grow")
    sizes_log = str(tmp_path / "sizes.log")

    class GrowingDiscovery:
        def find_available_hosts_and_slots(self):
            hosts = {"hostA": 1}
            if os.path.exists(marker):
                hosts["hostB"] = 1
            return hosts

    settings = ElasticRayExecutor.create_settings(min_np=1,
                                                  timeout_s=20)
    ex = ElasticRayExecutor(settings, override_discovery=False,
                            env_vars={**WORKER_ENV})
    ex.discovery = GrowingDiscovery()
    ex.start()

    def work(marker=marker, sizes_log=sizes_log):
        import os
        import time

        import numpy as np

        import horovod_tpu as hvd
        from horovod_tpu.common.elastic import JaxState

        hvd.shutdown()
        hvd.init(force_cpu_devices=1)

        state = JaxState(step=0)

        @hvd.elastic.run
        def train(state):
            while state.step < 6:
                hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                              name="g")
                state.step += 1
                if state.step == 2 and hvd.size() == 1:
                    open(marker, "w").write("1")
                if state.step >= 3 and hvd.size() == 1:
                    # Hold until the join lands (discovery ~1s poll).
                    for _ in range(100):
                        time.sleep(0.2)
                        state.commit()
                state.commit()
                with open(sizes_log, "a") as f:
                    f.write(f"{state.step} {hvd.size()}\n")

        train(state)
        return hvd.size()

    results = ex.run(work)
    # Final world: both hosts -> 2 workers, each returning size 2.
    assert results == [2, 2]
    recs = [tuple(map(int, l.split()))
            for l in open(sizes_log).read().splitlines()]
    assert any(size == 1 for _, size in recs), "never ran small"
    assert recs[-1][1] == 2, recs[-5:]


def test_elastic_ray_executor_shrinks_on_node_death(ray_ctx,
                                                    monkeypatch,
                                                    tmp_path):
    """Node-death half of the elastic contract (VERDICT r4 #3c: the
    discovery loop under actor/node loss): RayHostDiscovery watches
    ray.nodes(); when a node dies mid-epoch (Alive=False — the actors
    it hosted die with it), the world must shrink to the survivors and
    the run complete at the smaller size. Complements
    test_elastic_ray_executor_scales_up (growth)."""
    import os
    import threading
    import time

    monkeypatch.setenv("HVD_TPU_ELASTIC_FORCE_LOCAL", "1")
    monkeypatch.setenv("HVD_TPU_ELASTIC_GRACE_SECS", "2")
    spawned = str(tmp_path / "spawned")

    fake_ray._set_nodes({"nodeA": 1.0, "nodeB": 1.0})
    try:
        settings = ElasticRayExecutor.create_settings(min_np=1,
                                                      timeout_s=30)
        ex = ElasticRayExecutor(settings, env_vars={**WORKER_ENV})
        ex.start()
        assert ex.discovery.find_available_hosts_and_slots() == \
            {"nodeA": 1, "nodeB": 1}

        def work(spawned=spawned):
            import os
            import time

            world = int(os.environ["HVD_TPU_NUM_PROC"])
            open(f"{spawned}.{os.environ['HVD_TPU_PROC_ID']}",
                 "w").close()
            if world >= 2:
                # Park until the node-death interrupt tears the epoch
                # down; survivors re-launch at world 1.
                for _ in range(600):
                    time.sleep(0.5)
                return ("never", world)
            return ("resumed", world)

        def kill_node():
            deadline = time.time() + 60.0
            while time.time() < deadline and \
                    not os.path.exists(spawned + ".1"):
                time.sleep(0.2)
            time.sleep(1.0)
            fake_ray._remove_node("nodeB")

        killer = threading.Thread(target=kill_node, daemon=True)
        killer.start()
        results = ex.run(work)
        killer.join(timeout=10.0)
        assert len(results) == 1
        assert results[0] == ("resumed", 1)
    finally:
        fake_ray._reset_nodes()
