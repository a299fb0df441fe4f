"""Elastic end-to-end with REAL fault injection (reference:
test/integration/elastic_common.py — launches actual elastic jobs with a
discovery script whose output the test mutates, and kills workers
mid-training).

The job runs under ``hvdtpurun --elastic --host-discovery-script`` with
virtual hosts forked locally (HVD_TPU_ELASTIC_FORCE_LOCAL — the
reference's localhost aliasing). Flow under test:

1. epoch 0: hostA+hostB train together, committing state each step;
2. at step 5 hostB's worker kills itself (hard exit) — the driver must
   blacklist hostB and restart survivors with stable ranks;
3. discovery (keyed off the kill marker) then offers hostA+hostB+hostC —
   hostB stays excluded (blacklist), hostC joins as rank 1;
4. training resumes from the last committed step and completes.
"""

import os
import stat
import sys

import pytest

from horovod_tpu.runner import launch as launch_lib

TRAIN_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import sys
import time

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.checkpoint import ObjectStore
from horovod_tpu.common.elastic import JaxState

workdir = sys.argv[1]
TOTAL = 12
hvd.init(force_cpu_devices=1)
rank = int(os.environ["HVD_TPU_PROC_ID"])
host = os.environ.get("HVD_TPU_HOSTNAME", "?")
# Virtual world (HVD_TPU_ELASTIC_FORCE_LOCAL): every worker is its own
# 1-process jax world, so the driver exports the epoch's virtual
# topology and lockstep must be simulated through the shared workdir.
peers = os.environ.get("HVD_TPU_VIRTUAL_HOSTS", "").split(",")
store = ObjectStore(os.path.join(workdir, "ckpt"))
kill_marker = os.path.join(workdir, "killed")
bprog = os.path.join(workdir, "hostB.step")


def b_step():
    try:
        return int(open(bprog).read() or 0)
    except (OSError, ValueError):
        return 0


state = JaxState(w=np.zeros(2, np.float32), step=0)
saved = store.get("state")
if saved is not None:
    for k, v in saved.items():
        setattr(state, k, v)
    state.save()

log = open(os.path.join(workdir, "progress.log"), "a")


@hvd.elastic.run
def train(state):
    while state.step < TOTAL:
        if host == "hostA" and "hostB" in peers:
            # Pace with hostB (real worlds pace via the collective;
            # independent virtual worlds must pace via the filesystem):
            # never run ahead of it while it lives...
            while not os.path.exists(kill_marker) \\
                    and b_step() < state.step:
                time.sleep(0.01)
            if os.path.exists(kill_marker):
                # ...and once it died mid-epoch, hold at a commit point
                # until the driver tears this epoch down (bounded so a
                # driver bug fails with evidence instead of hanging).
                for _ in range(150):
                    time.sleep(0.2)
                    state.commit()
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="grad")
        w = np.asarray(out.addressable_data(0)).reshape(-1)
        state.w = state.w + w
        state.step += 1
        if host == "hostB":
            with open(bprog, "w") as f:
                f.write(str(state.step))
        if (state.step == 5 and host == "hostB"
                and not os.path.exists(kill_marker)):
            open(kill_marker, "w").write("1")
            os._exit(1)  # hard failure mid-training, before commit
        state.commit()
        if rank == 0:
            store.put("state", dict(state.committed_items()))
        print(f"PROGRESS {host} rank={rank} step={state.step} "
              f"size={hvd.size()}", file=log, flush=True)


train(state)
"""

DISCOVERY_SCRIPT = """#!/bin/bash
if [ -f {workdir}/killed ]; then
  echo "hostA:1"
  echo "hostB:1"
  echo "hostC:1"
else
  echo "hostA:1"
  echo "hostB:1"
fi
"""


@pytest.mark.slow
def test_elastic_blacklist_and_resume(tmp_path, monkeypatch):
    workdir = str(tmp_path)
    train_py = os.path.join(workdir, "train.py")
    with open(train_py, "w") as f:
        f.write(TRAIN_SCRIPT)
    disco = os.path.join(workdir, "discovery.sh")
    with open(disco, "w") as f:
        f.write(DISCOVERY_SCRIPT.format(workdir=workdir))
    os.chmod(disco, os.stat(disco).st_mode | stat.S_IEXEC)

    monkeypatch.setenv("HVD_TPU_ELASTIC_FORCE_LOCAL", "1")
    monkeypatch.setenv("HVD_TPU_ELASTIC_RESET_LIMIT", "10")
    # Workers run `python /tmp/.../train.py` whose sys.path[0] is the tmp
    # dir — append (never replace) the repo root so horovod_tpu imports.
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv(
        "PYTHONPATH",
        repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    rc = launch_lib.run_commandline(
        ["-np", "2", "--elastic", "--min-np", "1", "--max-np", "3",
         "--host-discovery-script", disco, "--",
         sys.executable, train_py, workdir])
    assert rc == 0

    assert os.path.exists(os.path.join(workdir, "killed")), \
        "fault injection never fired"
    lines = open(os.path.join(workdir, "progress.log")).read().splitlines()
    recs = []
    for l in lines:
        if not l.startswith("PROGRESS"):
            continue
        parts = l.split()
        kv = dict(p.split("=") for p in parts[2:])
        recs.append((parts[1], int(kv["rank"]), int(kv["step"]),
                     int(kv["size"])))
    assert recs, "no progress recorded"

    # Training completed all steps.
    assert max(step for _, _, step, _ in recs) == 12
    # Phase 1 ran on hostB; after the failure hostB NEVER reappears
    # (blacklisted even though discovery kept listing it) and hostC joins.
    hostb_steps = [step for h, _, step, _ in recs if h == "hostB"]
    assert hostb_steps and max(hostb_steps) <= 5
    assert any(h == "hostC" for h, _, _, _ in recs), \
        "new host never joined after the topology change"
    # Rollback-to-commit: hostC's first step resumes from no later than
    # the last committed step + 1 (commits ran through step 4 before the
    # kill at step 5).
    first_c = min(step for h, _, step, _ in recs if h == "hostC")
    assert first_c <= 6
    # hostA kept rank 0 across the restart (rank stability).
    assert all(rank == 0 for h, rank, _, _ in recs if h == "hostA")


# -- scale-UP (host join, no failure) ---------------------------------------

GROW_TRAIN_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import sys

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.checkpoint import ObjectStore
from horovod_tpu.common.elastic import JaxState

workdir = sys.argv[1]
TOTAL = 12
hvd.init(force_cpu_devices=1)
rank = int(os.environ["HVD_TPU_PROC_ID"])
host = os.environ.get("HVD_TPU_HOSTNAME", "?")
# Virtual world size: under HVD_TPU_ELASTIC_FORCE_LOCAL each worker is
# its own single-process jax world, so the driver exports the epoch's
# virtual topology separately.
world = int(os.environ.get("HVD_TPU_VIRTUAL_NUM_PROC", "0")) or hvd.size()
store = ObjectStore(os.path.join(workdir, "ckpt"))

state = JaxState(w=np.zeros(2, np.float32), step=0)
saved = store.get("state")
if saved is not None:
    for k, v in saved.items():
        setattr(state, k, v)
    state.save()

log = open(os.path.join(workdir, "progress.log"), "a")


@hvd.elastic.run
def train(state):
    while state.step < TOTAL:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="grad")
        state.w = state.w + np.asarray(
            out.addressable_data(0)).reshape(-1)
        state.step += 1
        if state.step == 4 and rank == 0:
            # Announce capacity: discovery starts offering hostB. No
            # failure happens — the driver must notice the ADDITION and
            # interrupt workers at a commit boundary.
            open(os.path.join(workdir, "grow"), "w").write("1")
        if state.step >= 6 and world == 1:
            # Hold here until the join lands (discovery polls every
            # ~1s; commit() checks the topology channel and raises
            # HostsUpdatedInterrupt). Bounded so a driver bug fails the
            # test with evidence instead of hanging it.
            import time
            for _ in range(150):
                time.sleep(0.2)
                state.commit()
        state.commit()
        if rank == 0:
            store.put("state", dict(state.committed_items()))
        print(f"PROGRESS {host} rank={rank} step={state.step} "
              f"size={world}", file=log, flush=True)


train(state)
"""

GROW_DISCOVERY_SCRIPT = """#!/bin/bash
echo "hostA:1"
if [ -f {workdir}/grow ]; then
  echo "hostB:1"
fi
"""


@pytest.mark.slow
def test_elastic_scale_up_on_host_join(tmp_path, monkeypatch):
    """Reference elastic_common.py host-ADD scenario: discovery grows
    mid-training (no failure), the driver interrupts at commit(), and
    post-reset the world is LARGER with survivor ranks stable."""
    workdir = str(tmp_path)
    train_py = os.path.join(workdir, "train.py")
    with open(train_py, "w") as f:
        f.write(GROW_TRAIN_SCRIPT)
    disco = os.path.join(workdir, "discovery.sh")
    with open(disco, "w") as f:
        f.write(GROW_DISCOVERY_SCRIPT.format(workdir=workdir))
    os.chmod(disco, os.stat(disco).st_mode | stat.S_IEXEC)

    monkeypatch.setenv("HVD_TPU_ELASTIC_FORCE_LOCAL", "1")
    monkeypatch.setenv("HVD_TPU_ELASTIC_RESET_LIMIT", "10")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv(
        "PYTHONPATH",
        repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    rc = launch_lib.run_commandline(
        ["-np", "1", "--elastic", "--min-np", "1", "--max-np", "2",
         "--host-discovery-script", disco, "--",
         sys.executable, train_py, workdir])
    assert rc == 0

    recs = []
    for l in open(os.path.join(workdir, "progress.log")).read() \
            .splitlines():
        if not l.startswith("PROGRESS"):
            continue
        parts = l.split()
        kv = dict(p.split("=") for p in parts[2:])
        recs.append((parts[1], int(kv["rank"]), int(kv["step"]),
                     int(kv["size"])))
    assert recs, "no progress recorded"
    assert max(step for _, _, step, _ in recs) == 12

    # Before the join the world is 1; after the reset it is 2 — and the
    # post-reset world STAYS 2 (scale-up, not flapping).
    sizes_by_step = {}
    for _, _, step, size in recs:
        sizes_by_step.setdefault(step, set()).add(size)
    assert 1 in sizes_by_step[1], sizes_by_step
    last_sizes = sizes_by_step[max(sizes_by_step)]
    assert last_sizes == {2}, sizes_by_step
    # hostB actually trained steps.
    assert any(h == "hostB" for h, _, _, _ in recs), \
        "joined host never trained"
    # Survivor rank stability: hostA is rank 0 before AND after.
    assert all(rank == 0 for h, rank, _, _ in recs if h == "hostA")


@pytest.mark.slow
def test_elastic_reset_tool_cpu_loopback(tmp_path):
    """tools/tpu_elastic_reset.py end-to-end on the CPU loopback
    backend (the on-chip elastic-reset proof harness, VERDICT r3 #6 /
    r4 #5): train -> SIGKILL after the first save ->
    orbax restore -> persistent-compile-cache warm restart completes
    the remaining steps. Guards the harness itself so the TPU leg
    can't rot before it is first run on a chip."""
    import json
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools",
                                      "tpu_elastic_reset.py"),
         "--platform", "cpu", "--total-steps", "20",
         "--save-every", "4",
         "--ckpt-dir", str(tmp_path / "ckpt"),
         "--cache-dir", str(tmp_path / "xla_cache"),
         "--phase-timeout", "300"],
        capture_output=True, text=True, timeout=600, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(
        [l for l in proc.stdout.splitlines() if l.strip()][-1])
    assert rec["platform"] == "cpu"
    assert rec["metric"] == "elastic_reset_resume_step"
    # Killed after the first save -> resumes from a committed step and
    # completes the full horizon. 20 steps with a save every 4 leaves a
    # wide margin between the kill landing and the run finishing
    # (code-review r5: a 6-step config could complete before SIGKILL,
    # making resume_step overshoot final_step).
    assert 1 <= rec["resume_step"] <= rec["final_step"]
    assert rec["final_step"] == 19  # 20 steps, 0-indexed last
    # The warm restart must have a POPULATED persistent cache to read —
    # warm-vs-cold wall times alone cannot distinguish a working cache
    # from a silently disabled one.
    cache_files = [f for _, _, fs in os.walk(tmp_path / "xla_cache")
                   for f in fs]
    assert cache_files, "persistent compile cache is empty"
    # Structural cache-hit proof (code-review r5: wall-time bounds pass
    # even when warm == cold): phase 1 populated the cache and phase 2
    # wrote NOTHING — every phase-2 compile was served from it.
    assert rec["cache_entries_before_phase2"] > 0
    assert rec["phase2_cache_hit"] is True, \
        "phase 2 recompiled (added/rewrote persistent-cache entries)"
    assert rec["compile_s_warm"] <= rec["compile_s_cold"] * 1.5 + 0.5
