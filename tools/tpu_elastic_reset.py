#!/usr/bin/env python
"""On-chip elastic reset proof (VERDICT r3 #6): train a few steps on the
real TPU, SIGKILL the worker mid-run, then resume from the orbax
checkpoint with the persistent XLA compilation cache warm — the
single-chip analog of the reference's
elastic integration tier (/root/reference/test/integration/
elastic_common.py:1: train, kill a worker, verify the survivors resume
from committed state).

Emits ONE JSON line:
  {"metric": "elastic_reset_resume_step", "value": <resume_step>,
   "platform": "tpu", "compile_s_cold": X, "compile_s_warm": Y, ...}

The supervisor stays off JAX and runs two *worker* subprocesses, one
after the other — a chip belongs to one process at a time: phase 1 is
killed by SIGKILL once it reports a saved step; phase 2 restores and
finishes. Both read one compile cache, placed by the one rule
(JAX_COMPILATION_CACHE_DIR, which --cache-dir sets for them, else
<checkout>/.jax_cache). Never run on a chip so far (ROADMAP queue 2
item 6).
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # workers run with sys.path[0] = tools/


def _log(msg):
    print(f"elastic_reset: {msg}", file=sys.stderr, flush=True)


# --- worker ---------------------------------------------------------------

def worker(args):
    import jax

    # Phase 2's compile of the SAME step function should hit the
    # persistent cache — the measurable "warm restart". Thresholds down
    # so this small step is kept at all.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.checkpoint import CheckpointManager
    from horovod_tpu.models.mlp import ConvNet

    hvd.init()
    platform = jax.devices()[0].platform
    _log(f"worker up: platform={platform} phase={args.phase}")

    model = ConvNet()
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (64, 28, 28, 1), jnp.float32)
    y = jax.random.randint(rng, (64,), 0, 10)
    params = model.init(rng, x)["params"]
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    def loss_fn(p):
        logits = model.apply({"params": p}, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    def step(p, st):
        l, g = jax.value_and_grad(loss_fn)(p)
        updates, st = tx.update(g, st, p)
        p = optax.apply_updates(p, updates)
        return p, st, l

    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state).compile()
    compile_s = time.perf_counter() - t0
    _log(f"compile_s={compile_s:.2f}")

    mgr = CheckpointManager(args.ckpt_dir, max_to_keep=3)
    start = 0
    if args.phase == 2:
        latest = mgr.latest_step()
        if latest is None:
            _log("phase 2 found NO checkpoint — nothing to resume")
            return 2
        restored = mgr.restore(latest, target={"params": params,
                                               "opt": opt_state})
        params, opt_state = restored["params"], restored["opt"]
        start = latest + 1
        _log(f"restored step {latest}; resuming at {start}")

    loss = None
    for i in range(start, args.total_steps):
        params, opt_state, loss = compiled(params, opt_state)
        if (i + 1) % args.save_every == 0:
            mgr.save(i, {"params": params, "opt": opt_state}, force=True)
            mgr.wait()
            # The supervisor watches for this marker to time the kill.
            print(f"SAVED_STEP {i}", flush=True)
    mgr.close()

    final_loss = float(loss) if loss is not None else -1.0
    print(json.dumps({
        "phase": args.phase, "platform": platform,
        "compile_s": round(compile_s, 2), "resume_step": start,
        "final_step": args.total_steps - 1,
        "final_loss": round(final_loss, 5)}), flush=True)
    return 0


# --- supervisor -----------------------------------------------------------

def supervise(args):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=args.cache_dir)
    if args.platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    base = [sys.executable, os.path.abspath(__file__), "--_worker",
            "--ckpt-dir", args.ckpt_dir,
            "--total-steps", str(args.total_steps),
            "--save-every", str(args.save_every),
            "--platform", args.platform]

    # Phase 1: run until the first SAVED_STEP marker, then SIGKILL — the
    # worker dies with committed state on disk, exactly the elastic
    # failure the reference injects.
    _log("phase 1: starting (will be SIGKILLed after first save)")
    p1 = subprocess.Popen(base + ["--phase", "1"], stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True, env=env)
    killed_at = None
    cold_compile = None
    t_deadline = time.time() + args.phase_timeout
    import select
    buf = ""
    while time.time() < t_deadline and killed_at is None:
        ready, _, _ = select.select([p1.stdout], [], [], 5.0)
        if not ready:
            if p1.poll() is not None:
                break
            continue
        chunk = os.read(p1.stdout.fileno(), 65536).decode("utf-8",
                                                          "replace")
        if not chunk:
            break
        buf += chunk
        while "\n" in buf:
            line, buf = buf.split("\n", 1)
            sys.stderr.write("[p1] " + line + "\n")
            if "compile_s=" in line:
                try:
                    cold_compile = float(line.rsplit("=", 1)[1])
                except ValueError:
                    pass
            if line.startswith("SAVED_STEP"):
                killed_at = int(line.split()[1])
                os.kill(p1.pid, signal.SIGKILL)
                _log(f"SIGKILLed phase-1 worker after saved step "
                     f"{killed_at}")
                break
    try:
        p1.kill()
    except OSError:
        pass
    p1.wait(timeout=30)
    if killed_at is None:
        _log("phase 1 never saved a step; aborting")
        return 1
    if cold_compile is not None:
        args.cold_compile_s = cold_compile

    # p1.wait() above has reaped the worker, and with it its hold on
    # the device.

    # Snapshot the persistent cache BEFORE phase 2: a genuine warm
    # restart reads existing entries and writes nothing, while a silent
    # cache miss recompiles and (re)writes its key. Wall-clock
    # warm-vs-cold comparison alone cannot tell these apart on fast
    # compiles (code-review r5).
    def _cache_snapshot():
        snap = {}
        for root, _, files in os.walk(args.cache_dir):
            for f in files:
                p = os.path.join(root, f)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                snap[p] = (st.st_mtime_ns, st.st_size)
        return snap

    def _cache_writes(before, after):
        """Paths phase 2 WROTE: new files, or pre-existing files whose
        size changed. A pre-existing file whose mtime moved but whose
        size didn't is classified as a READ: jax's LRU cache touches
        read entries (and maintains sidecar bookkeeping files whose
        names are a jax-internal detail — the old check hard-coded the
        '-atime' suffix and would flip phase2_cache_hit spuriously the
        day a jax upgrade renames it)."""
        return sorted(
            p for p, (mtime, size) in after.items()
            if p not in before or before[p][1] != size)

    cache_before = _cache_snapshot()

    # Phase 2: fresh process restores the checkpoint and finishes.
    _log("phase 2: resuming")
    try:
        p2 = subprocess.run(base + ["--phase", "2"], capture_output=True,
                            text=True, timeout=args.phase_timeout, env=env)
    except subprocess.TimeoutExpired:
        _log("phase 2 timed out")
        return 1
    sys.stderr.write(p2.stderr[-2000:] if p2.stderr else "")
    lines = [l for l in p2.stdout.strip().splitlines() if l.strip()]
    for l in lines:
        sys.stderr.write("[p2] " + l + "\n")
    try:
        payload = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        _log(f"phase 2 emitted no JSON (rc={p2.returncode})")
        return 1

    # Cold compile time comes from phase 1's log marker; phase 2's
    # compile of the identical function should hit the persistent cache.
    warm = payload.get("compile_s")
    cache_after = _cache_snapshot()
    cache_written = _cache_writes(cache_before, cache_after)
    result = {
        "metric": "elastic_reset_resume_step",
        "value": payload.get("resume_step"),
        "unit": "step",
        "platform": payload.get("platform"),
        "killed_after_step": killed_at,
        "resume_step": payload.get("resume_step"),
        "final_step": payload.get("final_step"),
        "final_loss": payload.get("final_loss"),
        "compile_s_warm": warm,
        "cache_entries_before_phase2": len(cache_before),
        # True iff phase 2 neither added nor rewrote any cache entry —
        # i.e. every compile in phase 2 was served from the cache
        # phase 1 populated.
        "phase2_cache_hit": not cache_written,
        "config_note": f"ConvNet adam total={args.total_steps} "
                       f"save_every={args.save_every}; SIGKILL after "
                       f"first save",
    }
    if args.cold_compile_s is not None:
        result["compile_s_cold"] = args.cold_compile_s
    print(json.dumps(result), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--_worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--phase", type=int, default=1)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(REPO, "results", "elastic_ckpt"))
    ap.add_argument("--cache-dir",
                    default=os.environ.get("JAX_COMPILATION_CACHE_DIR")
                    or os.path.join(REPO, ".jax_cache"))
    ap.add_argument("--total-steps", type=int, default=40)
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--phase-timeout", type=int, default=600)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="cpu = loopback validation of the protocol")
    ap.add_argument("--cold-compile-s", type=float, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args._worker:
        return worker(args)
    return supervise(args)


if __name__ == "__main__":
    sys.exit(main())
