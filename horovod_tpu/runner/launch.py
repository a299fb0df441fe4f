"""hvdtpurun — the launcher CLI (horovodrun equivalent).

Reference: horovod/runner/launch.py:239-523 (argparse surface), :524-614
(_run_static), gloo_run.py:65-99 (per-slot env wiring), :226-284 (fan-out,
fail-fast). TPU-native differences:

* no MPI/gloo choice — workers bootstrap through ``jax.distributed`` whose
  coordinator runs in rank-0's process; the launcher only wires env vars
  (HVD_TPU_COORDINATOR / NUM_PROC / PROC_ID — the HOROVOD_RANK/... analog);
* one process **per host** (each process drives all local TPU chips; ranks
  are per-chip inside the SPMD program), not one per GPU;
* local mode forks subprocesses (the test/dev path — the reference's
  localhost gloo launch); multi-host mode fans out over ssh.

Config flags export the same knobs as the reference CLI
(--fusion-threshold-mb, --cycle-time-ms, --timeline-filename, ...,
launch.py:392-523 + config_parser.py).
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from . import hosts as hosts_lib
from ..common.config import runtime_env


def build_env_for_slot(base_env: Dict[str, str], coordinator: str,
                       num_proc: int, proc_id: int,
                       extra: Optional[Dict[str, str]] = None
                       ) -> Dict[str, str]:
    """Reference: gloo_run.py:65-99 slot env construction."""
    env = dict(base_env)
    env["HVD_TPU_COORDINATOR"] = coordinator
    env["HVD_TPU_NUM_PROC"] = str(num_proc)
    env["HVD_TPU_PROC_ID"] = str(proc_id)
    if num_proc > 1 and env.get("HVD_TPU_METRICS_FILE"):
        # One JSON-lines dump per worker: N processes appending
        # snapshots to one file would interleave rank states. The
        # .rank<k> suffix is what analyze_metrics.py --metrics globs to
        # build its per-rank + merged report (docs/podmon.md).
        env["HVD_TPU_METRICS_FILE"] = \
            f"{env['HVD_TPU_METRICS_FILE']}.rank{proc_id}"
    if extra:
        env.update(extra)
    return env


def _slot_local_env(local_rank: int, local_size: int) -> Dict[str, str]:
    """Per-slot local topology (reference HOROVOD_LOCAL_RANK/LOCAL_SIZE,
    gloo_run.py:65-99)."""
    return {"HVD_TPU_LOCAL_RANK": str(local_rank),
            "HVD_TPU_LOCAL_SIZE": str(local_size)}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_fail_fast(procs,
                    threads: List[threading.Thread],
                    poll_interval: float = 0.1) -> int:
    """Wait for all workers; on the FIRST non-zero exit kill the rest
    (reference fail-fast: gloo_run.py:226-284 kills the job when any slot
    exits non-zero). Polls all processes so a late-indexed crash is acted
    on while earlier workers still block on their peers."""
    rc = 0
    try:
        while True:
            running = False
            for p in procs:
                code = p.poll()
                if code is None:
                    running = True
                elif code != 0 and rc == 0:
                    rc = code
                    for q in procs:
                        if q.poll() is None:
                            q.terminate()
            if not running:
                break
            time.sleep(poll_interval)
        for t in threads:
            t.join(timeout=2)
        return rc
    except KeyboardInterrupt:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        for p in procs:
            p.wait()
        return 1


def run_local(np: int, command: List[str], env_extra: Dict[str, str],
              verbose: bool = False) -> int:
    """Fork np local worker processes (the localhost-gloo analog).
    Workers run under a pty (safe_shell_exec: children see a tty, output
    line-buffered + prefixed, group-signal termination)."""
    from . import safe_shell_exec as sse

    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    handles: List[sse.SpawnedProcess] = []
    for i in range(np):
        env = build_env_for_slot(dict(os.environ), coordinator, np, i,
                                 {**env_extra, **_slot_local_env(i, np)})
        handles.append(sse.spawn(command, env=env, prefix=str(i)))
    return _wait_fail_fast(handles, [h.thread for h in handles])


def used_hosts(host_infos: List[hosts_lib.HostInfo], np: int) -> List[str]:
    """Ordered dedup of the hosts covering ``np`` slots — the single source
    of truth for the ssh process count (shared with runner.run so the
    driver polls for exactly the result files run_ssh spawns)."""
    slots = hosts_lib.get_host_assignments(host_infos, np)
    ordered: List[str] = []
    for s in slots:
        if s.hostname not in ordered:
            ordered.append(s.hostname)
    return ordered


def run_ssh(host_infos: List[hosts_lib.HostInfo], command: List[str],
            env_extra: Dict[str, str], np: int,
            verbose: bool = False,
            ssh_port: Optional[int] = None) -> int:
    """One process per *used* host over ssh (reference gloo_run ssh
    fan-out). TPU model: ``-np`` requests total slots (chips); a host's
    process drives all of that host's assigned chips, so the process count
    is the number of hosts covering ``np`` slots — unlike local mode which
    forks one process per slot. Rank-0 host runs the jax.distributed
    coordinator."""
    from . import safe_shell_exec as sse

    hosts = used_hosts(host_infos, np)
    num_proc = len(hosts)
    coord_host = hosts[0]
    if runtime_env("NIC_DISCOVERY") == "1" and num_proc > 1:
        picked = _nic_discovery_coordinator(hosts, ssh_port)
        if picked:
            coord_host = picked
    coord = f"{coord_host}:{_free_port()}"
    handles = []
    for i, hostname in enumerate(hosts):
        # HVD_TPU_HOSTNAME rides along like the elastic/spark paths:
        # podmon.register_endpoint advertises it as the scrape address
        # (without it a remote worker falls back to loopback and the
        # driver-side aggregator scrapes itself).
        env = build_env_for_slot({}, coord, num_proc, i,
                                 {**env_extra, **_slot_local_env(0, 1),
                                  "HVD_TPU_HOSTNAME": hostname})
        # *_SECRET vars must not ride the remote argv (any local user on
        # the worker reads it via ps); they travel over ssh stdin as one
        # export line the bootstrap evals before exec'ing the command.
        secrets = {k: v for k, v in env.items() if k.endswith("_SECRET")}
        plain = {k: v for k, v in env.items() if k not in secrets}
        env_str = " ".join(f"{k}={shlex.quote(v)}"
                           for k, v in plain.items())
        remote_cmd = f"cd {shlex.quote(os.getcwd())} && {env_str} " + \
            " ".join(shlex.quote(c) for c in command)
        input_data = None
        if secrets:
            exports = " ".join(f"{k}={shlex.quote(v)}"
                               for k, v in secrets.items())
            remote_cmd = ('IFS= read -r __HVD_SECRET_ENV__ && '
                          'eval "export $__HVD_SECRET_ENV__"; '
                          + remote_cmd)
            input_data = (exports + "\n").encode()
        ssh_cmd = ["ssh", "-o", "StrictHostKeyChecking=no"]
        if ssh_port:
            ssh_cmd += ["-p", str(ssh_port)]
        ssh_cmd += [hostname, remote_cmd]
        handles.append(sse.spawn(ssh_cmd, prefix=hostname,
                                 input_data=input_data))
    return _wait_fail_fast(handles, [h.thread for h in handles])


def _nic_discovery_coordinator(hosts: List[str],
                               ssh_port: Optional[int]) -> Optional[str]:
    """Routable-NIC discovery before the fan-out (HVD_TPU_NIC_DISCOVERY=1
    — reference driver_service.py:49-257): start a task server on every
    host over ssh, intersect the registered interface sets, and return
    the rank-0 host's IP on the first common interface. Returns None
    (fall back to the hostname) on any failure — discovery must never
    make a working launch fail."""
    import select

    from . import driver_service as ds

    servers: List[subprocess.Popen] = []
    try:
        task_addrs = {}
        for hostname in hosts:
            ssh_cmd = ["ssh", "-o", "StrictHostKeyChecking=no",
                       "-o", "BatchMode=yes"]
            if ssh_port:
                ssh_cmd += ["-p", str(ssh_port)]
            # --ttl: servers self-terminate, so a dropped ssh control
            # channel cannot strand listeners on the remote host.
            ssh_cmd += [hostname, sys.executable, "-m",
                        "horovod_tpu.runner.driver_service", "--serve",
                        "--ttl", "120"]
            p = subprocess.Popen(ssh_cmd, stdout=subprocess.PIPE,
                                 stderr=subprocess.DEVNULL, text=True)
            servers.append(p)
            # Bounded banner wait — a hung host must degrade discovery,
            # not hang the launch.
            ready, _, _ = select.select([p.stdout], [], [], 20.0)
            line = (p.stdout.readline() or "").strip() if ready else ""
            if not line.startswith("TASKSERVER "):
                return None
            task_addrs[hostname] = (hostname, int(line.split()[1]))
        common = ds.discover_routable_interfaces(task_addrs)
        ifaces = ds.query_interfaces(task_addrs[hosts[0]])
        port0 = task_addrs[hosts[0]][1]
        for iface in common:
            ip = ifaces.get(iface)
            # Verify the candidate actually routes to rank 0's server
            # from here — a host-local bridge (docker0, virbr0) exists
            # everywhere but answers with the WRONG machine's stack, so
            # its probe fails and it is skipped.
            if ip and ds.probe_reachable((ip, port0)):
                return ip
        return None
    except (OSError, RuntimeError, ValueError):
        return None
    finally:
        for p in servers:
            if p.poll() is None:
                p.terminate()
        for p in servers:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def check_build() -> str:
    """Capability matrix (reference horovodrun --check-build,
    launch.py:107-143) — honest answers: shims are available when
    their framework imports; the one tensor-op plane is XLA."""
    from .. import __version__
    from ..common import basics

    def mark(v):
        return "X" if v else " "

    def importable(mod):
        import importlib.util

        return importlib.util.find_spec(mod) is not None

    return f"""\
horovod_tpu v{__version__}:

Available Frameworks:
    [X] JAX (native)
    [{mark(importable('tensorflow'))}] TensorFlow (shim)
    [{mark(importable('torch'))}] PyTorch (shim)
    [{mark(importable('mxnet'))}] MXNet (shim)

Available Controllers:
    [X] XLA single-controller (SPMD)
    [X] jax.distributed + rendezvous KV (multi-process)
    [{mark(basics.mpi_built())}] MPI
    [{mark(basics.gloo_built())}] Gloo

Available Tensor Operations:
    [{mark(basics.xla_built())}] XLA (ICI/DCN)
    [{mark(basics.nccl_built())}] NCCL
    [{mark(basics.ddl_built())}] DDL
    [{mark(basics.ccl_built())}] CCL
    [{mark(basics.mpi_built())}] MPI
    [{mark(basics.gloo_built())}] Gloo

Available Parallelism Strategies (beyond the reference):
    [X] DP (fused/hierarchical/Adasum/quantized-DCN allreduce)
    [X] TP (Megatron column/row-parallel)
    [X] PP (GPipe + interleaved 1F1B)
    [X] SP (ring attention + Ulysses)
    [X] EP (GShard top-2 MoE)
    [X] ZeRO-1 (sharded optimizer state)
    [X] FSDP/ZeRO-3 (fully-sharded parameters)"""


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    return _build_parser().parse_args(argv)


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdtpurun",
        description="Launch a horovod_tpu training job "
                    "(horovodrun equivalent for TPU).")
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="number of worker processes (default 1; on a TPU "
                        "pod an UNSET -np auto-scales to the pod's chips)")
    p.add_argument("-H", "--hosts", default=None,
                   help="host list, e.g. host1:4,host2:4")
    p.add_argument("--hostfile", default=None,
                   help="hostfile with 'hostname slots=N' lines")
    p.add_argument("--config-file", default=None,
                   help="YAML config supplying any of these flags "
                        "(explicit CLI flags win — reference "
                        "launch.py:290 --config-file)")
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("--version", action="store_true")
    p.add_argument("-cb", "--check-build", action="store_true",
                   help="print the capability matrix (reference "
                        "horovodrun --check-build, launch.py:107-143) "
                        "and exit")
    # Knob flags -> env (reference launch.py:392-523 / config_parser.py).
    p.add_argument("--fusion-threshold-mb", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--hierarchical-allreduce", action="store_true")
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--stall-check-time-seconds", type=float, default=None)
    p.add_argument("--stall-shutdown-time-seconds", type=float, default=None)
    p.add_argument("--no-stall-check", action="store_true")
    p.add_argument("--compression", default=None,
                   choices=["none", "fp16", "bf16"])
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve the Prometheus /metrics endpoint on each "
                        "worker (0 = ephemeral, logged at init; exported "
                        "as HVD_TPU_METRICS_PORT — docs/metrics.md). "
                        "With >1 worker per host pass 0: a fixed port "
                        "would collide")
    p.add_argument("--metrics-file", default=None,
                   help="per-worker metrics JSON-lines dump path "
                        "(.rank<k> is appended in multi-proc runs; "
                        "HVD_TPU_METRICS_FILE)")
    p.add_argument("--pod-metrics-port", type=int, default=None,
                   help="driver-side pod aggregator (docs/podmon.md): "
                        "scrape every worker's /metrics.json and serve "
                        "the merged rank-labeled view + "
                        "hvd_tpu_pod_step_skew_seconds on ONE "
                        "/pod/metrics endpoint at this port (0 = "
                        "ephemeral; HVD_TPU_POD_METRICS_PORT). Workers "
                        "default to --metrics-port 0 when unset so "
                        "there is something to scrape")
    p.add_argument("--log-level", default=None)
    # Elastic (reference launch.py elastic flags).
    p.add_argument("--elastic", action="store_true")
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None)
    p.add_argument("--fault-plan", default=None,
                   help="chaos: JSON fault plan (or @/path/to/plan.json) "
                        "exported to workers as HVD_TPU_FAULT_PLAN — see "
                        "horovod_tpu/common/faults.py for sites/format")
    p.add_argument("--autoscale-policy", default=None,
                   help="telemetry-driven autoscaling policy for the "
                        "elastic driver: a JSON file path or inline JSON "
                        "object (docs/autoscale.md). Validated eagerly — "
                        "a bad field fails the launch naming it. Implies "
                        "--elastic; exported as HVD_TPU_AUTOSCALE_POLICY "
                        "(+ HVD_TPU_AUTOSCALE=1)")
    p.add_argument("--autoscale-log", default=None,
                   help="driver-side autoscale decision log path "
                        "(JSON lines; HVD_TPU_AUTOSCALE_LOG)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command")
    return p


def _coerce_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes", "on")
    return bool(v)


def apply_config_file(args: argparse.Namespace,
                      argv: Optional[List[str]] = None
                      ) -> argparse.Namespace:
    """Fill unset args from a YAML config (reference launch.py:510-523 +
    config_parser.py set_args_from_config). Keys may be flat or nested
    under sections; dashes and underscores are interchangeable.

    Explicit CLI flags win — "explicit" is determined by re-parsing
    ``argv`` with SUPPRESS defaults (so ``--cache-capacity 0`` counts as
    set even though 0 is falsy, and the config CAN supply flags with
    non-None defaults like -np). Config values are coerced/validated
    through the same argparse type/choices as the CLI path.
    """
    if not getattr(args, "config_file", None):
        return args
    import yaml

    probe = _build_parser()
    actions = {}
    for a in probe._actions:
        actions[a.dest] = a
        a.default = argparse.SUPPRESS
    explicit = set(vars(probe.parse_args(argv if argv is not None
                                         else sys.argv[1:])))

    with open(args.config_file) as f:
        cfg = yaml.safe_load(f) or {}
    flat: Dict[str, object] = {}

    def walk(d):
        for k, v in d.items():
            if isinstance(v, dict):
                walk(v)
            else:
                flat[str(k).replace("-", "_")] = v

    walk(cfg)
    for k, v in flat.items():
        if k in explicit or not hasattr(args, k) or k == "config_file":
            continue
        action = actions.get(k)
        if action is not None:
            if isinstance(action, argparse._StoreTrueAction):
                v = _coerce_bool(v)
            elif action.type is not None and v is not None:
                v = action.type(v)
            if action.choices is not None and v not in action.choices:
                raise ValueError(
                    f"config file: {k}={v!r} not in {action.choices}")
        setattr(args, k, v)
    return args


def knob_env(args: argparse.Namespace) -> Dict[str, str]:
    env = {}
    if args.fusion_threshold_mb is not None:
        env["HVD_TPU_FUSION_THRESHOLD"] = str(
            int(args.fusion_threshold_mb * 1024 * 1024))
    if args.cache_capacity is not None:
        env["HVD_TPU_CACHE_CAPACITY"] = str(args.cache_capacity)
    if args.hierarchical_allreduce:
        env["HVD_TPU_HIERARCHICAL_ALLREDUCE"] = "1"
    if args.timeline_filename:
        env["HVD_TPU_TIMELINE"] = args.timeline_filename
    if args.timeline_mark_cycles:
        env["HVD_TPU_TIMELINE_MARK_CYCLES"] = "1"
    if args.stall_check_time_seconds is not None:
        env["HVD_TPU_STALL_CHECK_TIME_SECONDS"] = str(
            args.stall_check_time_seconds)
    if args.stall_shutdown_time_seconds is not None:
        env["HVD_TPU_STALL_SHUTDOWN_TIME_SECONDS"] = str(
            args.stall_shutdown_time_seconds)
    if args.no_stall_check:
        env["HVD_TPU_STALL_CHECK_DISABLE"] = "1"
    if args.compression:
        env["HVD_TPU_COMPRESSION_DTYPE"] = args.compression
    if args.autotune:
        env["HVD_TPU_AUTOTUNE"] = "1"
    if args.autotune_log_file:
        env["HVD_TPU_AUTOTUNE_LOG"] = args.autotune_log_file
    if args.metrics_port is not None:
        env["HVD_TPU_METRICS_PORT"] = str(args.metrics_port)
    if args.metrics_file:
        env["HVD_TPU_METRICS_FILE"] = args.metrics_file
    if getattr(args, "pod_metrics_port", None) is not None:
        env["HVD_TPU_POD_METRICS_PORT"] = str(args.pod_metrics_port)
        # The aggregator scrapes the workers' /metrics.json — an
        # explicit --metrics-port wins, otherwise each worker binds an
        # ephemeral endpoint and advertises it over the KV.
        env.setdefault("HVD_TPU_METRICS_PORT",
                       str(args.metrics_port
                           if args.metrics_port is not None else 0))
    if args.log_level:
        env["HVD_TPU_LOG_LEVEL"] = args.log_level
    if args.elastic:
        env["HVD_TPU_ELASTIC"] = "1"
    if args.fault_plan:
        plan = args.fault_plan
        if plan.startswith("@"):
            with open(plan[1:]) as f:
                plan = f.read()
        # Parse eagerly: a malformed plan must fail the launch, not
        # silently strip the chaos from every worker.
        from ..common.faults import FaultPlan

        FaultPlan.from_json(plan)
        env["HVD_TPU_FAULT_PLAN"] = plan
    if getattr(args, "autoscale_policy", None):
        # Parse eagerly: a typo'd threshold must fail THIS launch with
        # the field named, not silently run the job on defaults. The
        # canonical (validated) JSON is what gets exported, so file
        # paths work on the driver even when workers can't read them.
        from ..common.autoscale import AutoscalePolicy

        policy = AutoscalePolicy.load(args.autoscale_policy)
        env["HVD_TPU_AUTOSCALE"] = "1"
        env["HVD_TPU_AUTOSCALE_POLICY"] = policy.to_json()
    if getattr(args, "autoscale_log", None):
        env["HVD_TPU_AUTOSCALE_LOG"] = args.autoscale_log
    return env


def _start_pod_monitor(env_extra: Dict[str, str],
                       advertise_host: str = "127.0.0.1"):
    """Start the driver-side pod aggregator (docs/podmon.md) when
    ``HVD_TPU_POD_METRICS_PORT`` requests one for a STATIC launch.
    Without a rendezvous KV in play, one is started here purely for
    worker endpoint advertisement (workers ignore it otherwise —
    elastic host-update polling only arms under ``--elastic``).
    Returns ``(monitor, owned_rdv)``; the caller stops both."""
    from ..common import podmon as podmon_lib

    merged_env = {**os.environ, **env_extra}
    port = podmon_lib.monitor_port_from_env(merged_env)
    if port is None:
        return None, None
    from .rendezvous import RendezvousServer

    owned_rdv = None
    sources = [podmon_lib.static_endpoints(
        merged_env.get(podmon_lib.ENV_ENDPOINTS))]
    if "HVD_TPU_RENDEZVOUS" not in merged_env:
        owned_rdv = RendezvousServer("0.0.0.0")
        kv_port = owned_rdv.start()
        env_extra["HVD_TPU_RENDEZVOUS"] = f"{advertise_host}:{kv_port}"
        sources.append(podmon_lib.kv_endpoints(owned_rdv))
    monitor = podmon_lib.PodMonitor(
        podmon_lib.combined_endpoints(*sources))
    monitor.start(port)
    return monitor, owned_rdv


def run_commandline(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.version:
        from .. import __version__

        print(__version__)
        return 0
    args = apply_config_file(args, argv)
    # After the config merge so `check-build: true` in a YAML file works
    # like the flag (the config contract covers every flag).
    if args.check_build:
        print(check_build())
        return 0
    # An explicit -np 1 must survive pod auto-scaling; only an UNSET -np
    # may be grown to the pod size below.
    np_unset = args.num_proc is None
    if np_unset:
        args.num_proc = 1
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        print("hvdtpurun: no command given", file=sys.stderr)
        return 2

    env_extra = knob_env(args)

    if getattr(args, "autoscale_policy", None) and not args.elastic:
        # Autoscaling is a property of the elastic driver; the flag
        # implies the mode (scaling a static world is a contradiction).
        args.elastic = True

    if args.elastic:
        from .elastic_driver import run_elastic

        return run_elastic(args, command, env_extra)

    if args.hostfile:
        host_infos = hosts_lib.parse_host_files(args.hostfile)
    elif args.hosts:
        host_infos = hosts_lib.parse_hosts(args.hosts)
    else:
        host_infos = None
        # Inside an LSF allocation the scheduler already owns the host
        # set (reference js_run/LSFUtils detection, launch.py:672-707).
        from . import lsf as lsf_lib

        if lsf_lib.in_lsf():
            try:
                host_infos = lsf_lib.lsf_hosts()
            except RuntimeError as e:
                # A stale LSB_JOBID without host variables must not turn
                # a working local launch into a crash.
                print(f"hvdtpurun: ignoring LSF environment ({e}); "
                      "launching locally", file=sys.stderr)
        if host_infos is None:
            # On a Cloud TPU pod VM the platform publishes the full
            # topology as env metadata — no -H/--hostfile needed
            # (tpu_pod.py; SURVEY §7.6 "discovers TPU pod topology").
            from . import tpu_pod

            try:
                pod = tpu_pod.discover_pod()
            except ValueError as e:
                # Stale/inconsistent pod metadata must not turn a working
                # local launch into a crash (same contract as LSF above).
                print(f"hvdtpurun: ignoring TPU pod environment ({e}); "
                      "launching locally", file=sys.stderr)
                pod = None
            if pod is not None:
                # Single-host "pods" publish an internal IP that won't
                # match gethostname() — keep those on run_local instead
                # of demanding working ssh-to-self.
                host_infos = (pod.host_infos() if pod.num_hosts > 1
                              else None)
                if np_unset and pod.num_chips > 1:
                    print(f"hvdtpurun: TPU pod detected "
                          f"({pod.accelerator_type or 'unknown type'}, "
                          f"{pod.num_hosts} hosts x {pod.chips_per_host} "
                          f"chips); running -np {pod.num_chips}",
                          file=sys.stderr)
                    args.num_proc = pod.num_chips

    if host_infos is not None:
        # Validate np against available slots (reference: horovodrun errors
        # on -np > slots rather than oversubscribing, hosts.py:100).
        hosts_lib.get_host_assignments(host_infos, args.num_proc)

    monitor = owned_rdv = None
    try:
        if host_infos is None or all(
                h.hostname in ("localhost", "127.0.0.1",
                               socket.gethostname())
                for h in host_infos):
            monitor, owned_rdv = _start_pod_monitor(env_extra)
            return run_local(args.num_proc, command, env_extra,
                             args.verbose)
        monitor, owned_rdv = _start_pod_monitor(
            env_extra, advertise_host=socket.gethostname())
        return run_ssh(host_infos, command, env_extra, args.num_proc,
                       args.verbose, args.ssh_port)
    finally:
        if monitor is not None:
            monitor.stop()
        if owned_rdv is not None:
            owned_rdv.stop()


def main() -> None:
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
