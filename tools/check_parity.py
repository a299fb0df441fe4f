#!/usr/bin/env python
"""Audit the documents (README.md, docs/*.md, the verify skill): every
file path, test and module they mention must exist, so what a reader is
sent to can't silently rot as the tree moves (``dangling_references``).
Also audits the Compression surface: every compressor
exposed on the ``Compression`` namespace (ops/compression.py) must be
documented in docs/api.md and docs/compression.md — a new wire format
(e.g. ``int8_ef``) that ships undocumented is invisible to users.
Likewise the ``hvd.metrics()`` surface: every ``hvd_tpu_*`` metric the
code registers must be documented in docs/metrics.md (an undocumented
metric is an undiscoverable one), and the top-level metrics API must
appear in docs/api.md. Exits non-zero listing dangling references.

Run: python tools/check_parity.py
"""

from __future__ import annotations

import ast
import fnmatch
import functools
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
DOC = REPO / "docs" / "parity.md"

# A whole backquoted span that is a file by its suffix (bare module
# files like `common/basics.py` resolve under horovod_tpu/, a name
# without a directory anywhere in the tree); a `:line` or `::test`
# qualifier may follow it.
_FILE_SPAN = re.compile(
    r"`([\w./-]+\.(?:py|cc|md|yml|json))(?:::?[\w:.-]+)?`")
# Anywhere, commands and code blocks included: a path under one of the
# tree's directories. A glob or a <placeholder> is not a path.
_TREE_PATH = re.compile(
    r"(?<![\w./<>*-])((?:tools|results|benchmark|tests|examples|docs)/"
    r"[\w./-]*[\w/])(?![\w./-]*[*<{])")


def documents() -> list:
    """What ``dangling_references`` is run over. The histories (PERF.md,
    ROADMAP.md, CHANGES.md, VERDICT.md) name files that are gone on
    purpose and are not checked."""
    return [REPO / "README.md", *sorted((REPO / "docs").glob("*.md")),
            REPO / ".claude" / "skills" / "verify" / "SKILL.md"]


@functools.cache
def _ignored_patterns() -> tuple:
    """.gitignore's patterns, read from the file (the driver's checkout
    has no .git to ask)."""
    lines = (REPO / ".gitignore").read_text().splitlines()
    return tuple(l.strip().rstrip("/") for l in lines
                 if l.strip() and not l.startswith("#"))


def _is_run_output(rel: str) -> bool:
    """Whether .gitignore lists ``rel`` (a path from the root): what a
    build, a test or a run leaves behind, not a file of the tree. A
    pattern with a slash is a path from the root, one without matches a
    name at any depth."""
    parts = rel.strip("/").split("/")
    for pat in _ignored_patterns():
        if "/" in pat:
            if parts[:pat.count("/") + 1] == pat.split("/"):
                return True
        elif any(fnmatch.fnmatch(part, pat) for part in parts):
            return True
    return False


@functools.cache
def _tree_names() -> tuple:
    """(the name of every file of the tree at the root and under its own
    directories, every test function under tests/). A run's outputs are
    not files of the tree, whether or not this checkout has them."""
    paths = [p for p in REPO.iterdir() if p.is_file()]
    for top in ("horovod_tpu", "tools", "tests", "benchmark", "examples",
                "docs", "results"):
        paths += [p for p in (REPO / top).rglob("*") if p.is_file()]
    files = {p.name for p in paths
             if not _is_run_output(p.relative_to(REPO).as_posix())}
    tests = set()
    for path in (REPO / "tests").rglob("*.py"):
        tests |= set(re.findall(r"^\s*def (test_\w+)", path.read_text(),
                                re.M))
    return files, tests


def _package_names(package: pathlib.Path) -> set:
    """What a package's __init__.py defines or imports (no name where
    it has none)."""
    init = package / "__init__.py"
    if not init.exists():
        return set()
    names = set()
    for node in ast.walk(ast.parse(init.read_text())):
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names |= {(a.asname or a.name).split(".")[0]
                      for a in node.names}
        elif isinstance(node, ast.Assign):
            names |= {t.id for t in node.targets
                      if isinstance(t, ast.Name)}
    return names


def dangling_references(doc: pathlib.Path) -> list:
    """What ``doc`` mentions and the tree does not hold: file paths,
    ``test_*`` modules or functions, ``horovod_tpu.x.y`` modules. A path
    that starts with ``/`` or ``horovod/`` is the reference's; a path
    .gitignore lists is a run's output, as is a file written
    ``<dir>/name``: none of them is a path of this tree, and the answer
    is the same in a checkout that holds such outputs and in one that
    does not. Glob-style references are not validated."""
    text = doc.read_text()
    files, tests = _tree_names()
    missing = []
    for ref in set(_FILE_SPAN.findall(text)) | set(_TREE_PATH.findall(text)):
        if ref.startswith(("/", "horovod/")) or _is_run_output(ref):
            continue
        if "/" not in ref:
            found = ref in files
        else:
            found = (REPO / ref).exists() \
                or (REPO / "horovod_tpu" / ref).exists()
        if not found:
            missing.append(f"path: {ref}")

    # A test_* word is a module under tests/ or a test function in one.
    for name in set(re.findall(r"\btest_[a-z0-9_]+\b", text)):
        if f"{name}.py" not in files and name not in tests:
            missing.append(f"test: {name}")

    # `pkg.func`-style claims spot-check: every `horovod_tpu.x.y` dotted
    # name mentioned must resolve to a module, or to a name a package's
    # __init__.py defines or imports.
    for dotted in set(re.findall(r"`horovod_tpu(?:\.[a-z0-9_]+)+`", text)):
        p = REPO / "horovod_tpu"
        for seg in dotted.strip("`").split(".")[1:]:
            if (p / seg).is_dir():
                p = p / seg
            elif (p / f"{seg}.py").exists() or seg in _package_names(p):
                break
            else:
                missing.append(f"module: {dotted.strip('`')}")
                break
    return [f"{doc.relative_to(REPO)}: {m}" for m in sorted(missing)]


def check_compression_surface(missing: list) -> None:
    """Names on the Compression namespace <-> docs. Parsed textually
    (no package import — this tool must run without jax installed)."""
    src = (REPO / "horovod_tpu" / "ops" / "compression.py").read_text()
    if "class Compression:" not in src:
        missing.append("compression: Compression namespace not found")
        return
    # `name = SomeCompressor` class-level assignments only occur on the
    # Compression namespace.
    names = re.findall(r"^    (\w+) = \w+Compressor$", src, re.M)
    if not names:
        missing.append("compression: no compressors on the namespace")
    api = (REPO / "docs" / "api.md")
    comp_doc = (REPO / "docs" / "compression.md")
    if not comp_doc.exists():
        missing.append("path: docs/compression.md")
    api_text = api.read_text() if api.exists() else ""
    comp_text = comp_doc.read_text() if comp_doc.exists() else ""
    for name in names:
        if name not in api_text:
            missing.append(f"compression {name}: undocumented in "
                           "docs/api.md")
        if name not in comp_text:
            missing.append(f"compression {name}: undocumented in "
                           "docs/compression.md")


def check_metrics_surface(missing: list) -> None:
    """Every metric name the package registers (the ``"hvd_tpu_*"``
    string literals passed to the registry) must be documented in
    docs/metrics.md, and the hvd.metrics()/start_metrics_server API in
    docs/api.md. Parsed textually (runs without jax installed)."""
    names = set()
    # Only names passed to a registry constructor count — a bare
    # "hvd_tpu_*" literal may be a thread name or an env value.
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*"(hvd_tpu_[a-z0-9_]+)"')
    for path in (REPO / "horovod_tpu").rglob("*.py"):
        names |= set(reg_call.findall(path.read_text()))
    if not names:
        missing.append("metrics: no hvd_tpu_* metric names registered")
        return
    doc = REPO / "docs" / "metrics.md"
    if not doc.exists():
        missing.append("path: docs/metrics.md")
        return
    text = doc.read_text()
    for n in sorted(names):
        if n not in text:
            missing.append(f"metric {n}: undocumented in docs/metrics.md")
    api = REPO / "docs" / "api.md"
    api_text = api.read_text() if api.exists() else ""
    for name in ("hvd.metrics()", "start_metrics_server"):
        if name not in api_text:
            missing.append(f"api: {name} undocumented in docs/api.md")


def check_integrity_surface(missing: list) -> None:
    """Every knob and metric of the training-integrity layer must be
    documented in docs/integrity.md: ``HVD_TPU_*`` env knobs are
    recovered from the ``_env*("NAME")`` lookups in the layer's source
    files (config.py prefixes the name), metrics from the registry
    constructor calls. Parsed textually (runs without jax installed)."""
    doc = REPO / "docs" / "integrity.md"
    if not doc.exists():
        missing.append("path: docs/integrity.md")
        return
    text = doc.read_text()
    sources = [REPO / "horovod_tpu" / "common" / "integrity.py",
               REPO / "horovod_tpu" / "checkpoint.py"]
    env_call = re.compile(r'_env(?:_int|_float|_bool)?\(\s*"([A-Z0-9_]+)"')
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*"(hvd_tpu_[a-z0-9_]+)"')
    knobs, metric_names = set(), set()
    for path in sources:
        src = path.read_text()
        knobs |= {"HVD_TPU_" + n for n in env_call.findall(src)}
        metric_names |= set(reg_call.findall(src))
    # Wired through Config rather than a local _env lookup, but part of
    # this layer's knob surface all the same.
    knobs |= {"HVD_TPU_STALL_FATAL", "HVD_TPU_NONFINITE_POLICY",
              "HVD_TPU_DIVERGE_CHECK_STEPS", "HVD_TPU_DIVERGE_POLICY",
              "HVD_TPU_CHECKPOINT_VERIFY"}
    if not metric_names:
        missing.append("integrity: no hvd_tpu_* metrics registered by "
                       "the integrity layer")
    for k in sorted(knobs):
        if k not in text:
            missing.append(f"integrity knob {k}: undocumented in "
                           "docs/integrity.md")
    for m in sorted(metric_names):
        if m not in text:
            missing.append(f"integrity metric {m}: undocumented in "
                           "docs/integrity.md")


def check_topology_surface(missing: list) -> None:
    """The topology-routing layer (docs/topology.md): its env knobs,
    its route metrics, and the router's public names must be
    documented — an undocumented WirePlan wire or knob is an
    undiscoverable one. Parsed textually (runs without jax)."""
    doc = REPO / "docs" / "topology.md"
    if not doc.exists():
        missing.append("path: docs/topology.md")
        return
    text = doc.read_text()
    for knob in ("HVD_TPU_MESH_SHAPE", "HVD_TPU_ROUTE"):
        if knob not in text:
            missing.append(f"topology knob {knob}: undocumented in "
                           "docs/topology.md")
    # Route metrics registered by the layer's source files.
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*"(hvd_tpu_[a-z0-9_]+)"')
    names = set()
    for rel in (("horovod_tpu", "ops", "collectives.py"),
                ("horovod_tpu", "ops", "adasum.py")):
        names |= set(reg_call.findall(REPO.joinpath(*rel).read_text()))
    names.add("hvd_tpu_autotune_route_index")
    for n in sorted(names):
        if n not in text:
            missing.append(f"topology metric {n}: undocumented in "
                           "docs/topology.md")
    # Public router surface must appear in the API doc.
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    src = (REPO / "horovod_tpu" / "ops" / "collectives.py").read_text()
    for name in ("WirePlan", "mesh_allreduce", "mesh_reducescatter",
                 "mesh_allgather", "mesh_wire_cost"):
        if (f"def {name}" in src or f"class {name}" in src) \
                and name not in api_text:
            missing.append(f"api: {name} undocumented in docs/api.md")


def check_autoscale_surface(missing: list) -> None:
    """The autoscaling layer (docs/autoscale.md): every
    ``HVD_TPU_AUTOSCALE_*`` knob — the enable/policy/log trio plus one
    generated ``HVD_TPU_AUTOSCALE_<FIELD>`` override per AutoscalePolicy
    field — and every ``hvd_tpu_autoscale_*`` metric must be documented
    there, or the control plane's thresholds are undiscoverable. Parsed
    textually (runs without jax installed)."""
    doc = REPO / "docs" / "autoscale.md"
    if not doc.exists():
        missing.append("path: docs/autoscale.md")
        return
    text = doc.read_text()
    src = (REPO / "horovod_tpu" / "common" / "autoscale.py").read_text()
    # Policy fields = annotated dataclass attributes of AutoscalePolicy.
    m = re.search(r"class AutoscalePolicy:.*?\n\n    @classmethod", src,
                  re.S)
    if m is None:
        missing.append("autoscale: AutoscalePolicy dataclass not found")
        return
    fields = re.findall(r"^    (\w+): (?:bool|int|float)", m.group(0),
                        re.M)
    if not fields:
        missing.append("autoscale: no AutoscalePolicy fields parsed")
    knobs = {"HVD_TPU_AUTOSCALE", "HVD_TPU_AUTOSCALE_POLICY",
             "HVD_TPU_AUTOSCALE_LOG", "HVD_TPU_DISCOVERY_DEBOUNCE"}
    knobs |= {"HVD_TPU_AUTOSCALE_" + f.upper() for f in fields}
    for k in sorted(knobs):
        if k not in text:
            missing.append(f"autoscale knob {k}: undocumented in "
                           "docs/autoscale.md")
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*"(hvd_tpu_[a-z0-9_]+)"')
    names = set(reg_call.findall(src))
    if not names:
        missing.append("autoscale: no hvd_tpu_* metrics registered by "
                       "the autoscale layer")
    for n in sorted(names):
        if n not in text:
            missing.append(f"autoscale metric {n}: undocumented in "
                           "docs/autoscale.md")
    # The field list in the doc's policy-schema table must be complete.
    for f in fields:
        if f"`{f}`" not in text:
            missing.append(f"autoscale policy field {f}: missing from "
                           "the docs/autoscale.md schema table")
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    for name in ("AutoscalePolicy", "AutoscaleEngine",
                 "--autoscale-policy"):
        if name not in api_text:
            missing.append(f"api: {name} undocumented in docs/api.md")


def check_mfu_surface(missing: list) -> None:
    """The MFU-campaign surface (docs/performance.md "MFU playbook"):
    its env knobs, the bench arms and the infeed metrics must all be
    documented — an MFU lever nobody can find is an MFU lever nobody
    pulls. Parsed textually
    (runs without jax installed)."""
    perf = REPO / "docs" / "performance.md"
    if not perf.exists():
        missing.append("path: docs/performance.md")
        return
    perf_text = perf.read_text()
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    if "MFU playbook" not in perf_text:
        missing.append('mfu: docs/performance.md lacks the '
                       '"MFU playbook" section')
    for knob in ("HVD_TPU_ACCUM_STEPS", "HVD_TPU_REMAT_POLICY",
                 "HVD_TPU_PREFETCH", "HVD_TPU_AUTO_SHARD_THRESHOLD"):
        for where, text in (("docs/performance.md", perf_text),
                            ("docs/api.md", api_text)):
            if knob not in text:
                missing.append(f"mfu knob {knob}: undocumented in "
                               f"{where}")
    # Bench arms named in the playbook so A/Bs are reproducible.
    bench_src = (REPO / "bench.py").read_text()
    for flag in ("--accum", "--remat-policy", "--prefetch",
                 "--shard-update"):
        if flag not in bench_src:
            missing.append(f"mfu: bench.py lacks the {flag} arm")
        elif flag not in perf_text:
            missing.append(f"mfu bench arm {flag}: undocumented in "
                           "docs/performance.md")
    # Infeed metrics registered by the data layer.
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*\n?\s*"(hvd_tpu_[a-z0-9_]+)"')
    names = set(reg_call.findall(
        (REPO / "horovod_tpu" / "data.py").read_text()))
    infeed = {n for n in names if n.startswith("hvd_tpu_infeed_")}
    if not infeed:
        missing.append("mfu: no hvd_tpu_infeed_* metrics registered by "
                       "horovod_tpu/data.py")
    doc = REPO / "docs" / "metrics.md"
    text = doc.read_text() if doc.exists() else ""
    for n in sorted(names):
        if n not in text:
            missing.append(f"mfu metric {n}: undocumented in "
                           "docs/metrics.md")
    # The sharding heuristic + accumulation API in the API doc.
    for name in ("accumulate_gradients", "should_shard_update",
                 "auto_shard_threshold", "DeviceInfeed"):
        if name not in api_text:
            missing.append(f"api: {name} undocumented in docs/api.md")


def check_podmon_surface(missing: list) -> None:
    """The pod-observability layer (docs/podmon.md): every
    ``HVD_TPU_FLIGHTREC_*`` / ``HVD_TPU_POD_METRICS_*`` knob, every
    flight-recorder and pod-level metric, and the ``--pod-metrics-port``
    CLI flag must be documented, and the black-box JSON schema must
    round-trip through ``tools/flight_diff.py`` — the writer's and
    reader's key tuples are compared byte for byte so the schema cannot
    drift. Parsed textually (runs without jax installed)."""
    doc = REPO / "docs" / "podmon.md"
    if not doc.exists():
        missing.append("path: docs/podmon.md")
        return
    text = doc.read_text()
    flightrec_src = (REPO / "horovod_tpu" / "common"
                     / "flightrec.py").read_text()
    podmon_src = (REPO / "horovod_tpu" / "common"
                  / "podmon.py").read_text()
    driver_src = (REPO / "horovod_tpu" / "runner"
                  / "elastic_driver.py").read_text()
    metrics_doc = REPO / "docs" / "metrics.md"
    metrics_text = metrics_doc.read_text() if metrics_doc.exists() else ""
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""

    # Knobs: every HVD_TPU_* literal the layer consults.
    env_lit = re.compile(r'"(HVD_TPU_[A-Z0-9_]+)"')
    knobs = set(env_lit.findall(flightrec_src))
    knobs |= set(env_lit.findall(podmon_src))
    knobs |= {k for k in env_lit.findall(driver_src)
              if "FLIGHTREC" in k or "POD_METRICS" in k}
    knobs |= {"HVD_TPU_METRICS_DEBUG"}       # the /debug arm switch
    # Consulted identity/env plumbing, not knobs of this layer.
    knobs -= {"HVD_TPU_RENDEZVOUS", "HVD_TPU_PROC_ID",
              "HVD_TPU_HOSTNAME", "HVD_TPU_ELASTIC_FORCE_LOCAL"}
    if not any("FLIGHTREC" in k for k in knobs):
        missing.append("podmon: no HVD_TPU_FLIGHTREC_* knobs parsed")
    for k in sorted(knobs):
        if k not in text:
            missing.append(f"podmon knob {k}: undocumented in "
                           "docs/podmon.md")

    # Metrics: registry-constructed (flightrec) + computed pod families
    # (emitted straight into the /pod/metrics exposition, so the
    # registry scan in check_metrics_surface cannot see them).
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*\n?\s*"(hvd_tpu_[a-z0-9_]+)"')
    names = set(reg_call.findall(flightrec_src))
    names |= set(re.findall(r'"(hvd_tpu_pod_[a-z0-9_]+)"', podmon_src))
    if not any(n.startswith("hvd_tpu_pod_") for n in names):
        missing.append("podmon: no hvd_tpu_pod_* families parsed")
    for n in sorted(names):
        for where, t in (("docs/podmon.md", text),
                         ("docs/metrics.md", metrics_text)):
            if n not in t:
                missing.append(f"podmon metric {n}: undocumented in "
                               f"{where}")

    # The launcher flag.
    launch_src = (REPO / "horovod_tpu" / "runner"
                  / "launch.py").read_text()
    if "--pod-metrics-port" not in launch_src:
        missing.append("podmon: launch.py lacks --pod-metrics-port")
    for where, t in (("docs/podmon.md", text), ("docs/api.md", api_text)):
        if "--pod-metrics-port" not in t:
            missing.append("podmon: --pod-metrics-port undocumented in "
                           f"{where}")
    for name in ("hvd.flight_recorder()", "flight_diff.py",
                 "/debug/stacks", "/debug/profile"):
        if name not in api_text:
            missing.append(f"api: {name} undocumented in docs/api.md")

    # Black-box schema round-trip: the writer's and the reader's key
    # tuples must be LITERALLY identical (flight_diff must run on a
    # machine with nothing but the boxes, so it carries a copy).
    tup = re.compile(
        r"^(BLACKBOX_KEYS|EVENT_KEYS) = (\([^)]*\))", re.M | re.S)
    writer = dict(tup.findall(flightrec_src))
    reader = dict(tup.findall(
        (REPO / "tools" / "flight_diff.py").read_text()))
    for key in ("BLACKBOX_KEYS", "EVENT_KEYS"):
        if key not in writer or key not in reader:
            missing.append(f"podmon schema: {key} missing from "
                           "flightrec.py or flight_diff.py")
        elif re.sub(r"\s+", " ", writer[key]) != \
                re.sub(r"\s+", " ", reader[key]):
            missing.append(
                f"podmon schema drift: {key} differs between "
                "common/flightrec.py and tools/flight_diff.py")
    ver = re.compile(r"^BLACKBOX_SCHEMA_VERSION = (\d+)", re.M)
    wv = ver.search(flightrec_src)
    rv = ver.search((REPO / "tools" / "flight_diff.py").read_text())
    if not wv or not rv or wv.group(1) != rv.group(1):
        missing.append("podmon schema drift: BLACKBOX_SCHEMA_VERSION "
                       "differs between writer and reader")


def check_moe_surface(missing: list) -> None:
    """The expert-parallel MoE hot path (docs/moe.md): every
    ``HVD_TPU_MOE_*`` knob (config.py), every ``hvd_tpu_moe_*`` /
    ``hvd_tpu_alltoall_*`` metric, the bench flags, and the public API
    names must be documented — an undocumented dispatch knob is an
    undiscoverable one. Parsed textually (runs without jax)."""
    doc = REPO / "docs" / "moe.md"
    if not doc.exists():
        missing.append("path: docs/moe.md")
        return
    text = doc.read_text()
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    metrics_doc = REPO / "docs" / "metrics.md"
    metrics_text = metrics_doc.read_text() if metrics_doc.exists() else ""

    # Knobs: the MOE_* env lookups in config.py (prefixed HVD_TPU_).
    config_src = (REPO / "horovod_tpu" / "common"
                  / "config.py").read_text()
    env_call = re.compile(r'_env(?:_int|_float|_bool)?\(\s*"(MOE_[A-Z0-9_]+)"')
    knobs = {"HVD_TPU_" + n for n in env_call.findall(config_src)}
    if not knobs:
        missing.append("moe: no HVD_TPU_MOE_* knobs parsed from "
                       "config.py")
    for k in sorted(knobs):
        if k not in text:
            missing.append(f"moe knob {k}: undocumented in docs/moe.md")

    # Metrics: hvd_tpu_moe_* (parallel/moe.py) + hvd_tpu_alltoall_*
    # (ops/collectives.py, ops/eager.py, common/autotune.py gauges).
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*\n?\s*"(hvd_tpu_[a-z0-9_]+)"')
    names = set()
    for rel in (("horovod_tpu", "parallel", "moe.py"),
                ("horovod_tpu", "ops", "collectives.py"),
                ("horovod_tpu", "ops", "eager.py"),
                ("horovod_tpu", "common", "autotune.py")):
        names |= set(reg_call.findall(REPO.joinpath(*rel).read_text()))
    names = {n for n in names
             if n.startswith("hvd_tpu_moe_")
             or n.startswith("hvd_tpu_alltoall_")
             or n == "hvd_tpu_autotune_moe_wire_index"}
    if not any(n.startswith("hvd_tpu_moe_") for n in names):
        missing.append("moe: no hvd_tpu_moe_* metrics registered")
    if not any(n.startswith("hvd_tpu_alltoall_") for n in names):
        missing.append("moe: no hvd_tpu_alltoall_* metrics registered")
    for n in sorted(names):
        for where, t in (("docs/moe.md", text),
                         ("docs/metrics.md", metrics_text)):
            if n not in t:
                missing.append(f"moe metric {n}: undocumented in "
                               f"{where}")

    # Bench flags: present in bench.py AND named in docs/moe.md.
    bench_src = (REPO / "bench.py").read_text()
    for flag in ("--moe", "--moe-wire", "--moe-overlap",
                 "--moe-router-noise"):
        if f'"{flag}"' not in bench_src:
            missing.append(f"moe: bench.py lacks the {flag} flag")
        elif flag not in text:
            missing.append(f"moe bench flag {flag}: undocumented in "
                           "docs/moe.md")

    # Public API names: if defined in source, they must appear in both
    # docs/api.md and docs/moe.md.
    api_names = {
        ("horovod_tpu", "parallel", "moe.py"): (
            "moe_layer", "top2_gating", "ep_index", "ep_size",
            "record_moe_stats", "chaos_skew_gate"),
        ("horovod_tpu", "ops", "collectives.py"): (
            "compressed_alltoall", "mesh_alltoall",
            "alltoall_wire_cost"),
        ("horovod_tpu", "common", "fusion.py"): (
            "assign_alltoall_wire",),
        ("horovod_tpu", "models", "gpt.py"): ("MoeMlp",),
        ("horovod_tpu", "common", "exceptions.py"): (
            "AlltoallvLayoutError",),
    }
    for rel, fns in api_names.items():
        src = REPO.joinpath(*rel).read_text()
        for name in fns:
            if f"def {name}" not in src and f"class {name}" not in src:
                continue
            for where, t in (("docs/api.md", api_text),
                             ("docs/moe.md", text)):
                if name not in t:
                    missing.append(f"moe api {name}: undocumented in "
                                   f"{where}")

    # The tool surface: chaos family.
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()
    if "run_moe_soak" not in soak_src or '"moe"' not in soak_src:
        missing.append("moe: chaos_soak.py lacks the moe family")
    # The fault site + hot-expert troubleshooting entry.
    faults_src = (REPO / "horovod_tpu" / "common"
                  / "faults.py").read_text()
    if '"moe_skew"' not in faults_src:
        missing.append("moe: faults.py lacks the moe_skew site")
    ts = (REPO / "docs" / "troubleshooting.md")
    ts_text = ts.read_text() if ts.exists() else ""
    if "hvd_tpu_moe_expert_load" not in ts_text:
        missing.append("moe: docs/troubleshooting.md lacks the "
                       "hot-expert entry reading the load gauge")


def check_serve_surface(missing: list) -> None:
    """The inference-serving subsystem (docs/serve.md): every
    ``HVD_TPU_SERVE_*`` knob (explicit literals in the serve package
    plus one generated ``HVD_TPU_SERVE_<FIELD>`` override per SLOPolicy
    field), every ``hvd_tpu_serve_*`` metric, the ``hvd.serve`` public
    API names, the bench/chaos surfaces, and the fault site must all be
    documented — an undocumented serving knob is an undiscoverable one.
    Parsed textually (runs without jax installed)."""
    doc = REPO / "docs" / "serve.md"
    if not doc.exists():
        missing.append("path: docs/serve.md")
        return
    text = doc.read_text()
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    metrics_doc = REPO / "docs" / "metrics.md"
    metrics_text = metrics_doc.read_text() if metrics_doc.exists() else ""
    serve_dir = REPO / "horovod_tpu" / "serve"
    sources = {p.name: p.read_text()
               for p in sorted(serve_dir.glob("*.py"))}
    if not sources:
        missing.append("serve: horovod_tpu/serve/ has no sources")
        return

    # Knobs: explicit HVD_TPU_SERVE_* literals + one generated
    # override per SLOPolicy field (controller.from_env).
    knobs = set()
    env_lit = re.compile(r'"(HVD_TPU_SERVE_[A-Z0-9_]+)"')
    for src in sources.values():
        knobs |= set(env_lit.findall(src))
    m = re.search(r"class SLOPolicy:.*?\n\n    @classmethod",
                  sources.get("controller.py", ""), re.S)
    if m is None:
        missing.append("serve: SLOPolicy dataclass not found")
        return
    fields = re.findall(r"^    (\w+): (?:bool|int|float|str)",
                        m.group(0), re.M)
    if not fields:
        missing.append("serve: no SLOPolicy fields parsed")
    knobs |= {"HVD_TPU_SERVE_" + f.upper() for f in fields}
    for k in sorted(knobs):
        if k not in text:
            missing.append(f"serve knob {k}: undocumented in "
                           "docs/serve.md")
    for f in fields:
        if f"`{f}`" not in text:
            missing.append(f"serve policy field {f}: missing from the "
                           "docs/serve.md schema table")

    # Metrics registered by the serve package.
    reg_call = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*\n?\s*"(hvd_tpu_[a-z0-9_]+)"')
    names = set()
    for src in sources.values():
        names |= set(reg_call.findall(src))
    if not any(n.startswith("hvd_tpu_serve_") for n in names):
        missing.append("serve: no hvd_tpu_serve_* metrics registered")
    for n in sorted(names):
        for where, t in (("docs/serve.md", text),
                         ("docs/metrics.md", metrics_text)):
            if n not in t:
                missing.append(f"serve metric {n}: undocumented in "
                               f"{where}")

    # Public API names: defined in source -> documented in both docs.
    api_names = {
        "queue.py": ("Request", "RequestQueue", "insert_by_arrival"),
        "traffic.py": ("TrafficTrace", "poisson_trace"),
        "engine.py": ("DecodeEngine", "make_engine_factory",
                      "compile_programs", "compile_spec_programs"),
        "batcher.py": ("ContinuousBatcher",),
        "controller.py": ("SLOPolicy", "ServeController",
                          "ServeCluster"),
        "kvcache.py": ("init_cache", "export_slot", "import_slot",
                       "rewind_slots"),
        "prefix.py": ("PrefixCache",),
    }
    for fname, fns in api_names.items():
        src = sources.get(fname, "")
        for name in fns:
            if f"def {name}" not in src and f"class {name}" not in src:
                continue
            for where, t in (("docs/api.md", api_text),
                             ("docs/serve.md", text)):
                if name not in t:
                    missing.append(f"serve api {name}: undocumented "
                                   f"in {where}")
    gpt_src = (REPO / "horovod_tpu" / "models" / "gpt.py").read_text()
    if "def init_kv_cache" in gpt_src:
        for where, t in (("docs/api.md", api_text),
                         ("docs/serve.md", text)):
            if "init_kv_cache" not in t:
                missing.append("serve api init_kv_cache: undocumented "
                               f"in {where}")

    # Bench + chaos + fault-site surfaces.
    bench_src = (REPO / "bench.py").read_text()
    for flag in ("--serve", "--serve-replicas", "--serve-kv",
                 "--serve-requests", "--serve-rate", "--serve-seed",
                 "--serve-arm"):
        if f'"{flag}"' not in bench_src:
            missing.append(f"serve: bench.py lacks the {flag} flag")
        elif flag not in text:
            missing.append(f"serve bench flag {flag}: undocumented in "
                           "docs/serve.md")
    if '"workload": "serve"' not in bench_src:
        missing.append("serve: bench.py serve records lack the "
                       "workload tag")
    if '"arm": args.serve_arm' not in bench_src:
        missing.append("serve: bench.py serve records lack the "
                       "arm tag")
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()
    if "run_serve_soak" not in soak_src or '"serve"' not in soak_src:
        missing.append("serve: chaos_soak.py lacks the serve family")
    if "run_serve_disagg_soak" not in soak_src \
            or '"serve_disagg"' not in soak_src:
        missing.append("serve: chaos_soak.py lacks the serve_disagg "
                       "family")
    if "serve_disagg" not in text:
        missing.append("serve: docs/serve.md does not describe the "
                       "serve_disagg chaos family")
    faults_src = (REPO / "horovod_tpu" / "common"
                  / "faults.py").read_text()
    if '"replica_kill"' not in faults_src:
        missing.append("serve: faults.py lacks the replica_kill site")
    ts = (REPO / "docs" / "troubleshooting.md")
    ts_text = ts.read_text() if ts.exists() else ""
    if "hvd_tpu_serve_queue_depth" not in ts_text:
        missing.append("serve: docs/troubleshooting.md lacks the "
                       "queue-backlog entry reading the depth gauge")


def check_serve_trace_surface(missing: list) -> None:
    """The request-scoped tracing + goodput surface (docs/serve.md
    "Tracing & goodput"): the span-schema literals must be byte-level
    identical between the writer (serve/tracing.py) and the post-mortem
    reader (tools/analyze_serve.py, which must run on a machine with
    nothing but the dump), the three trace knobs must be registered and
    documented, and every observability outlet the tracer feeds
    (podmon /pod/serve, bench record fields, the slow-request runbook)
    must exist. Parsed textually (runs without jax installed)."""
    tracing_path = REPO / "horovod_tpu" / "serve" / "tracing.py"
    analyze_path = REPO / "tools" / "analyze_serve.py"
    if not tracing_path.exists():
        missing.append("path: horovod_tpu/serve/tracing.py")
        return
    if not analyze_path.exists():
        missing.append("path: tools/analyze_serve.py")
        return
    writer_src = tracing_path.read_text()
    reader_src = analyze_path.read_text()
    text = (REPO / "docs" / "serve.md").read_text() \
        if (REPO / "docs" / "serve.md").exists() else ""

    # Span-schema round-trip: writer and reader tuples must be
    # LITERALLY identical (same contract as the flightrec black box).
    tup = re.compile(r"^TRACE_SPAN_KEYS = (\([^)]*\))", re.M | re.S)
    wt, rt = tup.search(writer_src), tup.search(reader_src)
    if not wt or not rt:
        missing.append("serve trace schema: TRACE_SPAN_KEYS missing "
                       "from tracing.py or analyze_serve.py")
    elif re.sub(r"\s+", " ", wt.group(1)) != \
            re.sub(r"\s+", " ", rt.group(1)):
        missing.append("serve trace schema drift: TRACE_SPAN_KEYS "
                       "differs between serve/tracing.py and "
                       "tools/analyze_serve.py")
    ver = re.compile(r"^TRACE_SCHEMA_VERSION = (\d+)", re.M)
    wv, rv = ver.search(writer_src), ver.search(reader_src)
    if not wv or not rv or wv.group(1) != rv.group(1):
        missing.append("serve trace schema drift: TRACE_SCHEMA_VERSION "
                       "differs between writer and reader")

    # Knobs: registered in config.RUNTIME_KNOBS + documented.
    cfg_src = (REPO / "horovod_tpu" / "common" / "config.py").read_text()
    for knob in ("SERVE_TRACE", "SERVE_TRACE_DIR", "SERVE_TRACE_SIZE"):
        if f'"{knob}"' not in cfg_src:
            missing.append(f"serve trace: config.py RUNTIME_KNOBS "
                           f"lacks {knob}")
        if f"HVD_TPU_{knob}" not in text:
            missing.append(f"serve trace knob HVD_TPU_{knob}: "
                           "undocumented in docs/serve.md")

    # The podmon outlet: /pod/serve endpoint + docs.
    podmon_src = (REPO / "horovod_tpu" / "common"
                  / "podmon.py").read_text()
    pod_text = (REPO / "docs" / "podmon.md").read_text() \
        if (REPO / "docs" / "podmon.md").exists() else ""
    if '"/pod/serve"' not in podmon_src:
        missing.append("serve trace: podmon.py lacks the /pod/serve "
                       "endpoint")
    for where, t in (("docs/serve.md", text),
                     ("docs/podmon.md", pod_text)):
        if "/pod/serve" not in t:
            missing.append(f"serve trace: /pod/serve undocumented in "
                           f"{where}")

    # The post-mortem outlet: analyze_serve --flight correlation +
    # the slow-request runbook.
    if '"--flight"' not in reader_src:
        missing.append("serve trace: analyze_serve.py lacks the "
                       "--flight correlation flag")
    ts_text = (REPO / "docs" / "troubleshooting.md").read_text() \
        if (REPO / "docs" / "troubleshooting.md").exists() else ""
    if "analyze_serve.py" not in ts_text:
        missing.append("serve trace: docs/troubleshooting.md lacks the "
                       "slow-request runbook (analyze_serve.py)")
    if "analyze_serve.py" not in text:
        missing.append("serve trace: analyze_serve.py undocumented in "
                       "docs/serve.md")

    # The bench outlet: per-phase percentiles + goodput in the serve
    # BENCH record.
    bench_src = (REPO / "bench.py").read_text()
    for field in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s",
                  "tpot_p99_s", "queue_wait_p50_s", "queue_wait_p99_s",
                  "goodput"):
        if f'"{field}"' not in bench_src:
            missing.append(f"serve trace: bench.py serve record lacks "
                           f"{field}")

    # The chaos determinism surface: the trace summary joins the
    # byte-compared sequences when tracing is on.
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()
    if soak_src.count('sequences["trace"]') < 2:
        missing.append("serve trace: chaos_soak.py serve families do "
                       "not bank the trace summary in sequences")


def check_overload_surface(missing: list) -> None:
    """The multi-tenant overload-control surface (docs/serve.md
    "Overload & tenancy"): the SLO-class table and brownout ladder must
    exist with the documented rung order, the SLOPolicy overload fields
    and shed/reject/brownout metric families must be present and
    documented, the operator knobs must be registered, the terminal
    phases the zero-silent-drops contract counts must agree between the
    tracer and the post-mortem reader, and every evidence surface
    (chaos family, banked fleetsim storm, bench A/B arm, brownout
    runbook) must exist. Parsed textually (runs without jax)."""
    ov_path = REPO / "horovod_tpu" / "serve" / "overload.py"
    if not ov_path.exists():
        missing.append("path: horovod_tpu/serve/overload.py")
        return
    ov_src = ov_path.read_text()
    text = (REPO / "docs" / "serve.md").read_text() \
        if (REPO / "docs" / "serve.md").exists() else ""

    # The ladder: four rungs, mildest first, literally in this order.
    rungs = ("spec_off", "clamp_tokens", "shed_batch",
             "reject_admission")
    m = re.search(r"^BROWNOUT_RUNGS = \(([^)]*)\)", ov_src, re.M | re.S)
    if not m:
        missing.append("overload: overload.py lacks BROWNOUT_RUNGS")
    elif tuple(re.findall(r'"(\w+)"', m.group(1))) != rungs:
        missing.append("overload: BROWNOUT_RUNGS order drifted from "
                       "the documented ladder "
                       "(spec_off -> reject_admission)")
    if 'SLO_CLASSES = ("latency", "throughput", "batch")' not in ov_src:
        missing.append("overload: overload.py lacks the three-tier "
                       "SLO_CLASSES tuple")
    for sym in ("class SLOClass", "class BrownoutLadder",
                "def admission_estimate"):
        if sym not in ov_src:
            missing.append(f"overload: overload.py lacks {sym}")

    # Lazy exports on hvd.serve.
    init_src = (REPO / "horovod_tpu" / "serve"
                / "__init__.py").read_text()
    for sym in ("SLOClass", "BrownoutLadder", "SLO_CLASSES",
                "BROWNOUT_RUNGS"):
        if f'"{sym}"' not in init_src:
            missing.append(f"overload: serve/__init__.py does not "
                           f"lazy-export {sym}")

    # SLOPolicy carries the class table + ladder tuning as data.
    ctl_src = (REPO / "horovod_tpu" / "serve"
               / "controller.py").read_text()
    for field in ("overload", "latency_deadline_s",
                  "throughput_deadline_s", "batch_priority",
                  "admission_safety", "brownout_enter_depth",
                  "brownout_exit_depth", "brownout_enter_ticks",
                  "brownout_exit_ticks", "brownout_clamp_tokens"):
        if not re.search(rf"^\s+{field}\s*[:=]", ctl_src, re.M):
            missing.append(f"overload: SLOPolicy lacks field {field}")

    # Metric families registered in source + documented.
    queue_src = (REPO / "horovod_tpu" / "serve" / "queue.py").read_text()
    metrics_text = (REPO / "docs" / "metrics.md").read_text() \
        if (REPO / "docs" / "metrics.md").exists() else ""
    for name, src, where in (
            ("hvd_tpu_serve_shed_total", ov_src, "overload.py"),
            ("hvd_tpu_serve_brownout_level", ov_src, "overload.py"),
            ("hvd_tpu_serve_rejected_total", queue_src, "queue.py")):
        if f'"{name}"' not in src:
            missing.append(f"overload: {where} does not register "
                           f"{name}")
        if name not in metrics_text:
            missing.append(f"overload: {name} undocumented in "
                           "docs/metrics.md")

    # Operator knobs: registered + documented.
    cfg_src = (REPO / "horovod_tpu" / "common" / "config.py").read_text()
    for knob in ("SERVE_BROWNOUT", "SERVE_CLASS_MIX"):
        if f'"{knob}"' not in cfg_src:
            missing.append(f"overload: config.py RUNTIME_KNOBS lacks "
                           f"{knob}")
        if f"HVD_TPU_{knob}" not in text:
            missing.append(f"overload knob HVD_TPU_{knob}: "
                           "undocumented in docs/serve.md")

    # Zero-silent-drops contract: the reader's terminal phases must be
    # a subset of the tracer's (brownout is fleet-scoped, rid -1).
    tr_src = (REPO / "horovod_tpu" / "serve" / "tracing.py").read_text()
    rd_src = (REPO / "tools" / "analyze_serve.py").read_text()
    tm = re.search(r"^TRACE_TERMINAL_PHASES = \(([^)]*)\)", tr_src,
                   re.M | re.S)
    rm = re.search(r"^TERMINAL_PHASES = \(([^)]*)\)", rd_src,
                   re.M | re.S)
    if not tm or not rm:
        missing.append("overload: terminal-phase tuple missing from "
                       "serve/tracing.py or tools/analyze_serve.py")
    else:
        writer = set(re.findall(r'"(\w+)"', tm.group(1)))
        reader = set(re.findall(r'"(\w+)"', rm.group(1)))
        if not reader <= writer:
            missing.append("overload: analyze_serve.py TERMINAL_PHASES "
                           "drifted from tracing.py "
                           "TRACE_TERMINAL_PHASES")

    # Evidence surfaces: chaos family, banked storm, bench arm,
    # brownout runbook.
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()
    if '"overload"' not in soak_src:
        missing.append("overload: chaos_soak.py lacks the overload "
                       "family")
    if not (REPO / "results" / "fleetsim"
            / "overload_storm.json").exists():
        missing.append("overload: results/fleetsim/overload_storm.json "
                       "not banked")
    bench_src = (REPO / "bench.py").read_text()
    if '"overload"' not in bench_src:
        missing.append("overload: bench.py lacks the overload serve "
                       "arm")
    ts_text = (REPO / "docs" / "troubleshooting.md").read_text() \
        if (REPO / "docs" / "troubleshooting.md").exists() else ""
    if "brownout" not in ts_text:
        missing.append("overload: docs/troubleshooting.md lacks the "
                       "stuck-in-brownout runbook")


def check_zero_surface(missing: list) -> None:
    """The ZeRO-2/3 subsystem (docs/zero.md): every knob, metric, API
    name, bench/chaos/test surface named by ISSUE 12 must exist in the
    source AND be documented — an undocumented sharding stage is an
    unusable one. Parsed textually (runs without jax installed)."""
    doc = REPO / "docs" / "zero.md"
    if not doc.exists():
        missing.append("path: docs/zero.md")
        return
    text = doc.read_text()
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    metrics_text = (REPO / "docs" / "metrics.md").read_text() \
        if (REPO / "docs" / "metrics.md").exists() else ""
    optim_src = (REPO / "horovod_tpu" / "optim.py").read_text()
    ckpt_src = (REPO / "horovod_tpu" / "checkpoint.py").read_text()
    integ_src = (REPO / "horovod_tpu" / "common"
                 / "integrity.py").read_text()
    cfg_src = (REPO / "horovod_tpu" / "common" / "config.py").read_text()
    tune_src = (REPO / "horovod_tpu" / "common"
                / "autotune.py").read_text()
    bench_src = (REPO / "bench.py").read_text()
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()

    # API names: defined -> documented in docs/zero.md AND docs/api.md.
    api = {
        "ZeroOptimizer": optim_src, "shard_params": optim_src,
        "gather_params": optim_src, "gather_state": optim_src,
        "reshard_state": optim_src, "zero_stage": optim_src,
        "save_sharded": ckpt_src, "restore_sharded": ckpt_src,
        "sharded_fingerprint": integ_src,
    }
    for name, src in api.items():
        if f"def {name}" not in src and f"class {name}" not in src \
                and f"{name}:" not in src and f"{name}=" not in src:
            missing.append(f"zero api {name}: not found in source")
            continue
        for where, t in (("docs/zero.md", text),
                         ("docs/api.md", api_text)):
            if name not in t:
                missing.append(f"zero api {name}: undocumented in "
                               f"{where}")

    # Metrics: the two ISSUE-named series must be registered and
    # documented in both docs.
    for metric in ("hvd_tpu_zero_gather_bytes_total",
                   "hvd_tpu_zero_param_bytes_resident"):
        if metric not in optim_src:
            missing.append(f"zero metric {metric}: not registered in "
                           "optim.py")
        for where, t in (("docs/zero.md", text),
                         ("docs/metrics.md", metrics_text)):
            if metric not in t:
                missing.append(f"zero metric {metric}: undocumented "
                               f"in {where}")

    # Knobs: config + bench + autotune widening.
    if 'zero_stage' not in cfg_src or '"ZERO_STAGE"' not in cfg_src:
        missing.append("zero: config.py lacks the zero_stage knob")
    if "HVD_TPU_ZERO_STAGE" not in text:
        missing.append("zero knob HVD_TPU_ZERO_STAGE: undocumented in "
                       "docs/zero.md")
    if '"--zero-stage"' not in bench_src:
        missing.append("zero: bench.py lacks the --zero-stage flag")
    elif "--zero-stage" not in text:
        missing.append("zero bench flag --zero-stage: undocumented in "
                       "docs/zero.md")
    if '"memory"' not in bench_src:
        missing.append("zero: bench.py records lack the memory block")
    elif "memory" not in text:
        missing.append("zero: the BENCH memory block is undocumented "
                       "in docs/zero.md")
    if "shard_candidates" not in tune_src:
        missing.append("zero: autotune.py shard axis not widened to "
                       "stages (shard_candidates)")
    elif "shard_candidates" not in text:
        missing.append("zero: shard_candidates undocumented in "
                       "docs/zero.md")

    # Chaos + test surfaces.
    if "run_zero_soak" not in soak_src or '"zero"' not in soak_src:
        missing.append("zero: chaos_soak.py lacks the zero family")
    elif "--family zero" not in text:
        missing.append("zero: chaos family undocumented in "
                       "docs/zero.md")
    if not (REPO / "tests" / "test_zero.py").exists():
        missing.append("zero: tests/test_zero.py missing")


def check_pipeline_surface(missing: list) -> None:
    """The hybrid 3D-parallelism subsystem (docs/pipeline.md): every
    knob (HVD_TPU_PARALLEL / HVD_TPU_PP_* / HVD_TPU_TP), metric, API
    name, bench/chaos/autotune surface named by ISSUE 13 must exist in
    the source AND be documented. Parsed textually (runs without
    jax installed)."""
    doc = REPO / "docs" / "pipeline.md"
    if not doc.exists():
        missing.append("path: docs/pipeline.md")
        return
    text = doc.read_text()
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    metrics_text = (REPO / "docs" / "metrics.md").read_text() \
        if (REPO / "docs" / "metrics.md").exists() else ""
    spec_src = (REPO / "horovod_tpu" / "parallel" / "spec.py").read_text()
    pipe_src = (REPO / "horovod_tpu" / "parallel"
                / "pipeline.py").read_text()
    tp_src = (REPO / "horovod_tpu" / "parallel"
              / "tensor_parallel.py").read_text()
    gpt_src = (REPO / "horovod_tpu" / "models" / "gpt.py").read_text()
    optim_src = (REPO / "horovod_tpu" / "optim.py").read_text()
    coll_src = (REPO / "horovod_tpu" / "ops" / "collectives.py").read_text()
    cfg_src = (REPO / "horovod_tpu" / "common" / "config.py").read_text()
    tune_src = (REPO / "horovod_tpu" / "common"
                / "autotune.py").read_text()
    bench_src = (REPO / "bench.py").read_text()
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()

    # API names: defined -> documented in docs/pipeline.md AND api.md.
    api = {
        "ParallelSpec": spec_src, "grad_route": spec_src,
        "parallel_spec": (REPO / "horovod_tpu"
                          / "__init__.py").read_text(),
        "parallel_mesh": (REPO / "horovod_tpu"
                          / "__init__.py").read_text(),
        "pipeline_accumulate_gradients": pipe_src,
        "pipeline_apply": pipe_src,
        "pipeline_train_step_1f1b": pipe_src,
        "select_last_stage": pipe_src,
        "wired_ppermute": coll_src,
        "tp_mlp": tp_src, "column_parallel": tp_src,
        "row_parallel": tp_src, "shard_heads": tp_src,
        "shard_head_rows": tp_src, "combine_slice_grads": tp_src,
        "stack_stage_params": gpt_src, "pipeline_fns": gpt_src,
    }
    for name, src in api.items():
        if f"def {name}" not in src and f"class {name}" not in src:
            missing.append(f"pipeline api {name}: not found in source")
            continue
        for where, t in (("docs/pipeline.md", text),
                         ("docs/api.md", api_text)):
            if name not in t:
                missing.append(f"pipeline api {name}: undocumented in "
                               f"{where}")

    # The optimizer surfaces must take the spec.
    if "parallel=None" not in optim_src:
        missing.append("pipeline: optim.py optimizer surfaces lack "
                       "parallel=")
    elif "parallel=" not in text:
        missing.append("pipeline: the optimizer parallel= knob is "
                       "undocumented in docs/pipeline.md")

    # Metrics: the activation byte counter + the autotune gauge.
    for metric, src, srcname in (
            ("hvd_tpu_pipeline_activation_bytes_total", pipe_src,
             "parallel/pipeline.py"),
            ("hvd_tpu_autotune_pp_wire_index", tune_src,
             "common/autotune.py")):
        if metric not in src:
            missing.append(f"pipeline metric {metric}: not registered "
                           f"in {srcname}")
        for where, t in (("docs/pipeline.md", text),
                         ("docs/metrics.md", metrics_text)):
            if metric not in t:
                missing.append(f"pipeline metric {metric}: "
                               f"undocumented in {where}")

    # Knobs: config fields + env names documented.
    for field, env in (("parallel", '"PARALLEL"'),
                       ("pp_wire", '"PP_WIRE"'),
                       ("pp_stages", '"PP_STAGES"'),
                       ("tp", '"TP"')):
        if f"{field}:" not in cfg_src or env not in cfg_src:
            missing.append(f"pipeline: config.py lacks the {field} "
                           "knob")
    for knob in ("HVD_TPU_PARALLEL", "HVD_TPU_PP_WIRE",
                 "HVD_TPU_PP_STAGES", "HVD_TPU_TP"):
        if knob not in text:
            missing.append(f"pipeline knob {knob}: undocumented in "
                           "docs/pipeline.md")

    # Autotune axis.
    if "pp_wire_candidates" not in tune_src:
        missing.append("pipeline: autotune.py lacks the pp_wire axis")
    elif "pp_wire_candidates" not in text:
        missing.append("pipeline: pp_wire_candidates undocumented in "
                       "docs/pipeline.md")

    # Bench arms + chaos family.
    for flag in ('"--pipeline-stages"', '"--tp"', '"--pp-wire"'):
        if flag not in bench_src:
            missing.append(f"pipeline: bench.py lacks the {flag} flag")
        elif flag.strip('"') not in text:
            missing.append(f"pipeline bench flag {flag.strip(chr(34))}:"
                           " undocumented in docs/pipeline.md")
    if "run_pipeline_soak" not in soak_src \
            or '"pipeline"' not in soak_src:
        missing.append("pipeline: chaos_soak.py lacks the pipeline "
                       "family")
    elif "--family pipeline" not in text:
        missing.append("pipeline: chaos family undocumented in "
                       "docs/pipeline.md")
    if not (REPO / "tests" / "test_pipeline.py").exists():
        missing.append("pipeline: tests/test_pipeline.py missing")


def check_seq_surface(missing: list) -> None:
    """The sequence-parallelism subsystem (ISSUE 18,
    docs/sequence.md): the sp role, the ring/Ulysses exchange API, the
    wire knobs (``HVD_TPU_SEQ_*``), the K/V byte counter + autotune
    gauge, and the bench/test surfaces must exist in the source
    AND be documented. Parsed textually (runs without jax installed)."""
    doc = REPO / "docs" / "sequence.md"
    if not doc.exists():
        missing.append("path: docs/sequence.md")
        return
    text = doc.read_text()
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    metrics_text = (REPO / "docs" / "metrics.md").read_text() \
        if (REPO / "docs" / "metrics.md").exists() else ""
    spec_src = (REPO / "horovod_tpu" / "parallel" / "spec.py").read_text()
    ring_src = (REPO / "horovod_tpu" / "parallel"
                / "ring_attention.py").read_text()
    uly_src = (REPO / "horovod_tpu" / "parallel" / "ulysses.py").read_text()
    gpt_src = (REPO / "horovod_tpu" / "models" / "gpt.py").read_text()
    coll_src = (REPO / "horovod_tpu" / "ops" / "collectives.py").read_text()
    cfg_src = (REPO / "horovod_tpu" / "common" / "config.py").read_text()
    tune_src = (REPO / "horovod_tpu" / "common" / "autotune.py").read_text()
    mesh_src = (REPO / "horovod_tpu" / "parallel" / "mesh.py").read_text()
    respec_src = (REPO / "horovod_tpu" / "parallel"
                  / "respec.py").read_text()
    bench_src = (REPO / "bench.py").read_text()
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()

    # API names: defined -> documented in docs/sequence.md AND api.md.
    api = {
        "striped_attention": ring_src, "striped_attend_fn": ring_src,
        "stripe_layout": ring_src, "striped_positions": ring_src,
        "resolve_seq_wire": ring_src,
        "ulysses_attention": uly_src, "ulysses_attend_fn": uly_src,
        "activation_bytes": gpt_src,
        "count_seq_kv_bytes": coll_src,
    }
    for name, src in api.items():
        if f"def {name}" not in src and f"class {name}" not in src:
            missing.append(f"seq api {name}: not found in source")
            continue
        for where, t in (("docs/sequence.md", text),
                         ("docs/api.md", api_text)):
            if name not in t:
                missing.append(f"seq api {name}: undocumented in "
                               f"{where}")

    # The sp role: spec property, mesh placement, fold_sp rung.
    if "def sp_axis" not in spec_src or '"sp"' not in spec_src:
        missing.append("seq: parallel/spec.py lacks the sp role")
    if '"sp"' not in mesh_src:
        missing.append("seq: parallel/mesh.py AXIS_ORDER lacks sp")
    if "fold_sp" not in respec_src:
        missing.append("seq: parallel/respec.py lacks the fold_sp rung")
    elif "fold_sp" not in text:
        missing.append("seq: fold_sp undocumented in docs/sequence.md")

    # Metrics: the K/V byte counter + the autotune gauge.
    for metric, src, srcname in (
            ("hvd_tpu_seq_kv_bytes_total", coll_src,
             "ops/collectives.py"),
            ("hvd_tpu_autotune_seq_wire_index", tune_src,
             "common/autotune.py")):
        if metric not in src:
            missing.append(f"seq metric {metric}: not registered "
                           f"in {srcname}")
        for where, t in (("docs/sequence.md", text),
                         ("docs/metrics.md", metrics_text)):
            if metric not in t:
                missing.append(f"seq metric {metric}: undocumented "
                               f"in {where}")

    # Knobs: config fields + env names documented.
    for field, env in (("seq_wire", '"SEQ_WIRE"'),
                       ("seq_parallel", '"SEQ_PARALLEL"'),
                       ("seq_impl", '"SEQ_IMPL"')):
        if f"{field}:" not in cfg_src or env not in cfg_src:
            missing.append(f"seq: config.py lacks the {field} knob")
    for knob in ("HVD_TPU_SEQ_WIRE", "HVD_TPU_SEQ_PARALLEL",
                 "HVD_TPU_SEQ_IMPL"):
        if knob not in text:
            missing.append(f"seq knob {knob}: undocumented in "
                           "docs/sequence.md")

    # Autotune axis.
    if "seq_wire_candidates" not in tune_src:
        missing.append("seq: autotune.py lacks the seq_wire axis")
    elif "seq_wire_candidates" not in text:
        missing.append("seq: seq_wire_candidates undocumented in "
                       "docs/sequence.md")

    # Bench arms + the sp'd chaos world.
    for flag in ('"--seq-parallel"', '"--seq-impl"', '"--seq-wire"',
                 '"--seq-len"'):
        if flag not in bench_src:
            missing.append(f"seq: bench.py lacks the {flag} flag")
        elif flag.strip('"') not in text:
            missing.append(f"seq bench flag {flag.strip(chr(34))}: "
                           "undocumented in docs/sequence.md")
    if "sp=2" not in soak_src:
        missing.append("seq: chaos_soak.py hybrid world lacks the sp "
                       "dimension")
    if not (REPO / "tests" / "test_seq_parallel.py").exists():
        missing.append("seq: tests/test_seq_parallel.py missing")


def check_hybrid_elastic_surface(missing: list) -> None:
    """The elastic-hybrid-parallelism surface (ISSUE 14,
    docs/elastic.md "hybrid worlds"): the respec solver's knobs
    (``HVD_TPU_RESPEC_*``), the reshape metric, the role labels on pod
    metrics + the replica-stalled gauge, the policy's ``min_np``
    field, the solver API names, and the hybrid chaos family must all
    exist in source AND be documented. Parsed textually (runs without
    jax installed)."""
    elastic_doc = REPO / "docs" / "elastic.md"
    if not elastic_doc.exists():
        missing.append("path: docs/elastic.md")
        return
    text = elastic_doc.read_text()
    auto_text = (REPO / "docs" / "autoscale.md").read_text() \
        if (REPO / "docs" / "autoscale.md").exists() else ""
    pod_text = (REPO / "docs" / "podmon.md").read_text() \
        if (REPO / "docs" / "podmon.md").exists() else ""
    pipe_text = (REPO / "docs" / "pipeline.md").read_text() \
        if (REPO / "docs" / "pipeline.md").exists() else ""
    metrics_text = (REPO / "docs" / "metrics.md").read_text() \
        if (REPO / "docs" / "metrics.md").exists() else ""
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    respec_src = (REPO / "horovod_tpu" / "parallel"
                  / "respec.py").read_text()
    spec_src = (REPO / "horovod_tpu" / "parallel" / "spec.py").read_text()
    auto_src = (REPO / "horovod_tpu" / "common"
                / "autoscale.py").read_text()
    pod_src = (REPO / "horovod_tpu" / "common" / "podmon.py").read_text()
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()

    if '"hybrid worlds"' not in text and "## Hybrid worlds" not in text:
        missing.append('hybrid: docs/elastic.md lacks the '
                       '"Hybrid worlds" section')

    # Knobs: every HVD_TPU_RESPEC_* literal the solver consults, plus
    # the enable switch, documented in docs/elastic.md.
    knobs = set(re.findall(r'"(HVD_TPU_RESPEC[A-Z0-9_]*)"', respec_src))
    if len(knobs) < 3:
        missing.append("hybrid: expected >= 3 HVD_TPU_RESPEC* knobs in "
                       "parallel/respec.py")
    for k in sorted(knobs):
        if k not in text:
            missing.append(f"hybrid knob {k}: undocumented in "
                           "docs/elastic.md")

    # Metrics: the reshape counter + the replica-stalled gauge.
    if "hvd_tpu_respec_total" not in respec_src:
        missing.append("hybrid: parallel/respec.py does not register "
                       "hvd_tpu_respec_total")
    for metric, wheres in (
            ("hvd_tpu_respec_total",
             (("docs/elastic.md", text), ("docs/metrics.md",
                                          metrics_text))),
            ("hvd_tpu_pod_replica_stalled",
             (("docs/podmon.md", pod_text), ("docs/metrics.md",
                                             metrics_text)))):
        for where, t in wheres:
            if metric not in t:
                missing.append(f"hybrid metric {metric}: undocumented "
                               f"in {where}")
    if "hvd_tpu_pod_replica_stalled" not in pod_src:
        missing.append("hybrid: common/podmon.py does not serve "
                       "hvd_tpu_pod_replica_stalled")

    # The solver ladder's rung names are the decision-log reasons —
    # the preference table in docs/elastic.md must name each.
    for rung in ("shed_dp", "fold_pp", "drop_tp", "dp_only"):
        if f'"{rung}"' not in respec_src and f"'{rung}'" not in respec_src:
            missing.append(f"hybrid: respec rung {rung} not in "
                           "parallel/respec.py")
        elif rung not in text:
            missing.append(f"hybrid rung {rung}: missing from the "
                           "docs/elastic.md preference table")

    # API names, defined and documented.
    api = {"solve_respec": respec_src, "RespecDecision": respec_src,
           "min_world": respec_src, "plan_respec": auto_src,
           "role_label": spec_src, "replica_of": spec_src,
           "replica_ranks": spec_src, "spec_from_env": spec_src}
    for name, src in api.items():
        if f"def {name}" not in src and f"class {name}" not in src:
            missing.append(f"hybrid api {name}: not found in source")
        elif name not in text and name not in api_text:
            missing.append(f"hybrid api {name}: undocumented in "
                           "docs/elastic.md or docs/api.md")

    # The policy floor + role labels.
    if "min_np: int" not in auto_src:
        missing.append("hybrid: AutoscalePolicy lacks the min_np field")
    elif "`min_np`" not in auto_text:
        missing.append("hybrid: min_np missing from the "
                       "docs/autoscale.md schema table")
    if "resolve_min_np" not in auto_src:
        missing.append("hybrid: AutoscalePolicy lacks resolve_min_np")
    for where, t in (("docs/autoscale.md", auto_text),
                     ("docs/podmon.md", pod_text)):
        if "role" not in t or "dp" not in t:
            missing.append(f"hybrid: role labels undocumented in {where}")
    # The respec action in the decision table.
    if '"respec"' not in auto_src:
        missing.append("hybrid: autoscale.py lacks the respec action")
    elif "respec" not in auto_text:
        missing.append("hybrid: the respec decision is undocumented in "
                       "docs/autoscale.md")

    # Composition rows: pipeline + autoscale docs must cross-reference
    # the elastic journey.
    for where, t in (("docs/pipeline.md", pipe_text),
                     ("docs/autoscale.md", auto_text)):
        if "elastic.md" not in t:
            missing.append(f"hybrid: {where} lacks the elastic "
                           "composition row")

    # The chaos family + its tier-1 smoke.
    if "run_hybrid_soak" not in soak_src or '"hybrid"' not in soak_src:
        missing.append("hybrid: chaos_soak.py lacks the hybrid family")
    elif "--family hybrid" not in text:
        missing.append("hybrid: the chaos family is undocumented in "
                       "docs/elastic.md")
    if not (REPO / "tests" / "test_respec.py").exists():
        missing.append("hybrid: tests/test_respec.py missing")


def check_lint_surface(missing: list) -> None:
    """The static-analysis surface (ISSUE 15, docs/lint.md): every
    hvdlint rule id documented with its historical anchor, every
    fixture pair present, the runtime-knob registry cross-referenced
    against docs, and the lockdep watchdog knob + API documented.
    Parsed textually (runs without jax installed)."""
    lint_doc = REPO / "docs" / "lint.md"
    if not lint_doc.exists():
        missing.append("path: docs/lint.md")
        return
    text = lint_doc.read_text()
    api_text = (REPO / "docs" / "api.md").read_text() \
        if (REPO / "docs" / "api.md").exists() else ""
    readme_text = (REPO / "README.md").read_text() \
        if (REPO / "README.md").exists() else ""

    # Rule ids: collected from the checker sources' `rule = "..."`
    # class attributes; each must have its docs/lint.md row.
    checker_dir = REPO / "tools" / "hvdlint" / "checkers"
    if not checker_dir.is_dir():
        missing.append("path: tools/hvdlint/checkers/")
        return
    rules = set()
    for path in checker_dir.glob("*.py"):
        rules |= set(re.findall(r'^    rule = "([a-z0-9\-]+)"',
                                path.read_text(), re.M))
    if len(rules) < 8:
        missing.append(f"lint: expected >= 8 checker rules, found "
                       f"{len(rules)}")
    for rule in sorted(rules | {"bare-suppression"}):
        if f"`{rule}`" not in text:
            missing.append(f"lint rule {rule}: undocumented in "
                           "docs/lint.md")

    # Fixture pairs: every checker ships one violating + one clean
    # fixture (knob-doc uses mini-trees).
    fixtures = REPO / "tools" / "hvdlint" / "fixtures"
    for stem in ("env_knob", "explicit_only", "ste_vjp",
                 "trace_purity", "signal_safety", "error_stamp",
                 "metric_name", "lock_order"):
        for kind in ("bad", "clean"):
            if not (fixtures / f"{stem}_{kind}.py").exists():
                missing.append(f"lint fixture: {stem}_{kind}.py")
    for tree in ("knob_doc_bad", "knob_doc_clean"):
        if not (fixtures / tree / "horovod_tpu" / "common"
                / "config.py").exists():
            missing.append(f"lint fixture tree: {tree}")

    # Runtime knob registry: every RUNTIME_KNOBS name documented
    # somewhere under docs/ (the same contract the knob-doc rule
    # enforces — drift between the two audits is itself a finding).
    cfg_src = (REPO / "horovod_tpu" / "common" / "config.py").read_text()
    m = re.search(r"RUNTIME_KNOBS = \{(.*?)\n\}", cfg_src, re.S)
    if m is None:
        missing.append("lint: config.RUNTIME_KNOBS table not found")
        knob_names = []
    else:
        knob_names = re.findall(r'^    "([A-Z0-9_]+)":', m.group(1),
                                re.M)
        if len(knob_names) < 30:
            missing.append("lint: RUNTIME_KNOBS suspiciously small "
                           f"({len(knob_names)} entries)")
    docs_blob = "\n".join(p.read_text()
                          for p in (REPO / "docs").glob("*.md")) \
        + readme_text
    for k in knob_names:
        if f"HVD_TPU_{k}" not in docs_blob:
            missing.append(f"lint knob HVD_TPU_{k}: undocumented "
                           "under docs/")

    # The lockdep watchdog: knob + API + the module itself.
    if not (REPO / "horovod_tpu" / "common" / "lockdep.py").exists():
        missing.append("path: horovod_tpu/common/lockdep.py")
    for needle, where, blob in (
            ("HVD_TPU_LOCKDEP", "docs/lint.md", text),
            ("lockdep.cycles()", "docs/lint.md", text),
            ("hvdlint", "docs/api.md", api_text),
            ("hvdlint", "README.md", readme_text),
            ("docs/lint.md", "docs/parity.md",
             DOC.read_text() if DOC.exists() else "")):
        if needle not in blob:
            missing.append(f"lint: {needle!r} missing from {where}")

    # The tier-1 gate exists and runs the clean-tree command.
    test_file = REPO / "tests" / "test_hvdlint.py"
    if not test_file.exists():
        missing.append("path: tests/test_hvdlint.py")
    elif "tools/" not in test_file.read_text():
        missing.append("lint: tests/test_hvdlint.py does not lint the "
                       "full tree")


def check_fleetsim_surface(missing: list) -> None:
    """The fleet digital twin (ISSUE 17, docs/fleetsim.md): every
    FleetScenario schema field and event kind in the doc's tables,
    every builtin scenario documented AND banked in results/fleetsim/,
    every CLI flag documented, the HVD_TPU_FLEETSIM_* knobs
    cross-referenced, the sweep evidence behind the tuned
    straggler_ratio default on disk, and chaos_soak actually riding
    the sim core. Parsed textually (runs without jax installed)."""
    doc = REPO / "docs" / "fleetsim.md"
    if not doc.exists():
        missing.append("path: docs/fleetsim.md")
        return
    text = doc.read_text()
    sim_path = REPO / "horovod_tpu" / "common" / "fleetsim.py"
    cli_path = REPO / "tools" / "fleetsim.py"
    for p in (sim_path, cli_path):
        if not p.exists():
            missing.append(f"path: {p.relative_to(REPO)}")
            return
    sim_src = sim_path.read_text()
    cli_src = cli_path.read_text()

    # Scenario schema: every FleetScenario field has its backquoted
    # row in the docs table (same contract as the SLOPolicy audit).
    m = re.search(r"class FleetScenario:.*?\n    @classmethod",
                  sim_src, re.S)
    if m is None:
        missing.append("fleetsim: FleetScenario dataclass not found")
        return
    fields = re.findall(r"^    (\w+): (?:str|bool|int|float|List|Dict)",
                        m.group(0), re.M)
    if len(fields) < 15:
        missing.append(f"fleetsim: only {len(fields)} FleetScenario "
                       "fields parsed")
    for f in fields:
        if f"`{f}`" not in text:
            missing.append(f"fleetsim field {f}: missing from the "
                           "docs/fleetsim.md schema table")

    # Event kinds + builtin scenarios: documented and (for scenarios)
    # banked as regression baselines.
    kinds = re.findall(r'EVENT_KINDS = \(([^)]*)\)', sim_src)
    for kind in re.findall(r'"([a-z_]+)"', kinds[0] if kinds else ""):
        if f"`{kind}`" not in text:
            missing.append(f"fleetsim event kind {kind}: undocumented")
    lib = sim_src[sim_src.find("def builtin_scenarios"):]
    scenarios = re.findall(r'name="([a-z0-9_]+)"', lib)
    if len(scenarios) < 5:
        missing.append(f"fleetsim: only {len(scenarios)} builtin "
                       "scenarios found (expected >= 5)")
    for s in scenarios:
        if f"`{s}`" not in text:
            missing.append(f"fleetsim scenario {s}: undocumented in "
                           "docs/fleetsim.md")
        if not (REPO / "results" / "fleetsim" / f"{s}.json").exists():
            missing.append(f"fleetsim scenario {s}: no banked baseline "
                           "in results/fleetsim/")

    # CLI flags: every add_argument("--flag") documented.
    for flag in re.findall(r'add_argument\("(--[a-z-]+)"', cli_src):
        if flag not in text:
            missing.append(f"fleetsim CLI flag {flag}: undocumented")

    # Knobs: the registry's FLEETSIM_* entries spelled in the doc.
    cfg_src = (REPO / "horovod_tpu" / "common" / "config.py").read_text()
    for k in re.findall(r'^    "(FLEETSIM_[A-Z0-9_]+)":', cfg_src, re.M):
        if f"HVD_TPU_{k}" not in text:
            missing.append(f"fleetsim knob HVD_TPU_{k}: undocumented "
                           "in docs/fleetsim.md")

    # The tuned-default evidence chain: sweep baseline on disk, cited
    # by both the policy source and docs/autoscale.md.
    sweep = REPO / "results" / "fleetsim" / "sweep_straggler_ratio.json"
    if not sweep.exists():
        missing.append("fleetsim: results/fleetsim/"
                       "sweep_straggler_ratio.json evidence missing")
    auto_doc = (REPO / "docs" / "autoscale.md").read_text() \
        if (REPO / "docs" / "autoscale.md").exists() else ""
    for where, blob in (("docs/autoscale.md", auto_doc),
                        ("common/autoscale.py",
                         (REPO / "horovod_tpu" / "common"
                          / "autoscale.py").read_text())):
        if "sweep_straggler_ratio" not in blob:
            missing.append(f"fleetsim: {where} does not cite the "
                           "straggler_ratio sweep evidence")

    # The chaos families ride the sim core; the twin is discoverable
    # from the front doors.
    soak_src = (REPO / "tools" / "chaos_soak.py").read_text()
    if "fleetsim" not in soak_src:
        missing.append("fleetsim: tools/chaos_soak.py does not use the "
                       "sim core")
    for where, path in (("docs/api.md", REPO / "docs" / "api.md"),
                        ("README.md", REPO / "README.md"),
                        ("docs/serve.md", REPO / "docs" / "serve.md")):
        if "fleetsim" not in (path.read_text() if path.exists() else ""):
            missing.append(f"fleetsim: no cross-link in {where}")
    if not (REPO / "tests" / "test_fleetsim.py").exists():
        missing.append("path: tests/test_fleetsim.py")


def main() -> int:
    missing = []
    docs = documents()
    for doc in docs:
        missing += dangling_references(doc)

    check_compression_surface(missing)
    check_metrics_surface(missing)
    check_integrity_surface(missing)
    check_topology_surface(missing)
    check_autoscale_surface(missing)
    check_mfu_surface(missing)
    check_podmon_surface(missing)
    check_moe_surface(missing)
    check_serve_surface(missing)
    check_serve_trace_surface(missing)
    check_overload_surface(missing)
    check_zero_surface(missing)
    check_pipeline_surface(missing)
    check_seq_surface(missing)
    check_hybrid_elastic_surface(missing)
    check_lint_surface(missing)
    check_fleetsim_surface(missing)

    if missing:
        print("the documents have dangling references:")
        for m in sorted(missing):
            print(f"  - {m}")
        return 1
    print(f"{len(docs)} documents (README.md, docs/*.md, the verify "
          "skill): all file/test/module references resolve")
    return 0


if __name__ == "__main__":
    sys.exit(main())
