"""Unified telemetry (docs/metrics.md): registry semantics, the three
export surfaces (snapshot / JSON-lines dump / Prometheus endpoint),
zero-cost disable, the profiler bridge, and the cross-layer
instrumentation (eager engine, fusion, stall, recovery, autotune,
optimizer)."""

import json
import re
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from horovod_tpu.common import metrics as metrics_lib
from horovod_tpu.common.metrics import (MetricsDumper, MetricsRegistry,
                                        MetricsServer, NOOP)

sys.path.insert(0, __file__.rsplit("/", 2)[0])


# -- registry core ----------------------------------------------------------

def test_counter_gauge_histogram_basic():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("hvd_tpu_t_events_total", "events", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc(5)
    g = reg.gauge("hvd_tpu_t_depth", "depth")
    g.set(3)
    g.inc()
    g.dec(2)
    h = reg.histogram("hvd_tpu_t_seconds", "latency",
                      buckets=(0.01, 0.1, 1.0))
    h.observe(0.005)
    h.observe(0.5)
    h.observe(99.0)
    snap = reg.snapshot()
    events = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["hvd_tpu_t_events_total"]["samples"]}
    assert events[(("kind", "a"),)] == 3
    assert events[(("kind", "b"),)] == 5
    assert snap["hvd_tpu_t_depth"]["samples"][0]["value"] == 2
    hval = snap["hvd_tpu_t_seconds"]["samples"][0]["value"]
    assert hval["count"] == 3
    assert hval["buckets"]["0.01"] == 1
    assert hval["buckets"]["1"] == 2
    assert hval["buckets"]["+Inf"] == 3
    assert abs(hval["sum"] - 99.505) < 1e-9
    # The whole snapshot is JSON-able (the dump surface depends on it).
    json.dumps(snap)


def test_counter_monotonic_and_schema_conflicts():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("hvd_tpu_t_mono_total", "x")
    with pytest.raises(ValueError):
        c.inc(-1)
    # Same name, different type or label schema: loud failure.
    with pytest.raises(ValueError):
        reg.gauge("hvd_tpu_t_mono_total", "x")
    with pytest.raises(ValueError):
        reg.counter("hvd_tpu_t_mono_total", "x", labels=("k",))
    # Labeled family rejects unlabeled updates and unknown labels.
    lc = reg.counter("hvd_tpu_t_lab_total", "x", labels=("k",))
    with pytest.raises(ValueError):
        lc.inc()
    with pytest.raises(ValueError):
        lc.labels(bogus="1")


def test_thread_safety_under_contention():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("hvd_tpu_t_race_total", "x", labels=("t",))
    h = reg.histogram("hvd_tpu_t_race_seconds", "x")

    def worker(tid):
        child = c.labels(t=str(tid % 2))
        for _ in range(500):
            child.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(s["value"] for s in
                reg.snapshot()["hvd_tpu_t_race_total"]["samples"])
    assert total == 8 * 500
    assert reg.snapshot()["hvd_tpu_t_race_seconds"]["samples"][0][
        "value"]["count"] == 8 * 500


def test_disabled_registry_returns_singletons():
    """The HVD_TPU_METRICS=0 contract (acceptance criterion): every
    constructor of a disabled registry returns THE shared no-op
    singleton — instrumented hot paths hold no per-site state and
    allocate nothing."""
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("hvd_tpu_t_a_total") is NOOP
    assert reg.gauge("hvd_tpu_t_b") is NOOP
    assert reg.histogram("hvd_tpu_t_c_seconds") is NOOP
    assert reg.counter("hvd_tpu_t_other_total") is reg.counter(
        "hvd_tpu_t_a_total")
    # labels() returns the same singleton; every mutator is a no-op.
    assert NOOP.labels(kind="x") is NOOP
    NOOP.inc()
    NOOP.set(5)
    NOOP.observe(0.1)
    with NOOP.time():
        pass
    assert reg.snapshot() == {}
    assert reg.prometheus_text() == "\n"
    # Disabled registries also refuse to do bridge work.
    reg2 = MetricsRegistry(enabled=False, trace_bridge=True)
    assert reg2.trace_bridge is False


def test_global_labels_stamped_on_every_sample():
    reg = MetricsRegistry(enabled=True)
    reg.set_global_labels(rank="3", size="8")
    reg.counter("hvd_tpu_t_gl_total", "x").inc()
    reg.histogram("hvd_tpu_t_gl_seconds", "x").observe(0.1)
    snap = reg.snapshot()
    for fam in snap.values():
        for s in fam["samples"]:
            assert s["labels"]["rank"] == "3"
            assert s["labels"]["size"] == "8"
    assert 'rank="3"' in reg.prometheus_text()


# -- Prometheus text format -------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? '
    r'(-?[0-9.eE+\-]+|NaN|[+-]Inf)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prometheus(body):
    """Minimal exposition-format parser: asserts every line is either a
    well-formed comment or a sample; returns [(name, labels, value)]."""
    samples = []
    for line in body.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ",
                            line), f"malformed comment: {line!r}"
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        labels = dict(_LABEL_RE.findall(m.group(2) or ""))
        samples.append((m.group(1), labels, float(m.group(3))))
    return samples


def test_prometheus_text_format():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("hvd_tpu_t_fmt_total", 'with "quotes"\nand lines',
                    labels=("wire",))
    c.labels(wire='va"l\\ue').inc(3)
    h = reg.histogram("hvd_tpu_t_fmt_seconds", "lat",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    body = reg.prometheus_text()
    samples = _parse_prometheus(body)
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["hvd_tpu_t_fmt_total"][0][0]["wire"] == 'va\\"l\\\\ue'
    assert by_name["hvd_tpu_t_fmt_total"][0][1] == 3
    buckets = {l["le"]: v for l, v in
               by_name["hvd_tpu_t_fmt_seconds_bucket"]}
    assert buckets["0.1"] == 1 and buckets["1"] == 1
    assert buckets["+Inf"] == 2
    assert by_name["hvd_tpu_t_fmt_seconds_count"][0][1] == 2
    assert by_name["hvd_tpu_t_fmt_seconds_sum"][0][1] == \
        pytest.approx(5.05)
    assert "# TYPE hvd_tpu_t_fmt_seconds histogram" in body


def test_prometheus_text_survives_non_finite_values():
    """A diverging run can publish inf/nan (e.g. the EF residual norm);
    the scrape must keep serving — Prometheus spellings, no crash."""
    reg = MetricsRegistry(enabled=True)
    reg.gauge("hvd_tpu_t_inf", "x").set(float("inf"))
    reg.gauge("hvd_tpu_t_ninf", "x").set(float("-inf"))
    reg.gauge("hvd_tpu_t_nan", "x").set(float("nan"))
    reg.histogram("hvd_tpu_t_nf_seconds", "x",
                  buckets=(1.0,)).observe(float("nan"))
    body = reg.prometheus_text()
    assert "hvd_tpu_t_inf +Inf" in body
    assert "hvd_tpu_t_ninf -Inf" in body
    assert "hvd_tpu_t_nan NaN" in body
    _parse_prometheus(body)
    json.dumps(reg.snapshot())  # snapshot stays JSON-able too


# -- timer + profiler bridge ------------------------------------------------

def test_histogram_timer_and_trace_bridge():
    reg = MetricsRegistry(enabled=True, trace_bridge=True)
    h = reg.histogram("hvd_tpu_t_span_seconds", "span",
                      buckets=(10.0,))
    with h.time():
        time.sleep(0.01)
    v = reg.snapshot()["hvd_tpu_t_span_seconds"]["samples"][0]["value"]
    assert v["count"] == 1
    assert v["sum"] >= 0.009
    # Labeled variant with an explicit annotation name.
    hl = reg.histogram("hvd_tpu_t_span2_seconds", "span", labels=("p",))
    with hl.labels(p="grad").time(annotation="step/grad"):
        pass
    assert reg.snapshot()["hvd_tpu_t_span2_seconds"]["samples"][0][
        "value"]["count"] == 1


def test_step_annotation_contexts():
    # Bridge off: the no-op context; on: a jax StepTraceAnnotation —
    # both must nest cleanly outside any active profile session.
    with metrics_lib.step_annotation(1):
        pass
    metrics_lib.enable_trace_bridge(True)
    try:
        with metrics_lib.step_annotation(2):
            pass
    finally:
        metrics_lib.enable_trace_bridge(False)


# -- export surface 2: JSON-lines dump --------------------------------------

def test_metrics_dumper_writes_and_drains(tmp_path):
    reg = MetricsRegistry(enabled=True)
    reg.counter("hvd_tpu_t_dump_total", "x").inc(7)
    path = str(tmp_path / "metrics.jsonl")
    d = MetricsDumper(path, interval_s=0.05, reg=reg)
    d.start()
    time.sleep(0.25)
    reg.counter("hvd_tpu_t_dump_total", "x").inc(1)
    d.stop()
    d.stop()  # idempotent
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert len(lines) >= 2
    # Drain-on-stop: the FINAL line carries the last pre-stop state.
    final = lines[-1]["metrics"]["hvd_tpu_t_dump_total"]["samples"][0]
    assert final["value"] == 8
    assert all("t" in rec for rec in lines)


# -- export surface 3: /metrics endpoint ------------------------------------

def test_metrics_server_serves_text_and_json(tmp_path):
    reg = MetricsRegistry(enabled=True)
    reg.set_global_labels(rank="0")
    reg.counter("hvd_tpu_t_http_total", "x").inc(4)
    srv = MetricsServer(reg=reg, host="127.0.0.1")
    port = srv.start(0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        samples = _parse_prometheus(body)
        assert ("hvd_tpu_t_http_total", {"rank": "0"}, 4.0) in samples
        raw = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert raw["hvd_tpu_t_http_total"]["samples"][0]["value"] == 4
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        srv.stop()


# -- cross-layer instrumentation -------------------------------------------

def _sample_values(name):
    fam = metrics_lib.snapshot().get(name, {"samples": []})
    return fam["samples"]


def _value(name, **labels):
    for s in _sample_values(name):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return None


def test_stall_inspector_inflight_gauge():
    from horovod_tpu.common.stall import StallInspector

    insp = StallInspector(check_time_seconds=60.0)
    insp.record_submit("allreduce.g1")
    assert _value("hvd_tpu_stall_inflight") == 1
    insp.record_submit("allreduce.g2")
    assert _value("hvd_tpu_stall_inflight") == 2
    insp.record_complete("allreduce.g1")
    insp.record_complete("allreduce.g2")
    assert _value("hvd_tpu_stall_inflight") == 0


def test_stall_warning_counter():
    from horovod_tpu.common.stall import StallInspector

    before = _value("hvd_tpu_stall_warnings_total") or 0
    insp = StallInspector(check_time_seconds=0.01)
    insp.record_submit("allreduce.slow")
    time.sleep(0.05)
    assert insp.check() is True
    assert (_value("hvd_tpu_stall_warnings_total") or 0) == before + 1
    insp.record_complete("allreduce.slow")


def test_recovery_stats_mirrored_to_registry():
    from horovod_tpu.common import faults

    base = _value("hvd_tpu_recovery_total", counter="resets") or 0
    base_agg = _value("hvd_tpu_recovery_total", counter="retries") or 0
    faults.stats.bump("resets")
    faults.stats.bump("rendezvous_retries", 2)
    assert _value("hvd_tpu_recovery_total", counter="resets") == base + 1
    # The retry aggregate mirrors the RecoveryStats aggregation rule.
    assert _value("hvd_tpu_recovery_total",
                  counter="retries") == base_agg + 2
    faults.stats.add_downtime(0.5)
    assert (_value("hvd_tpu_recovery_downtime_seconds") or 0) > 0
    # Every known counter is pre-seeded so a scrape shows 0, not absence.
    names = {s["labels"]["counter"]
             for s in _sample_values("hvd_tpu_recovery_total")}
    from horovod_tpu.common.faults import RecoveryStats
    assert set(RecoveryStats.COUNTERS) <= names


def test_autotuner_publishes_state():
    from horovod_tpu.common.autotune import Autotuner

    tuner = Autotuner(candidates_bytes=(1024, 2048), warmup_samples=0,
                      steps_per_sample=1, tune_compression=True)
    assert _value("hvd_tpu_autotune_threshold_bytes") == tuner.current
    before = sum(s["value"] for s in
                 _sample_values("hvd_tpu_autotune_samples_total"))
    tuner.feed(1024.0, 0.01)
    after = sum(s["value"] for s in
                _sample_values("hvd_tpu_autotune_samples_total"))
    assert after == before + 1
    assert _value("hvd_tpu_autotune_threshold_bytes") == tuner.current
    # Sample labels carry the full config string (threshold |
    # hierarchical | compression | route | accum | remat | shard |
    # moe_wire | pp_wire | seq_wire — the MFU axes widened it in PR 8,
    # the MoE dispatch-wire axis in PR 10, the pipeline send wire in
    # PR 13, the sequence K/V wire in PR 18; PR 29 took the overlap
    # field out).
    labeled = [s["labels"]["config"] for s in
               _sample_values("hvd_tpu_autotune_samples_total")]
    assert any(len(cfg.split("|")) == 10 for cfg in labeled)


def test_fusion_plan_metrics():
    import jax.numpy as jnp

    from horovod_tpu.common import fusion

    before = _value("hvd_tpu_fusion_plans_total") or 0
    tree = {"a": jnp.zeros((256,), jnp.float32),
            "b": jnp.zeros((64,), jnp.float32),
            "c": jnp.zeros((8,), jnp.int32)}
    plan = fusion.plan_fusion(tree, 512)
    assert (_value("hvd_tpu_fusion_plans_total") or 0) == before + 1
    assert _value("hvd_tpu_fusion_buckets") == len(plan.buckets)
    fill = _value("hvd_tpu_fusion_fill_efficiency")
    assert 0.0 < fill <= 1.0
    wb = _value("hvd_tpu_fusion_bucket_wire_total", wire="int8")
    fusion.assign_wire_dtypes(plan, quantize_min_bytes=1024)
    # 256 fp32 elems = 1024 B -> int8; the int bucket rides none.
    assert _value("hvd_tpu_fusion_bucket_wire_total",
                  wire="int8") == (wb or 0) + 1
    assert (_value("hvd_tpu_fusion_wire_bytes_total", wire="int8")
            or 0) >= 1024


def test_grouped_allreduce_counts_plan_once(hvd):
    """The byte-accounting template plan must not double-count the
    fusion metrics: one new grouped signature = ONE counted plan (the
    traced build's); a cache-hit repeat counts none."""
    import jax

    def plans():
        return _value("hvd_tpu_fusion_plans_total") or 0

    tree = {"a": np.ones((129,), np.float32),
            "b": np.ones((33,), np.float32)}
    before = plans()
    out = hvd.grouped_allreduce(tree, name="plan_once")
    jax.block_until_ready(jax.tree.leaves(out))
    assert plans() == before + 1
    out = hvd.grouped_allreduce(tree, name="plan_once2")  # cache hit
    jax.block_until_ready(jax.tree.leaves(out))
    assert plans() == before + 1


def test_observe_ef_residual_gauge():
    import horovod_tpu as hvd
    from horovod_tpu.optim import _EFState

    state = _EFState(inner=None,
                     residual={"w": np.full((4,), 2.0, np.float32)},
                     step=np.int32(0))
    norm = hvd.observe_ef_residual(state)
    assert norm == pytest.approx(4.0)
    assert _value("hvd_tpu_ef_residual_norm") == pytest.approx(4.0)
    # A state without a residual (plain optax state) reports None.
    assert hvd.observe_ef_residual(object()) is None


def test_step_timer_phases(hvd):
    import jax.numpy as jnp

    st = hvd.StepTimer()
    before = {s["labels"].get("phase"): s["value"]["count"]
              for s in _sample_values("hvd_tpu_step_phase_seconds")}
    out = st.timed("grad", lambda: jnp.ones((8,)) * 2)
    assert float(out[0]) == 2.0
    with st.phase("apply"):
        time.sleep(0.002)
    counts = {s["labels"].get("phase"): s["value"]["count"]
              for s in _sample_values("hvd_tpu_step_phase_seconds")}
    assert counts["grad"] == before.get("grad", 0) + 1
    assert counts["apply"] == before.get("apply", 0) + 1


# -- init wiring (stall satellite + config knobs) ---------------------------

def test_init_wires_stall_inspector_from_config(hvd):
    """hvd.init() constructs the StallInspector from the HVD_TPU_STALL_*
    knobs and hands it to the eager engine + watchdog — no caller
    hand-construction needed; its view is the inflight gauge."""
    from horovod_tpu.common import basics

    ctx = basics.context()
    assert ctx.engine.stall is ctx.stall
    assert ctx.stall.check_time == ctx.config.stall_check_time_seconds
    assert ctx.stall.shutdown_time == \
        ctx.config.stall_shutdown_time_seconds
    assert ctx.stall.disabled == ctx.config.stall_check_disable
    assert ctx.stall.disabled or ctx.stall._watchdog is not None


def test_stall_and_metrics_env_knobs_resolve(monkeypatch):
    from horovod_tpu.common.config import Config

    monkeypatch.setenv("HVD_TPU_STALL_CHECK_TIME_SECONDS", "7.5")
    monkeypatch.setenv("HVD_TPU_STALL_SHUTDOWN_TIME_SECONDS", "9.5")
    monkeypatch.setenv("HVD_TPU_METRICS_PORT", "9099")
    monkeypatch.setenv("HVD_TPU_METRICS_FILE", "/tmp/m.jsonl")
    monkeypatch.setenv("HVD_TPU_METRICS_INTERVAL_S", "2.5")
    monkeypatch.setenv("HVD_TPU_METRICS_TRACE", "1")
    c = Config.from_env()
    assert c.stall_check_time_seconds == 7.5
    assert c.stall_shutdown_time_seconds == 9.5
    assert c.metrics_port == 9099
    assert c.metrics_file == "/tmp/m.jsonl"
    assert c.metrics_interval_s == 2.5
    assert c.metrics_trace_bridge is True


def _run_subprocess(script, tmp_path, **extra_env):
    import os
    import subprocess

    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               HVD_TPU_FORCE_CPU_DEVICES="2", **extra_env)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=240)


def test_init_wires_metrics_exports(tmp_path):
    """HVD_TPU_METRICS_PORT/FILE knobs: init() stamps rank labels,
    starts the endpoint + JSON-lines dump; shutdown() drains the final
    dump line and stops the server it started."""
    script = r'''
import json, os, urllib.request
import numpy as np
import jax, horovod_tpu as hvd
ctx = hvd.init()
assert ctx.metrics_port is not None and ctx.metrics_port > 0
out = hvd.allreduce(np.ones((64,), np.float32), name="w")
jax.block_until_ready(out)
body = urllib.request.urlopen(
    f"http://127.0.0.1:{ctx.metrics_port}/metrics",
    timeout=10).read().decode()
assert "hvd_tpu_allreduce_bytes_total" in body
assert 'rank="0"' in body and 'size="2"' in body
hvd.shutdown()
lines = [json.loads(l)
         for l in open(os.environ["HVD_TPU_METRICS_FILE"]) if l.strip()]
assert lines, "shutdown() must drain a final dump line"
assert "hvd_tpu_allreduce_bytes_total" in lines[-1]["metrics"]
import urllib.error
try:
    urllib.request.urlopen(
        f"http://127.0.0.1:{ctx.metrics_port}/metrics", timeout=2)
    raise SystemExit("endpoint still up after shutdown")
except (urllib.error.URLError, ConnectionError, OSError):
    pass
print("WIRED_OK")
'''
    proc = _run_subprocess(
        script, tmp_path, HVD_TPU_METRICS_PORT="0",
        HVD_TPU_METRICS_FILE=str(tmp_path / "m.jsonl"),
        HVD_TPU_METRICS_INTERVAL_S="60")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "WIRED_OK" in proc.stdout


def test_disabled_metrics_hot_path_end_to_end(tmp_path):
    """HVD_TPU_METRICS=0: collectives run unchanged, hvd.metrics() is
    empty, and the instrumented modules bound the no-op singleton."""
    script = r'''
import numpy as np
import jax, horovod_tpu as hvd
from horovod_tpu.common.metrics import NOOP
from horovod_tpu.ops import eager
from horovod_tpu import optim
from horovod_tpu.common import fusion
assert eager._M_DISPATCH is NOOP and eager._M_CACHE_HIT is NOOP
assert optim._M_STEP is NOOP and fusion._M_FILL is NOOP
assert not eager._METRICS_ON
hvd.init()
out = hvd.allreduce(np.ones((64,), np.float32), name="w")
jax.block_until_ready(out)
assert hvd.metrics() == {}
print("DISABLED_OK")
'''
    proc = _run_subprocess(script, tmp_path, HVD_TPU_METRICS="0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DISABLED_OK" in proc.stdout


# -- the tier-1 end-to-end scrape (CI satellite + acceptance criteria) ------

def test_metrics_endpoint_scrapes_eager_allreduces(hvd):
    """Start the endpoint on an ephemeral port, run 3 eager allreduces,
    scrape /metrics: the output must be Prometheus-parseable with
    nonzero hvd_tpu_allreduce_bytes_total{wire=...}, and ONE scrape must
    expose dispatch-latency histograms, raw-vs-wire byte counters, cache
    hit/miss, fusion fill efficiency, autotune state, and recovery
    counters."""
    import jax

    from horovod_tpu.common.autotune import Autotuner

    Autotuner(warmup_samples=0, steps_per_sample=1)  # autotune gauges
    port = hvd.start_metrics_server(0)
    # Idempotent: a second start returns the same bound port.
    assert hvd.start_metrics_server(0) == port
    try:
        for i in range(3):
            out = hvd.allreduce(np.ones((4096,), np.float32),
                                name=f"scrape{i}")
            jax.block_until_ready(out)
        out = hvd.grouped_allreduce(
            {"w": np.ones((512,), np.float32),
             "b": np.ones((16,), np.float32)}, name="scrapeg")
        jax.block_until_ready(jax.tree.leaves(out))
        # Completion latency is recorded by the finalizer pool — give
        # it a moment to observe buffer readiness.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            v = _value("hvd_tpu_collective_seconds", op="allreduce")
            if v and v["count"] >= 3:
                break
            time.sleep(0.05)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    finally:
        hvd.stop_metrics_server()
    samples = _parse_prometheus(body)  # asserts parseability
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))

    # Nonzero wire-byte counters with a wire label (acceptance).
    wire_bytes = [(l, v) for l, v in by_name["hvd_tpu_allreduce_bytes_total"]
                  if "wire" in l]
    assert wire_bytes and sum(v for _, v in wire_bytes) >= 3 * 4096 * 4
    # Raw vs wire per op.
    raw = [v for l, v in by_name["hvd_tpu_collective_bytes_total"]
           if l.get("op") == "allreduce" and l.get("kind") == "raw"]
    assert raw and raw[0] >= 3 * 4096 * 4
    # Dispatch + completion latency histograms, per op.
    assert any(l.get("op") == "allreduce"
               for l, v in by_name["hvd_tpu_dispatch_seconds_count"])
    assert any(l.get("op") == "allreduce" and v >= 3
               for l, v in by_name["hvd_tpu_collective_seconds_count"])
    # Cache hit/miss (3 identical allreduces = >=1 hit).
    cache = {l["result"]: v
             for l, v in by_name["hvd_tpu_eager_cache_total"]}
    assert cache["miss"] >= 1 and cache["hit"] >= 1
    # Fusion fill efficiency (the grouped allreduce planned buckets).
    assert by_name["hvd_tpu_fusion_fill_efficiency"][0][1] > 0
    # Autotune state + recovery counters on the same scrape.
    assert "hvd_tpu_autotune_threshold_bytes" in by_name
    assert {l.get("counter") for l, _ in by_name["hvd_tpu_recovery_total"]} \
        >= {"resets", "preemptions"}
    # Rank identity for pod aggregation.
    assert all(l.get("rank") == "0" for l, _ in wire_bytes)


def test_hvd_metrics_snapshot_surface(hvd):
    """hvd.metrics() exposes the same families as the endpoint."""
    snap = hvd.metrics()
    for required in ("hvd_tpu_dispatch_seconds",
                     "hvd_tpu_collective_bytes_total",
                     "hvd_tpu_allreduce_bytes_total",
                     "hvd_tpu_eager_cache_total",
                     "hvd_tpu_fusion_fill_efficiency",
                     "hvd_tpu_recovery_total",
                     "hvd_tpu_stall_inflight"):
        assert required in snap, f"missing {required}"
    json.dumps(snap)


# -- tools/analyze_metrics.py merge + graceful degrade ----------------------

def _write_metrics_jsonl(path):
    snap = {
        "hvd_tpu_step_seconds": {"type": "histogram", "help": "",
                                 "samples": [{"labels": {},
                                              "value": {"count": 10,
                                                        "sum": 0.05,
                                                        "buckets": {}}}]},
        "hvd_tpu_allreduce_bytes_total": {
            "type": "counter", "help": "",
            "samples": [{"labels": {"wire": "int8"}, "value": 12345.0}]},
    }
    with open(path, "w") as f:
        f.write("not json\n")  # malformed lines are skipped
        f.write(json.dumps({"t": 1.0, "metrics": snap}) + "\n")


def _run_analyze(*args):
    import subprocess

    tool = __file__.rsplit("/", 2)[0] + "/tools/analyze_metrics.py"
    proc = subprocess.run([sys.executable, tool, *args],
                          capture_output=True, text=True, timeout=120)
    return proc.returncode, (json.loads(proc.stdout)
                             if proc.stdout.strip() else None)


def test_analyze_metrics_merges_metrics_dump(tmp_path):
    mpath = tmp_path / "metrics.jsonl"
    _write_metrics_jsonl(mpath)
    rc, out = _run_analyze("--metrics", str(mpath))
    assert rc == 0
    assert out["metrics"]["allreduce_bytes_on_wire"]["int8"] == 12345.0
    assert out["metrics"]["step_seconds"] == {"count": 10, "mean_ms": 5.0}
    assert out["metrics"]["snapshot_unix"] == 1.0


def test_analyze_metrics_degrades_without_a_dump(tmp_path):
    """A missing dump: message + rc 0, never a crash (the satellite
    contract). Nothing to read at all is a usage error."""
    rc, out = _run_analyze("--metrics", str(tmp_path / "none.jsonl"))
    assert rc == 0
    assert "no metrics snapshot" in out["note"] and "metrics" not in out
    rc2, out2 = _run_analyze()
    assert rc2 == 2 and out2 is None


# -- bench.py integration ---------------------------------------------------

def test_bench_metrics_summary(hvd):
    """bench.py embeds the condensed snapshot (bytes on wire, cache hit
    rate, fusion fill) in its JSON record."""
    import jax

    import bench

    out = hvd.allreduce(np.ones((2048,), np.float32), name="bench_m")
    jax.block_until_ready(out)
    mx = bench._metrics_summary()
    assert mx is not None
    # mesh_planned_per_compile appears when the mesh-router tests ran
    # earlier in this process (the registry is process-wide).
    assert mx["bytes_basis"] in ("eager", "planned_per_compile",
                                 "mesh_planned_per_compile")
    assert sum(mx["bytes_on_wire"].values()) > 0
    assert "cache" in mx and 0.0 <= mx["cache"]["hit_rate"] <= 1.0
    assert "fusion_fill_efficiency" in mx
