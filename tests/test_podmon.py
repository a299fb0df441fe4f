"""Pod-scope metrics aggregation (docs/podmon.md): snapshot-derived
step time/count, the PodMonitor scrape/merge/attribution pipeline, the
/pod/metrics exposition (computed families + rank-labeled
pass-through), endpoint discovery (KV advertisement + static list),
the autoscale scrape-path bridge (the engine reaches the same decision
from a scrape as from the KV), the per-rank /debug capture endpoints,
and analyze_metrics' multi-rank metrics-dump globbing."""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu.common import metrics as metrics_lib
from horovod_tpu.common import podmon as podmon_lib
from horovod_tpu.common.autoscale import (AutoscaleEngine, AutoscalePolicy,
                                          StepReport)
from horovod_tpu.common.metrics import MetricsRegistry, MetricsServer
from horovod_tpu.common.podmon import PodMonitor


# -- snapshot helpers --------------------------------------------------------

def _snap(rank, host, step_time=None, steps=None, resyncs=0,
          comm_sum=None, total_sum=None, step_hist=None):
    """A /metrics.json-shaped snapshot for one rank."""
    labels = {"rank": str(rank), "host": host}
    snap = {}
    if step_time is not None:
        snap["hvd_tpu_autoscale_step_time_seconds"] = {
            "type": "gauge", "help": "",
            "samples": [{"labels": dict(labels), "value": step_time}]}
    if steps is not None:
        snap["hvd_tpu_autoscale_steps_total"] = {
            "type": "counter", "help": "",
            "samples": [{"labels": dict(labels), "value": steps}]}
    if step_hist is not None:
        total, count = step_hist
        snap["hvd_tpu_step_seconds"] = {
            "type": "histogram", "help": "",
            "samples": [{"labels": dict(labels),
                         "value": {"sum": total, "count": count,
                                   "buckets": {}}}]}
    snap["hvd_tpu_recovery_total"] = {
        "type": "counter", "help": "",
        "samples": [{"labels": {**labels,
                                "counter": "divergence_resyncs"},
                     "value": resyncs}]}
    if comm_sum is not None:
        snap["hvd_tpu_step_phase_seconds"] = {
            "type": "histogram", "help": "",
            "samples": [
                {"labels": {**labels, "phase": "comm"},
                 "value": {"sum": comm_sum, "count": 1, "buckets": {}}},
                {"labels": {**labels, "phase": "apply"},
                 "value": {"sum": (total_sum or comm_sum) - comm_sum,
                           "count": 1, "buckets": {}}}]}
    return snap


def _seed(monitor, rank, host, t=1.0, **kw):
    monitor._ranks[rank] = {"snapshot": _snap(rank, host, **kw),
                            "host": host, "t": t,
                            "endpoint": f"{host}:1"}


def test_step_time_prefers_publisher_gauge_over_histograms():
    s = _snap(0, "a", step_time=0.2, step_hist=(5.0, 10))
    assert podmon_lib.step_time_from_snapshot(s) == 0.2
    s = _snap(0, "a", step_hist=(5.0, 10))
    assert podmon_lib.step_time_from_snapshot(s) == pytest.approx(0.5)
    assert podmon_lib.step_time_from_snapshot(_snap(0, "a")) is None


def test_step_count_prefers_publisher_counter():
    assert podmon_lib.step_count_from_snapshot(
        _snap(0, "a", steps=42, step_hist=(1.0, 7))) == 42
    assert podmon_lib.step_count_from_snapshot(
        _snap(0, "a", step_hist=(1.0, 7))) == 7
    assert podmon_lib.step_count_from_snapshot(_snap(0, "a")) == 0


# -- merge + attribution -----------------------------------------------------

def test_merged_skew_and_slowest_rank_attribution():
    mon = PodMonitor(lambda: [], interval_s=999)
    _seed(mon, 0, "hostA", step_time=0.10)
    _seed(mon, 1, "hostB", step_time=0.35)
    _seed(mon, 2, "hostC", step_time=0.12)
    m = mon.merged()
    assert m["ranks"] == [0, 1, 2]
    assert m["step_skew_seconds"] == pytest.approx(0.25)
    assert m["slowest_rank"] == 1
    assert m["hosts"][1] == "hostB"
    stats = m["family_stats"]["hvd_tpu_autoscale_step_time_seconds"]
    assert stats["min"] == pytest.approx(0.10)
    assert stats["max"] == pytest.approx(0.35)
    assert stats["p50"] == pytest.approx(0.12)


def test_merged_single_rank_has_zero_skew():
    mon = PodMonitor(lambda: [], interval_s=999)
    _seed(mon, 0, "hostA", step_time=0.1)
    m = mon.merged()
    assert m["step_skew_seconds"] == 0.0
    assert m["slowest_rank"] == 0


def test_prometheus_text_serves_pod_families_and_passthrough():
    mon = PodMonitor(lambda: [], interval_s=999)
    _seed(mon, 0, "hostA", step_time=0.10, steps=5)
    _seed(mon, 1, "hostB", step_time=0.30, steps=5)
    text = mon.prometheus_text()
    assert 'hvd_tpu_pod_step_time_seconds{host="hostA",rank="0"}' in text
    assert "hvd_tpu_pod_step_skew_seconds 0.2" in text
    assert "hvd_tpu_pod_slowest_rank 1" in text
    assert "hvd_tpu_pod_ranks_scraped 2" in text
    # Pass-through keeps the per-rank labels; histograms stay summary.
    assert 'hvd_tpu_autoscale_steps_total{host="hostB",rank="1"} 5' \
        in text
    assert "hvd_tpu_step_phase_seconds{" not in text
    assert 'hvd_tpu_pod_stat{family="hvd_tpu_autoscale_steps_total"' \
        in text


# -- hybrid role labels + replica-stall attribution (docs/elastic.md) --------

def _hybrid_monitor():
    from horovod_tpu.parallel.spec import ParallelSpec

    spec = ParallelSpec.parse("dp=2,pp=2,tp=2")
    mon = PodMonitor(lambda: [], interval_s=999, parallel=spec)
    return spec, mon


def test_role_labels_on_per_rank_series_and_merged_view():
    spec, mon = _hybrid_monitor()
    for r in range(8):
        _seed(mon, r, f"host{r // 2}", step_time=0.1)
    m = mon.merged()
    assert m["roles"][5] == "dp1/pp0/tp1"
    assert m["role_coords"][3] == {"dp": 0, "pp": 1, "tp": 1}
    text = mon.prometheus_text()
    # dp/pp/tp labels ride every per-rank step-time sample.
    assert ('hvd_tpu_pod_step_time_seconds{dp="1",host="host2",'
            'pp="0",rank="5",tp="1"}') in text


def test_replica_stalled_gauge_from_role_grouped_skew():
    """The 1F1B signature: replica dp1's ranks are COLLECTIVELY slow.
    The role-grouped view flags the REPLICA (stalled gauge 1) while
    slowest_rank still points at the individual laggard."""
    spec, mon = _hybrid_monitor()
    for r in range(8):
        slow = spec.replica_of(r) == 1
        _seed(mon, r, f"host{r // 2}",
              step_time=(0.55 if r == 5 else 0.5) if slow else 0.1)
    m = mon.merged()
    assert m["replica_step_time_seconds"][0] == pytest.approx(0.1)
    assert m["replica_step_time_seconds"][1] == pytest.approx(0.5)
    assert m["stalled_replicas"] == [1]
    assert m["slowest_rank"] == 5
    text = mon.prometheus_text()
    assert 'hvd_tpu_pod_replica_stalled{replica="0"} 0' in text
    assert 'hvd_tpu_pod_replica_stalled{replica="1"} 1' in text


def test_replica_gauge_absent_without_a_spec():
    mon = PodMonitor(lambda: [], interval_s=999)
    _seed(mon, 0, "hostA", step_time=0.1)
    m = mon.merged()
    assert m["roles"] == {} and m["stalled_replicas"] == []
    assert "hvd_tpu_pod_replica_stalled" not in mon.prometheus_text()


def test_scrape_reports_carry_roles():
    spec, mon = _hybrid_monitor()
    _seed(mon, 5, "host2", step_time=0.2, steps=7)
    reports = mon.reports()
    assert reports[5].role == "dp1/pp0/tp1"


# -- the autoscale bridge ----------------------------------------------------

def test_reports_derive_step_reports_from_scrapes():
    mon = PodMonitor(lambda: [], interval_s=999)
    _seed(mon, 0, "hostA", step_time=0.1, steps=12, resyncs=2,
          comm_sum=0.3, total_sum=1.0, t=7.5)
    _seed(mon, -1, "", step_time=0.1)     # identity-less pre-init scrape
    _seed(mon, 1, "hostB")                # no step time: no report
    reports = mon.reports()
    assert set(reports) == {0}
    r = reports[0]
    assert isinstance(r, StepReport)
    assert r.rank == 0 and r.host == "hostA"
    assert r.step == 12 and r.p50 == pytest.approx(0.1)
    assert r.resyncs == 2
    assert r.comm_fraction == pytest.approx(0.3)
    assert r.t == 7.5


def test_merged_report_fetcher_kv_wins_scrape_fills():
    mon = PodMonitor(lambda: [], interval_s=999)
    _seed(mon, 0, "hostA", step_time=0.5, steps=3)
    _seed(mon, 1, "hostB", step_time=0.2, steps=3)
    kv = {0: StepReport(rank=0, host="hostA", step=9, n=8, p50=0.11,
                        mean=0.11, last=0.11)}
    fetch = podmon_lib.merged_report_fetcher(lambda: dict(kv), mon)
    out = fetch()
    assert out[0].p50 == 0.11          # KV report wins for rank 0
    assert out[0].step == 9
    assert out[1].p50 == pytest.approx(0.2)   # scrape fills rank 1


def test_engine_same_evict_decision_from_scrape_as_from_kv():
    """The acceptance gate: on the same seeded straggler plan the
    AutoscaleEngine must reach the SAME decision whether its reports
    come from the KV publisher or from the pod aggregator's scrape
    snapshots."""
    policy = AutoscalePolicy.from_dict(dict(
        straggler_ratio=2.0, straggler_patience=2, min_ranks=3,
        evict_ttl_s=10.0, evict_cooldown_s=0.0, grow_cooldown_s=0.0,
        tick_interval_s=1.0))
    hosts = {"a": 1, "b": 1, "c": 1}
    plan = [  # (tick, per-rank (host, p50, step))
        [("a", 0.05, i * 5), ("b", 0.05, i * 5), ("c", 0.5, i * 5)]
        for i in range(5)]

    def run(make_fetch):
        now = {"t": 0.0}
        table = {}
        engine = AutoscaleEngine(policy, 1, 3, make_fetch(table),
                                 clock=lambda: now["t"], log_path="")
        for row in plan:
            table.clear()
            table.update({r: spec for r, spec in enumerate(row)})
            now["t"] += 1.0
            engine.tick(hosts, {})
        return engine.decision_log()

    def kv_fetch(table):
        def fetch():
            return {r: StepReport(rank=r, host=h, step=s, n=8, p50=p,
                                  mean=p, last=p)
                    for r, (h, p, s) in table.items()}
        return fetch

    def scrape_fetch(table):
        mon = PodMonitor(lambda: [], interval_s=999)

        def fetch():
            mon._ranks.clear()
            for r, (h, p, s) in table.items():
                _seed(mon, r, h, step_time=p, steps=s)
            return mon.reports()
        return fetch

    kv_log = run(kv_fetch)
    scrape_log = run(scrape_fetch)
    assert kv_log == scrape_log
    assert len(kv_log) == 1
    assert "evict" in kv_log[0] and "c" in kv_log[0] \
        and "straggler" in kv_log[0]


# -- live scrape over real endpoints ----------------------------------------

def _serve_rank(rank, host, step_time):
    reg = MetricsRegistry(enabled=True)
    reg.set_global_labels(rank=str(rank), host=host)
    reg.gauge("hvd_tpu_autoscale_step_time_seconds", "p50").set(step_time)
    reg.counter("hvd_tpu_autoscale_steps_total", "steps").inc(5)
    srv = MetricsServer(reg=reg, host="127.0.0.1")
    port = srv.start(0)
    return srv, port


def test_pod_monitor_scrapes_real_endpoints_and_serves_pod_metrics():
    s0, p0 = _serve_rank(0, "hostA", 0.10)
    s1, p1 = _serve_rank(1, "hostB", 0.30)
    mon = PodMonitor(podmon_lib.static_endpoints(
        f"127.0.0.1:{p0},127.0.0.1:{p1}"), interval_s=999)
    try:
        assert mon.scrape_once() == 2
        m = mon.merged()
        assert m["ranks"] == [0, 1]
        assert m["step_skew_seconds"] == pytest.approx(0.2)
        assert m["slowest_rank"] == 1
        pod_port = mon.start(0)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{pod_port}/pod/metrics",
            timeout=10).read().decode()
        assert "hvd_tpu_pod_step_skew_seconds 0.2" in body
        assert 'hvd_tpu_pod_step_time_seconds{host="hostB",rank="1"} 0.3' \
            in body
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{pod_port}/pod/metrics.json",
            timeout=10).read())
        assert js["slowest_rank"] == 1
        assert "snapshots" not in js       # the lean JSON view
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{pod_port}/nope", timeout=10)
    finally:
        mon.stop()
        s0.stop()
        s1.stop()


def test_scrape_counts_dead_endpoint_as_error():
    mon = PodMonitor(podmon_lib.static_endpoints("127.0.0.1:1"),
                     interval_s=999, timeout_s=0.2)
    assert mon.scrape_once() == 0
    assert mon.merged()["scrape_errors"] == 1


def test_dead_rank_evicted_after_consecutive_misses():
    """An evicted/dead rank's last snapshot must not inflate skew or
    slowest-rank attribution forever (elastic shrink: the straggler's
    final slow sample would otherwise stick)."""
    mon = PodMonitor(podmon_lib.static_endpoints("127.0.0.1:1"),
                     interval_s=999, timeout_s=0.1)
    _seed(mon, 1, "hostB", step_time=0.9)
    mon._ranks[1]["endpoint"] = "127.0.0.1:1"   # the dead endpoint
    _seed(mon, 0, "hostA", step_time=0.1)       # healthy, other endpoint
    for i in range(mon.STALE_SCRAPES - 1):
        mon.scrape_once()
        assert 1 in mon.rank_snapshots()        # one miss is a restart
    mon.scrape_once()
    assert set(mon.rank_snapshots()) == {0}
    assert mon.merged()["slowest_rank"] == 0


def test_preinit_pseudo_rank_replaced_by_real_identity():
    """A pre-init scrape (no rank label yet) keys by endpoint position;
    once the worker gains its identity the pseudo-rank twin must not
    linger with a stale snapshot."""
    reg = MetricsRegistry(enabled=True)       # no rank label yet
    srv = MetricsServer(reg=reg, host="127.0.0.1")
    port = srv.start(0)
    mon = PodMonitor(podmon_lib.static_endpoints(f"127.0.0.1:{port}"),
                     interval_s=999)
    try:
        assert mon.scrape_once() == 1
        assert set(mon.rank_snapshots()) == {-1}
        reg.set_global_labels(rank="2", host="hostC")
        reg.gauge("hvd_tpu_autoscale_step_time_seconds", "p50").set(0.2)
        assert mon.scrape_once() == 1
        assert set(mon.rank_snapshots()) == {2}
    finally:
        mon.stop()
        srv.stop()


# -- endpoint discovery ------------------------------------------------------

def test_register_endpoint_roundtrip_over_kv(monkeypatch):
    from horovod_tpu.runner.rendezvous import RendezvousServer

    rdv = RendezvousServer("127.0.0.1")
    port = rdv.start()
    try:
        monkeypatch.setenv("HVD_TPU_RENDEZVOUS", f"127.0.0.1:{port}")
        monkeypatch.setenv("HVD_TPU_PROC_ID", "3")
        monkeypatch.setenv("HVD_TPU_HOSTNAME", "hostD")
        monkeypatch.setenv("HVD_TPU_ELASTIC_FORCE_LOCAL", "1")
        assert podmon_lib.register_endpoint(9100)
        eps = podmon_lib.kv_endpoints(rdv)()
        # Virtual host names are unresolvable: FORCE_LOCAL advertises
        # loopback.
        assert eps == ["127.0.0.1:9100"]
    finally:
        rdv.stop()


def test_register_endpoint_without_kv_is_noop(monkeypatch):
    monkeypatch.delenv("HVD_TPU_RENDEZVOUS", raising=False)
    assert not podmon_lib.register_endpoint(9100)


def test_combined_endpoints_dedupes_and_survives_dead_source():
    def boom():
        raise RuntimeError("dead source")

    eps = podmon_lib.combined_endpoints(
        podmon_lib.static_endpoints("h1:1,h2:2"),
        podmon_lib.static_endpoints("h2:2,h3:3"), boom)()
    assert eps == ["h1:1", "h2:2", "h3:3"]


def test_monitor_port_from_env():
    f = podmon_lib.monitor_port_from_env
    assert f({}) is None
    assert f({"HVD_TPU_POD_METRICS_PORT": ""}) is None
    assert f({"HVD_TPU_POD_METRICS_PORT": "0"}) == 0
    assert f({"HVD_TPU_POD_METRICS_PORT": "9100"}) == 9100
    assert f({"HVD_TPU_POD_METRICS_PORT": "-1"}) is None
    assert f({"HVD_TPU_POD_METRICS_PORT": "nope"}) is None


# -- /debug capture endpoints ------------------------------------------------

def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_debug_endpoints_disabled_answer_503():
    reg = MetricsRegistry(enabled=True)
    srv = MetricsServer(reg=reg, host="127.0.0.1")
    port = srv.start(0, debug=False)
    try:
        code, body = _get(f"http://127.0.0.1:{port}/debug/stacks")
        assert code == 503 and "HVD_TPU_METRICS_DEBUG" in body
        code, body = _get(f"http://127.0.0.1:{port}/debug/profile?ms=5")
        assert code == 503 and "HVD_TPU_METRICS_DEBUG" in body
    finally:
        srv.stop()


def test_debug_stacks_dumps_all_threads():
    reg = MetricsRegistry(enabled=True)
    srv = MetricsServer(reg=reg, host="127.0.0.1")
    port = srv.start(0, debug=True)
    try:
        code, body = _get(f"http://127.0.0.1:{port}/debug/stacks")
        assert code == 200
        assert "--- thread MainThread" in body
        assert "test_debug_stacks_dumps_all_threads" in body
    finally:
        srv.stop()


def test_debug_profile_bounded_capture(tmp_path):
    reg = MetricsRegistry(enabled=True)
    srv = MetricsServer(reg=reg, host="127.0.0.1")
    port = srv.start(0, debug=True)
    try:
        code, body = _get(
            f"http://127.0.0.1:{port}/debug/profile?ms=10"
            f"&dir={tmp_path}")
        assert code == 200, body
        payload = json.loads(body)
        assert payload["dir"] == str(tmp_path)
        assert payload["ms"] == 10
        # The capture actually landed on disk.
        assert any(tmp_path.rglob("*")), "profiler wrote nothing"
    finally:
        srv.stop()


def test_debug_profile_ms_is_capped():
    assert metrics_lib.PROFILE_MS_CAP <= 60_000
    reg = MetricsRegistry(enabled=True)
    srv = MetricsServer(reg=reg, host="127.0.0.1")
    port = srv.start(0, debug=True)
    try:
        # A bogus ms falls back to the default without a 500.
        code, body = _get(
            f"http://127.0.0.1:{port}/debug/profile?ms=nope&dir=/tmp"
            f"/hvd_tpu_profile_cap_test")
        assert code in (200, 503)
    finally:
        srv.stop()


# -- analyze_metrics multi-rank globbing -------------------------------------

def _write_dump(path, rank, mean_ms, wire_bytes):
    snap = {
        "hvd_tpu_step_seconds": {
            "type": "histogram", "help": "",
            "samples": [{"labels": {"rank": str(rank)},
                         "value": {"count": 10,
                                   "sum": mean_ms * 10 / 1000.0,
                                   "buckets": {}}}]},
        "hvd_tpu_allreduce_bytes_total": {
            "type": "counter", "help": "",
            "samples": [{"labels": {"wire": "int8",
                                    "rank": str(rank)},
                         "value": wire_bytes}]},
    }
    with open(path, "w") as f:
        f.write(json.dumps({"t": 1.0, "metrics": snap}) + "\n")


def _run_analyze(*args):
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "analyze_metrics.py")
    proc = subprocess.run([sys.executable, tool, *args],
                          capture_output=True, text=True, timeout=120)
    return proc.returncode, (json.loads(proc.stdout)
                             if proc.stdout.strip() else None)


def test_analyze_metrics_globs_rank_suffixed_dumps(tmp_path):
    base = tmp_path / "metrics.jsonl"
    _write_dump(str(base) + ".rank0", 0, 5.0, 1000.0)
    _write_dump(str(base) + ".rank1", 1, 9.0, 3000.0)
    rc, out = _run_analyze("--metrics", str(base))
    assert rc == 0
    # Per-rank view for both ranks, not silently rank 0 only.
    assert set(out["metrics_per_rank"]) == {"0", "1"}
    assert out["metrics_per_rank"]["1"]["step_seconds"]["mean_ms"] == 9.0
    merged = out["metrics"]
    assert merged["ranks"] == [0, 1]
    # Extensive quantities sum; skew is the pod-only number.
    assert merged["allreduce_bytes_on_wire"]["int8"] == 4000.0
    assert merged["step_skew_ms"] == pytest.approx(4.0)
    assert merged["slowest_rank"] == 1
    assert merged["step_seconds"]["count"] == 20


def test_analyze_metrics_legacy_bare_suffix_and_single_file(tmp_path):
    base = tmp_path / "metrics.jsonl"
    # Legacy `.0` suffix from pre-PR-9 launches still globs.
    _write_dump(str(base) + ".0", 0, 5.0, 100.0)
    _write_dump(str(base) + ".1", 1, 7.0, 100.0)
    rc, out = _run_analyze("--metrics", str(base))
    assert rc == 0 and out["metrics"]["ranks"] == [0, 1]
    # A bare single dump keeps the historical single-rank report shape.
    single = tmp_path / "solo.jsonl"
    _write_dump(str(single), 0, 5.0, 100.0)
    rc, out = _run_analyze("--metrics", str(single))
    assert rc == 0
    assert "metrics_per_rank" not in out
    assert out["metrics"]["step_seconds"]["mean_ms"] == 5.0


def test_analyze_metrics_flight_overlay(tmp_path):
    boxdir = tmp_path / "blackbox"
    boxdir.mkdir()
    ev = {"seq": 1, "op": "allreduce", "name": "allreduce.grad",
          "step": 2, "bytes": 64, "wire": "none", "t_submit": 0.0,
          "t_complete": 0.001, "outcome": "ok"}
    hung = dict(ev, t_complete=None, outcome="stalled")
    for rank, events in ((0, [ev]), (1, [hung])):
        (boxdir / f"blackbox.rank{rank}.json").write_text(json.dumps({
            "schema": 1, "rank": rank, "host": "", "pid": 1,
            "trigger": "sigusr2", "reason": "", "t_unix": 0.0,
            "step": 2, "seq_head": 1, "events": events, "stacks": {},
            "stall_inflight": {}, "recovery": {}}))
    rc, out = _run_analyze("--flight", str(boxdir))
    assert rc == 0
    assert out["flight"]["ranks"] == [0, 1]
    assert out["flight"]["laggard_rank"] == 1
    assert any("rank 1 never completed allreduce.grad" in v
               for v in out["flight"]["verdicts"])
    # Missing dir: a note, not a crash.
    rc, out = _run_analyze("--flight", str(tmp_path / "nothing"))
    assert rc == 0 and "no blackbox" in out["flight"]["note"]
