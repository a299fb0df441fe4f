"""The yardstick's own arithmetic, on the CPU: the trace reduction on a
small recorded trace, the FLOP and byte counts against hand-worked
numbers, the token stream, the HLO counts, and ``BENCHMARK.json`` against
the contract's rules for names, units and files. Nothing here describes a
TPU topology or touches a device."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import device as device_lib
from benchmark import flops, hlo_counts, stream, trace_reduce
from benchmark.catalog import ROOT, Catalog

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = hlo_counts.load_names()
# A Mosaic call as the chip's trace names it: its whole HLO instruction.
MOSAIC = ('%{} = bf16[8,128]{{1,0}} custom-call(bf16[8,128]{{1,0}} {}), '
          'custom_call_target="tpu_custom_call"')
BUCKET_KERNELS = ("hvd_scale", "hvd_adasum_dot_norms", "hvd_adasum_combine",
                  "hvd_int8_quantize", "hvd_int8_quantize_sr",
                  "hvd_int8_dequantize")


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        return trace_reduce.reduce_trace(json.load(f), steps=1, names=NAMES)


# -- trace reduction ---------------------------------------------------------

def test_overlapping_events_give_a_busy_share_of_at_most_one(reduced):
    # Durations summed would be 750 us in a 601 us window: 1.25.
    for dev in reduced["per_device"].values():
        assert dev["busy_s"] == pytest.approx(550e-6)
        assert 0.0 <= dev["idle_share"] <= 1.0
    assert reduced["window_s"] == pytest.approx(601e-6)
    assert reduced["devices"] == 2


def test_collective_half_covered_by_compute_is_half_exposed(reduced):
    assert reduced["mean"]["collective_s"] == pytest.approx(200e-6)
    assert reduced["mean"]["exposed_collective_s"] == pytest.approx(100e-6)


def test_classes_and_idle_account_for_the_window(reduced):
    mean = reduced["mean"]
    assert mean["flash_s"] == pytest.approx(100e-6)
    assert mean["dense_s"] == pytest.approx(350e-6)
    idle = reduced["window_s"] - mean["busy_s"]
    assert (mean["dense_s"] + mean["flash_s"]
            + mean["exposed_collective_s"] + idle) == pytest.approx(
                reduced["window_s"])


def test_breakdown_names_ops_and_attributes_gaps_to_host_spans(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["%all-reduce.4"] == ops["%all-reduce.*"] \
        == pytest.approx(200e-6)
    assert ops["%fusion.*"] == pytest.approx(400e-6)
    assert len(reduced["breakdown"]["device_ops"]) <= 10
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert gaps["loss.fetch"] == pytest.approx(50e-6)


def test_a_trace_without_device_events_reduces_to_nothing():
    assert trace_reduce.reduce_trace({"devices": {}, "host": []}, 3,
                                     NAMES) == {}


@pytest.mark.parametrize("name, text, want", [
    ("%all-reduce.12", "", "collective"),
    ("all-reduce-start.3", "", "collective"),
    ("%reduce-scatter.1", "", "collective"),
    ("%all-gather-done.7", "", "collective"),
    ("%collective-permute.2", "", "collective"),
    ("%all-reduce-scatter-fusion", "", "dense"),
    ('%custom-call.228 = f32[768,768]{1,0} custom-call(f32[192,768]{1,0} '
     '%s), custom_call_target="ConcatBitcast"', "", "dense"),
    ("%fusion.3 = f32[4]{0} fusion(f32[4]{0} %all-reduce.1), kind=kLoop",
     "", "dense"),
    ("%fusion.77", "jit(step)/mul", "dense"),
    # A Mosaic call is a flash kernel by what it is, an attention kernel
    # named in ``flash_kernels``: by its own instruction name, as the
    # chip's compiler writes it, or by the text of its own stats ...
    (MOSAIC.format("hvd_flash_fwd.2", "%p.1"), "", "flash"),
    (MOSAIC.format("hvd_flash_dkv.3", "%hvd_flash_fwd.2"), "", "flash"),
    (MOSAIC.format("hvd_flash_dq.5", "%p.1"), "", "flash"),
    (MOSAIC.format("transpose_jvp_hvd_flash_dkv__.1", "%p.1"), "", "flash"),
    (MOSAIC.format("custom-call.5", "%p.1"),
     "jit(step)/jvp(GPT)/layer0/attn/hvd_flash_fwd/pallas_call", "flash"),
    # ... and every other Mosaic call is dense work of the layer that
    # owns it: a recurrence's kernel under or outside its scope, a
    # grouped matmul of the repo's own or XLA's, a wire's (the six
    # ``scopes.BUCKET_KERNELS``), one nothing names, and one that merely
    # takes a flash kernel's result as an operand.
    (MOSAIC.format("hvd_kda_fwd.7", "%p.1"),
     "jit(step)/jvp(SolarLM)/layer1/attn/hvd_kda/pallas_call", "dense"),
    (MOSAIC.format("hvd_kda_bwd.9", "%p.1"), "", "dense"),
    (MOSAIC.format("hvd_moe_experts_gmm.3", "%p.1"), "", "dense"),
    (MOSAIC.format("ragged-dot-none.4", "%p.1"), "ragged-dot-none", "dense"),
    (MOSAIC.format("ragged-dot-metadata.4", "%p.1"), "", "dense"),
    *[(MOSAIC.format(kernel + ".2", "%p.1"), "", "dense")
      for kernel in BUCKET_KERNELS],
    (MOSAIC.format("hvd_int8_dequantize.2", "%p.1"),
     "jit(step)/hvd_reduce/hvd_int8_dequantize/pallas_call", "dense"),
    (MOSAIC.format("attn.9", "%x"), "", "dense"),
    (MOSAIC.format("hvd_grouped_matmul.4", "%hvd_flash_fwd.2"), "", "dense"),
])
def test_classify_by_the_name_lists(name, text, want):
    assert trace_reduce.classify(name, text, NAMES) == want


def test_the_bucket_kernels_of_the_table_are_the_programs():
    """The six wire kernels of ``ops/pallas_kernels.py``: dense by the
    allow-list, with no list of their own to keep in step (the data
    file's ``not_flash_kernels`` is read by nothing under ``benchmark/``
    and stays for ``tests/test_scopes.py``). None of them holds an
    attention kernel's name, or it would be taken for one."""
    from horovod_tpu.common import scopes

    assert BUCKET_KERNELS == scopes.BUCKET_KERNELS
    assert NAMES["not_flash_kernels"] == list(BUCKET_KERNELS)
    flash = [k for k, _ in NAMES["flash_kernels"]]
    assert flash == list(scopes.FLASH_KERNELS)
    assert not any(f in k for f in flash for k in BUCKET_KERNELS)
    assert not any(f in scope for f in flash for scope in (
        scopes.STEP_SCOPES + scopes.LOOP_SCOPES + scopes.MOE_SCOPES
        + scopes.LINEAR_ATTN_SCOPES))


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)])
    assert u == [(0, 4), (5, 7)]
    assert trace_reduce.total(u) == 6
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 12)]) == \
        [(0, 2), (3, 5)]
    assert trace_reduce.subtract([(0, 2), (4, 6)], []) == [(0, 2), (4, 6)]
    assert trace_reduce.subtract([(0, 2), (4, 6)], [(0, 6)]) == []
    assert trace_reduce.overlap((0, 5), (3, 9)) == 2


# -- FLOPs and bytes, by hand ------------------------------------------------

def _family_flops(config_name, seq_len):
    cat = Catalog()
    config = cat.config(config_name)
    return cat.module("families", config["family"]).train_flops_per_token(
        config, seq_len)


@pytest.mark.parametrize("config, seq_len, want", [
    # 12 layers x (4 x 768^2 + 2 x 768 x 3072) = 84,934,656 weights in
    # layer matmuls, + 50257 x 768 = 38,597,376 in the head; x 6
    # = 741,192,192. Causal attention: 6 x 12 x S x 768.
    ("gpt2-small", 512, 741_192_192 + 28_311_552),
    ("gpt2-small", 2048, 741_192_192 + 113_246_208),
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) = 301,989,888, + 30522 x 1024
    # = 31,254,528; x 6 = 1,999,466,496. Unmasked: 12 x 24 x 512 x 1024.
    ("bert-large", 512, 1_999_466_496 + 150_994_944),
])
def test_train_flops_per_token_by_hand(config, seq_len, want):
    assert _family_flops(config, seq_len) == want


def test_causal_attention_is_counted_as_half():
    args = (12, 768, 3072, 50257, 2048)
    full = flops.transformer_train_flops_per_token(*args, causal=False)
    causal = flops.transformer_train_flops_per_token(*args, causal=True)
    assert full - causal == 6 * 12 * 2048 * 768


def test_flash_cost_by_hand():
    # One S x S x d product: 2 x 32 x 12 x 512^2 x 64 = 12,884,901,888
    # FLOPs; causal half = 6,442,450,944. Forward 2, backward 5.
    cost = flops.flash_attention_cost(32, 12, 512, 64, causal=True)
    assert cost["fwd"][0] == 2 * 6_442_450_944
    assert cost["bwd"][0] == 5 * 6_442_450_944
    # One bf16 tensor: 32 x 12 x 512 x 64 x 2 = 25,165,824 bytes; one
    # fp32 row: 32 x 12 x 512 x 4 = 786,432.
    assert cost["fwd"][1] == 4 * 25_165_824 + 786_432
    assert cost["bwd"][1] == 8 * 25_165_824 + 786_432
    full = flops.flash_attention_cost(32, 12, 512, 64, causal=False)
    assert full["fwd"][0] == 2 * cost["fwd"][0]
    assert full["fwd"][1] == cost["fwd"][1]


def test_roofline_says_which_bound_applies():
    peaks = device_lib.peaks("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    assert flops.roofline_seconds(1.0, 819e9, peaks) == (1.0, "memory")


def test_a_device_without_published_peaks_is_an_error():
    with pytest.raises(KeyError):
        device_lib.peaks("cpu")


# -- the token stream --------------------------------------------------------

def test_token_stream_repeats_for_a_seed_and_differs_for_another():
    traffic = {"batch": 4, "seq_len": 16, "score_rate": 0.15}

    def take(seed, n=3):
        it = stream.token_stream(seed, traffic, 1000)
        return [next(it) for _ in range(n)]

    a, b, c = take(7), take(7), take(8)
    for x, y in zip(a, b):
        assert np.array_equal(x["tokens"], y["tokens"])
        assert np.array_equal(x["scored"], y["scored"])
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])
    assert a[0]["tokens"].shape == (4, 17)
    assert a[0]["tokens"].dtype == np.int32
    assert a[0]["scored"].shape == (4, 16)
    assert 0 <= a[0]["tokens"].min() and a[0]["tokens"].max() < 1000
    assert stream.tokens_per_step(traffic) == 64


def test_token_stream_scores_positions_only_where_the_traffic_says():
    batch = next(stream.token_stream(1, {"batch": 2, "seq_len": 8}, 50))
    assert set(batch) == {"tokens"}
    many = next(stream.token_stream(
        1, {"batch": 64, "seq_len": 512, "score_rate": 0.15}, 50))
    assert many["scored"].mean() == pytest.approx(0.15, abs=0.01)


# -- HLO counts ----------------------------------------------------------------

def test_hlo_counts_collectives_bytes_and_mosaic_calls():
    hlo = "\n".join([
        '%all-reduce.1 = f32[1024,256]{1,0} all-reduce(f32[1024,256]{1,0} '
        '%p), replica_groups={{0,1,2,3}}, to_apply=%add',
        '%ars = (bf16[128]{0}, bf16[128]{0}) all-reduce-start(bf16[128]{0} '
        '%q), to_apply=%add',
        '%ard = bf16[128]{0} all-reduce-done((bf16[128]{0}, bf16[128]{0}) '
        '%ars)',
        '%ag = f32[8,4]{1,0} all-gather(f32[2,4]{1,0} %r), dimensions={0}',
        '%attn = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), '
        'custom_call_target="tpu_custom_call"',
        '%fusion.2 = f32[4]{0} fusion(f32[4]{0} %all-reduce.1), kind=kLoop',
        # the attention kernels, named as the chip's compiler writes them
        # (by the name or by the op_name), and a grouped matmul that takes
        # one's result: an operand's name makes no flash kernel
        '%hvd_flash_fwd.2 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} '
        '%x), custom_call_target="tpu_custom_call"',
        '%custom-call.7 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} '
        '%x), custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(step)/transpose(jvp(hvd_flash_dkv))/pallas_call"}',
        '%ragged-dot-none.4 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} '
        '%hvd_flash_fwd.2), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
    ])
    got = hlo_counts.count(hlo, NAMES)
    assert got["mosaic_calls"] == 4
    assert got["flash_mosaic_calls"] == 2
    assert got["collectives"] == {
        "all-reduce": {"ops": 2, "bytes": 1024 * 256 * 4 + 128 * 2},
        "all-gather": {"ops": 1, "bytes": 8 * 4 * 4}}


# -- BENCHMARK.json against the contract --------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def index():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def _line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_benchmark_json_has_exactly_the_contracts_keys(index):
    assert set(index) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(index["run_seconds"], int)
    assert 1 <= index["run_seconds"] <= 51
    assert 1 <= len(index["paths"]) <= 16
    for p in index["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(index["command"]) <= 32
    assert all(_line(w) for w in index["command"])
    script = index["command"][1]
    assert any(script.startswith(p + "/") for p in index["paths"])
    assert os.path.isfile(os.path.join(ROOT, script))


def test_every_file_under_paths_is_named_from_the_allowed_characters(index):
    for p in index["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel


def test_configs_name_files_that_exist_and_are_each_used(index):
    cat = Catalog()
    names = [c["name"] for c in index["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in index["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in index["workloads"]}
    for c in index["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in index["paths"])
        config = cat.config(c["name"])
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        cat.module("families", config["family"])
        cat.module("reference", config["family"])


def test_workloads_name_files_that_exist(index):
    cat = Catalog()
    cells = index["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell = cat.cell(w["name"])
        cat.traffic(cell["traffic"])
        cat.module("jobs", cell["job"])
        assert cell["tolerance"]["reason"]
        # The rehearsal preset is found by name too.
        cat.config(cell["rehearsal"]["config"])
        cat.traffic(cell["rehearsal"]["traffic"])


def test_metrics_use_allowed_names_units_and_sources(index):
    cat = Catalog()
    e2e, layers = index["end_to_end"], index["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in index["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in {m["name"] for m in e2e}
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in {e["name"] for e in e2e}
        assert _line(m["layer"])
        assert callable(cat.module("layer_metrics", m["name"]).read)
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        reported = {m["name"] for m in cat.metrics("end_to_end", cell)}
        assert "setup_s" in reported and len(reported) >= 2
        assert cat.metrics("per_layer", cell)


def test_a_full_check_fits_with_all_24_cells(index):
    runs = 2 + 14 * 24
    assert (runs * (index["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)


# -- the families build the models the configurations describe -----------

def test_families_build_the_published_models():
    from horovod_tpu.models import bert_large, gpt_small

    cat = Catalog()
    assert cat.module("families", "gpt").build(
        cat.config("gpt2-small")) == gpt_small()
    assert cat.module("families", "bert").build(
        cat.config("bert-large")) == bert_large(max_len=512)


# -- a slice of a trace recorded on the chip -----------------------------------

def test_reduction_of_a_slice_recorded_on_the_chip():
    """71 events around one flash kernel, as the v5e's profiler names
    them: whole HLO instructions, the kernel told by its Mosaic target."""
    with open(os.path.join(HERE, "fixtures", "trace_chip_excerpt.json")) as f:
        trace = json.load(f)
    # Recorded before the kernels had names: ``%attn.36`` is dense work by
    # the names of every cell, and a flash kernel for the cell whose file
    # of names adds it to the allow-list.
    plain = trace_reduce.reduce_trace(trace, steps=1, names=NAMES)["mean"]
    named = hlo_counts.load_names([{"flash_kernels": [["attn", "flash_fwd"]]}])
    got = trace_reduce.reduce_trace(trace, steps=1, names=named)
    mean = got["mean"]
    assert got["devices"] == 1
    assert plain["flash_s"] == 0.0
    assert plain["dense_s"] == pytest.approx(mean["dense_s"] + mean["flash_s"])
    assert mean["flash_s"] == pytest.approx(1.829717e-3)   # %attn.36 alone
    assert mean["collective_s"] == 0.0
    assert mean["busy_s"] <= got["window_s"]
    assert mean["busy_s"] == pytest.approx(mean["dense_s"] + mean["flash_s"])
    # The op line is the core's own sequence: nothing overlaps on it, so
    # here the union equals the sum of the durations.
    assert mean["busy_s"] == pytest.approx(
        sum(e[2] for e in trace["devices"]["/device:TPU:0"]) / 1e9)
    ops = dict(got["breakdown"]["device_ops"])
    assert ops["%attn.*"] == ops["%attn.36"] == pytest.approx(1.829717e-3)
    assert trace_reduce.short_name(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") \
        == "%fusion.12"
