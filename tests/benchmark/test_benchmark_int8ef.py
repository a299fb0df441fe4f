"""The int8 error-feedback cell's own list (``bert-large-s512-dp4-int8ef``,
PR 45): the two readers of its kernels, ``quantize_ms`` and
``dequantize_ms``, on synthetic events named as the compiled step names
them, what they give on a program that has no such call (the parent's),
where ``BENCHMARK.json`` lists them, and the earlier PRs' positional tests
run whole on the lists as they stood before this PR. This file's own tests
hold order and membership, never the end of a list or its length. Nothing
here touches a device."""

import importlib

import pytest

from benchmark import of_which
from benchmark.catalog import Catalog

CAT = Catalog()
CELL = "bert-large-s512-dp4-int8ef"
READERS = ["quantize_ms", "dequantize_ms"]

CALL = ' = custom-call(...), custom_call_target="tpu_custom_call"'
FUSION = "%fusion.{}"
REDUCE = "jit(step)/shard_map/hvd_reduce/"
# One bucket of a step as the trace of the compiled program shows it: the
# pack, the thresholds' draw, the quantise call (named by its
# ``pallas_call``), the sum over ranks, the owned chunk's requantise, the
# gathered result's dequantise, the unpack, the update.
EVENTS = [
    (FUSION, 400, REDUCE + "pack/concatenate"),
    ("%add_maximum_fusion.{}", 340, REDUCE + "jit(_uniform)/max"),
    ("%hvd_int8_quantize_sr.{}" + CALL, 260,
     REDUCE + "hvd_int8_quantize_sr/pallas_call"),
    ("%multiply_reduce_fusion.{}", 90, REDUCE + "reduce_sum"),
    ("%hvd_int8_quantize_sr.{}" + CALL, 70,
     REDUCE + "hvd_int8_quantize_sr/pallas_call"),
    ("%hvd_int8_dequantize.{}" + CALL, 270,
     REDUCE + "hvd_int8_dequantize/pallas_call"),
    (FUSION, 380, REDUCE + "unpack/slice"),
    (FUSION, 43, "jit(step)/shard_map/hvd_update/mul"),
]
# the step before PR 45: XLA's own fusions where the dequantise call is
BEFORE = [e for e in EVENTS if "hvd_int8_dequantize" not in e[0]] + [
    ("%convert_bitcast_fusion.{}", 560, REDUCE + "convert_element_type"),
    ("%mul.{}", 720, REDUCE + "mul")]


def _record(events, steps=1):
    out, start = [], 0.0
    for i, (name, us, op_name) in enumerate(events):
        out.append([name.format(i), start, us * 1e3, "", op_name, 1])
        start += us * 1e3
    trace = {"devices": {"/device:TPU:0": out}, "hlo": {}}
    return {"trace": {"steps": steps},
            "of_which_trace": of_which._without_loops(trace)}


def test_quantize_ms_sums_both_quantise_calls_of_a_bucket():
    read = CAT.module("layer_metrics", "quantize_ms").read
    # the bucket's and the owned chunk's; not the thresholds beside them
    assert read(_record(EVENTS)) == pytest.approx(0.330)
    assert read(_record(EVENTS, steps=2)) == pytest.approx(0.165)
    # the parent's step has the calls too, under the same names
    assert read(_record(BEFORE)) == pytest.approx(0.330)
    # round to nearest is named without the suffix and read alike
    nearest = [(n.replace("_sr", ""), us, op.replace("_sr", ""))
               for n, us, op in EVENTS]
    assert read(_record(nearest)) == pytest.approx(0.330)


def test_dequantize_ms_reads_the_call_and_nothing_where_there_is_none():
    read = CAT.module("layer_metrics", "dequantize_ms").read
    assert read(_record(EVENTS)) == pytest.approx(0.270)
    # a program that dequantises in XLA's own fusions (the parent's): the
    # reader finds nothing to read and raises nothing, and the line
    # leaves the metric out
    assert read(_record(BEFORE)) is None


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_gives_nothing_without_a_trace(reader):
    read = CAT.module("layer_metrics", reader).read
    assert read({"trace": {}}) is None and read({}) is None
    assert read({"trace": {"steps": 3}, "of_which_trace": None}) is None
    others = [e for e in EVENTS if "hvd_int8" not in e[0] + e[2]]
    assert len(others) == 5 and read(_record(others)) is None


def test_the_cell_lists_the_two_readers_and_no_other_cell_does():
    per_layer = {m["name"]: m for m in CAT.index["per_layer"]}
    for name in READERS:
        assert per_layer[name] == {
            "name": name, "unit": "ms/step", "better": "lower",
            "source": "device_trace", "layer": "optimizer and reduction",
            "moves": "train_tokens_per_s", "workloads": [CELL]}
    common = {m["name"] for m in CAT.index["per_layer"]
              if "workloads" not in m}
    assert {"bucket_copy_ms", "optimizer_ms", "collective_ms"} <= common
    for entry in CAT.index["workloads"]:
        got = {m["name"] for m in CAT.metrics("per_layer", entry["name"])}
        assert (set(READERS) <= got) == (entry["name"] == CELL)
    assert {m["name"] for m in CAT.metrics("per_layer", CELL)} \
        == common | set(READERS)
    # appended, in this order, after every reader that was there
    names = [m["name"] for m in CAT.index["per_layer"]]
    at = [names.index(n) for n in ["swa_tiles_visited_pct"] + READERS]
    assert at == sorted(at)
    # the layer is one BENCHMARK.json already names, letter for letter
    assert per_layer["bucket_copy_ms"]["layer"] == "optimizer and reduction"


# -- the earlier PRs' positional tests, whole, on the lists before this PR --

MARKED = [("test_benchmark_sdar", "test_the_cells_report_their_readings",
           None)] + [
    ("test_benchmark_laguna",
     "test_the_marked_tests_hold_whole_before_this_pr", case)
    for case in importlib.import_module("test_benchmark_laguna").MARKED]


@pytest.mark.parametrize(
    "module, test, case", MARKED,
    ids=[t if c is None else f"{c[1]}-{c[2][1] if c[2] else 'whole'}"
         for _, t, c in MARKED])
def test_the_marked_tests_hold_whole_before_this_pr(module, test, case,
                                                    monkeypatch):
    """The cases ``tests/conftest.py`` marks since this PR appended two
    readers, each run whole on ``BENCHMARK.json``'s per-layer list with
    the two taken out: every assertion of theirs holds there, the
    positions and the lengths too. PR 42's own runner of the five older
    marked cases is among them, so those run whole here as well, on the
    lists as they stood before PR 42."""
    names = ["test_benchmark_sdar", "test_benchmark_laguna",
             "test_benchmark_lfm2", "test_benchmark_block_parts"]
    their = importlib.import_module(module)
    index = dict(their.CAT.index)
    kept = [m for m in index["per_layer"] if m["name"] not in READERS]
    # the two lie after everything that was there
    assert index["per_layer"][:len(kept)] == kept
    assert [m["name"] for m in index["per_layer"][len(kept):]] == READERS
    index["per_layer"] = kept
    for name in names:
        monkeypatch.setattr(importlib.import_module(name).CAT, "index",
                            index)
    if case is None:
        getattr(their, test)()
    else:
        getattr(their, test)(*case, monkeypatch)
