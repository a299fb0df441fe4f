"""The six readings of a block's parts (``mixer_proj_ms``, ``rope_ms``,
``mlp_ms``, ``norm_ms``, ``embed_ms``, ``loss_ms``; layer "model blocks"):
each reader on a hand-made trace, their entries in ``BENCHMARK.json``, and
``tools/block_parts.py``, which prints the same readings for a cell that
does not list them yet. Nothing here touches a device."""

import importlib.util
import os
import shutil

import pytest

from benchmark import of_which, phase_reduce
from benchmark.catalog import Catalog

CAT = Catalog()
HERE = os.path.dirname(os.path.abspath(__file__))
EXCERPT = os.path.join(HERE, "fixtures", "phases_chip_excerpt.xplane.pb")
LFM2 = "lfm2-8b-a1b-l8-e8-s8192"
SEVEN = ["gpt2s-s512", "gpt2s-s2048", "gpt2s-s4096", "bert-large-s512",
         "bert-large-s512-dp4", "ouro-2.6b-l8-s2048",
         "solar-open2-l4-e8-s4096"]
# metric: (the markers it reads, the cells that list it)
TABLE = {
    "mixer_proj_ms": (("hvd_mixer_proj",), SEVEN),
    "rope_ms": (("hvd_rope",), ["gpt2s-s512", "gpt2s-s2048", "gpt2s-s4096",
                                "ouro-2.6b-l8-s2048"]),
    "mlp_ms": (("hvd_mlp", "hvd_moe_shared"), SEVEN),
    "norm_ms": (("hvd_norm",), SEVEN),
    "embed_ms": (("hvd_embed",), SEVEN),
    "loss_ms": (("hvd_loss",), ["ouro-2.6b-l8-s2048",
                                "solar-open2-l4-e8-s4096"]),
}

FWD = "jit(step)/jvp(Lfm2LM)/layer1/"
BACK = "jit(step)/transpose(jvp(Lfm2LM))/jvp(Lfm2LM)/checkpoint/"
FUSION = "%fusion.{} = f32[8]{{0}} fusion(%p.1), kind=kLoop"
FLASH = ('%hvd_flash_fwd.{} = f32[8]{{0}} custom-call(%p.1), '
         'custom_call_target="tpu_custom_call"')
WHILE = "%while.{} = (f32[8]{{0}}) while(%tuple.1), body=%b, condition=%c"
# (instruction, microseconds, op_name), one after the other on one device.
EVENTS = [
    (FUSION, 5, "jit(step)/jvp(Lfm2LM)/hvd_embed/tok_emb/jit(_take)/gather"),
    (FUSION, 9, FWD + "hvd_norm/op_norm/mul"),
    (FUSION, 90, FWD + "mixer/hvd_mixer_proj/q/dot_general"),
    (FUSION, 14, FWD + "mixer/hvd_mixer_proj/hvd_rope/mul"),
    (FLASH, 20, FWD + "mixer/hvd_flash_fwd/pallas_call"),
    # a kernel is in no such reading, wherever it was called from
    (FLASH, 21, FWD + "mixer/hvd_mixer_proj/hvd_flash_fwd/pallas_call"),
    (FUSION, 70, FWD + "mixer/hvd_mixer_proj/o/dot_general"),
    (FUSION, 120, FWD + "ffn/hvd_mlp/down/dot_general"),
    (FUSION, 30, "jit(step)/jvp(SolarLM)/layer1/moe/hvd_moe_shared/"
     "shared_down/dot_general"),
    (FUSION, 40, "jit(step)/jvp(Lfm2LM)/checkpoint/hvd_lm_head/dot_general"),
    (FUSION, 11, "jit(step)/jvp(Lfm2LM)/checkpoint/hvd_loss/reduce_max"),
    # one event over its body's events; XLA gives it no name of its own
    (WHILE, 999, BACK + "layer1/ffn/hvd_mlp/while"),
    (FUSION, 60, BACK + "rematted_computation/layer1/mixer/hvd_mixer_proj/"
     "q/dot_general"),
    (FUSION, 16, BACK + "layer1/mixer/hvd_mixer_proj/hvd_rope/mul"),
    (FUSION, 140, BACK + "layer1/ffn/hvd_mlp/gate/dot_general"),
    (FUSION, 7, BACK + "layer1/hvd_norm/ffn_norm/reduce_sum"),
    (FUSION, 13, BACK + "hvd_loss/exp"),
    (FUSION, 3, "jit(step)/transpose(jvp(Lfm2LM))/hvd_embed/"
     "convert_element_type"),
    (FUSION, 43, "jit(step)/hvd_update/mul"),
]
# by hand, microseconds: forward + forward again + backward
BY_HAND = {"mixer_proj_ms": 90 + 14 + 70 + 60 + 16, "rope_ms": 14 + 16,
           "mlp_ms": 120 + 30 + 140, "norm_ms": 9 + 7, "embed_ms": 5 + 3,
           "loss_ms": 11 + 13}


def _trace(events):
    out, start = [], 0.0
    for i, (name, us, op_name) in enumerate(events):
        out.append([name.format(i), start, us * 1e3, "", op_name, 1])
        start += us * 1e3
    return {"devices": {"/device:TPU:0": out}, "hlo": {}}


def _record(events, steps=1):
    return {"trace": {"steps": steps},
            "of_which_trace": of_which._without_loops(_trace(events))}


@pytest.mark.parametrize("metric", sorted(TABLE))
def test_a_reading_sums_the_time_under_its_markers(metric):
    read = CAT.module("layer_metrics", metric).read
    assert read(_record(EVENTS)) == pytest.approx(BY_HAND[metric] / 1e3)
    assert read(_record(EVENTS, steps=2)) \
        == pytest.approx(BY_HAND[metric] / 2e3)
    # the flash kernels and the loop are in none of them
    bare = [e for e in EVENTS if e[0] not in (FLASH, WHILE)]
    assert read(_record(bare)) == pytest.approx(BY_HAND[metric] / 1e3)
    # a program none of whose events carries the name (the parent's, or a
    # family without the part): nothing to read, and nothing raised
    others = [e for e in EVENTS
              if not any(m in e[2] for m in TABLE[metric][0])]
    assert 0 < len(others) < len(EVENTS) and read(_record(others)) is None
    assert read({"trace": {}}) is None and read({}) is None
    assert read({"trace": {"steps": 3}, "of_which_trace": None}) is None


def test_the_feed_forward_every_token_meets_counts_the_shared_expert():
    read = CAT.module("layer_metrics", "mlp_ms").read
    shared = [e for e in EVENTS if "hvd_moe_shared" in e[2]]
    assert len(shared) == 1 and read(_record(shared)) == pytest.approx(0.030)
    dense = [e for e in EVENTS if "hvd_moe_shared" not in e[2]]
    assert read(_record(dense)) == pytest.approx(0.260)


def test_the_rotation_lies_inside_the_mixers_projections():
    rope = CAT.module("layer_metrics", "rope_ms").read(_record(EVENTS))
    proj = CAT.module("layer_metrics", "mixer_proj_ms").read(_record(EVENTS))
    assert 0 < rope <= proj
    # and the other five share no event
    assert sum(BY_HAND.values()) - BY_HAND["rope_ms"] == sum(
        us for name, us, op in EVENTS if name == FUSION
        and any(m in op for ms, _ in TABLE.values() for m in ms))


@pytest.mark.parametrize("metric", sorted(TABLE))
def test_a_reading_has_its_entry_its_file_and_its_cells(metric):
    entry = next(m for m in CAT.index["per_layer"] if m["name"] == metric)
    assert entry == {"name": metric, "unit": "ms/step", "better": "lower",
                     "source": "device_trace", "layer": "model blocks",
                     "moves": "train_tokens_per_s",
                     "workloads": TABLE[metric][1]}
    cells = {w["name"] for w in CAT.index["workloads"]}
    assert set(entry["workloads"]) <= cells - {LFM2}
    module = CAT.module("layer_metrics", metric)
    markers = getattr(module, "MARKERS", None) or (module.MARKER,)
    assert tuple(markers) == TABLE[metric][0]


def test_the_six_are_appended_and_none_is_reported_everywhere():
    names = [m["name"] for m in CAT.index["per_layer"]]
    assert names[-6:] == ["mixer_proj_ms", "rope_ms", "mlp_ms", "norm_ms",
                          "embed_ms", "loss_ms"]
    # the readings every cell reports stay the 20 they were: the
    # gated-convolution cell's test holds that cell to them and its two
    assert sum("workloads" not in m for m in CAT.index["per_layer"]) == 20
    assert not set(TABLE) & {m["name"] for m in
                             CAT.metrics("per_layer", LFM2)}
    for cell, count in (("gpt2s-s512", 5), ("bert-large-s512-dp4", 4),
                        ("ouro-2.6b-l8-s2048", 6),
                        ("solar-open2-l4-e8-s4096", 5)):
        assert len(set(TABLE) & {m["name"] for m in CAT.metrics(
            "per_layer", cell)}) == count


def test_the_tool_prints_a_cells_readings_from_its_own_newest_trace(
        tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "block_parts", os.path.join(CAT.root, "tools", "block_parts.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    with pytest.raises(SystemExit, match="no trace"):
        tool.read(LFM2, 1, root=str(tmp_path))
    # the chip's excerpt, a program from before the names: read through
    # the real file, every reading says there is nothing to read
    for cell, stamp in ((LFM2, "t1"), (LFM2, "t2"), ("gpt2s-s512", "t3")):
        where = tmp_path / ".bench_scratch" / cell / "plugins" / "profile" \
            / stamp
        where.mkdir(parents=True)
        shutil.copy(EXCERPT, where / "host.xplane.pb")
        os.utime(where / "host.xplane.pb", (0, int(stamp[1]) * 1000))
    got = tool.read(LFM2, 28, root=str(tmp_path))
    assert set(got) == set(TABLE) | {"moe_route_ms", "moe_expert_ms",
                                     "moe_shared_ms"}
    assert set(got.values()) == {None}
    # the cell's own newest file, not another cell's newer one; the values
    # are the committed readers'
    read = []
    monkeypatch.setattr(phase_reduce, "read_trace", lambda path, names: (
        read.append(path), _trace(EVENTS))[1])
    got = tool.read(LFM2, 2, root=str(tmp_path))
    assert [os.path.relpath(p, tmp_path) for p in read] == [os.path.join(
        ".bench_scratch", LFM2, "plugins", "profile", "t2", "host.xplane.pb")]
    assert {k: got[k] for k in TABLE} == pytest.approx(
        {k: us / 2e3 for k, us in BY_HAND.items()})
    assert got["moe_shared_ms"] == pytest.approx(0.015)
    assert got["moe_route_ms"] is None and got["moe_expert_ms"] is None
