"""The split of ``dense_ms`` and ``flash_ms`` by the program's names
(``benchmark/phase_reduce.py``), on the CPU: the marker lists on a
hand-made trace, the reader of the ``.xplane.pb`` on an excerpt recorded
on the chip, and the seven per-layer metrics that read them. Nothing
here touches a device."""

import json
import os
import shutil

import pytest

from benchmark import phase_reduce, trace_reduce
from benchmark.catalog import ROOT, Catalog

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = phase_reduce.load_names()
EXCERPT = os.path.join(HERE, "fixtures", "phases_chip_excerpt.xplane.pb")
EXCERPT_DP4 = os.path.join(HERE, "fixtures",
                           "phases_chip_excerpt_dp4.xplane.pb")
NEW_METRICS = {
    "flash_fwd_ms": "flash_fwd", "flash_dkv_ms": "flash_dkv",
    "lm_head_ms": "lm_head", "fwd_ms": "fwd", "bwd_ms": "bwd",
    "optimizer_ms": "optimizer", "bucket_copy_ms": "bucket_copy"}
FLASH_PARTS = [p for _, p in NAMES["flash_kernels"]]
MOSAIC = ('%{} = f32[8]{{0}} custom-call(%p.1), '
          'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "fixtures", "phases_small.json")) as f:
        return json.load(f)


def _as_trace_reduce_sees(trace):
    """The same events without their ``op_name``: what ``trace_reduce``
    is given."""
    events = {k: [e[:4] for e in v] for k, v in trace["devices"].items()}
    return trace_reduce.reduce_trace({"devices": events, "host": []},
                                     steps=1, names=NAMES)["mean"]


def _one_event(name, op_name):
    return {"devices": {"/device:TPU:0": [[name, 0.0, 1e3, "", op_name, 7]]}}


# -- the marker lists, on a hand-made trace --------------------------------

def test_every_part_takes_the_time_worked_out_by_hand(small):
    got = phase_reduce.reduce_phases(small, NAMES)
    assert got["devices"] == 2
    assert {p: round(s * 1e6, 6) for p, s in got["seconds"].items()} \
        == small["want_us"]
    assert got["named"] == {"flash": True, "dense": True}


def test_the_parts_partition_dense_and_flash_exactly(small):
    seconds = phase_reduce.reduce_phases(small, NAMES)["seconds"]
    mean = _as_trace_reduce_sees(small)
    flash = sum(seconds[p] for p in FLASH_PARTS)
    dense = sum(s for p, s in seconds.items() if p not in FLASH_PARTS)
    assert flash == pytest.approx(mean["flash_s"], rel=1e-12)
    assert dense == pytest.approx(mean["dense_s"], rel=1e-12)


def test_two_devices_are_averaged(small):
    a, b = (phase_reduce.reduce_phases(
        {"devices": {plane: small["devices"][plane]}},
        NAMES)["seconds"]["optimizer_update"] for plane in sorted(
            small["devices"]))
    assert (a, b) == (pytest.approx(60e-6), pytest.approx(120e-6))
    both = phase_reduce.reduce_phases(small, NAMES)["seconds"]
    assert both["optimizer_update"] == pytest.approx((a + b) / 2)


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/shard_map/hvd_reduce/pack/concatenate", "bucket_pack"),
    ("jit(step)/shard_map/hvd_reduce/unpack/slice", "bucket_unpack"),
    ("jit(step)/shard_map/hvd_reduce/convert_element_type", "bucket_other"),
    ("jit(step)/jvp(GPT)/hvd_lm_head/dot_general", "lm_head"),
    ("jit(step)/transpose(jvp(GPT))/hvd_lm_head/dot_general", "lm_head"),
    ("jit(step)/transpose(jvp(Bert))/hvd_lm_head/transpose", "lm_head"),
    ("jit(step)/hvd_update/mul", "optimizer_update"),
    ("jit(step)/transpose(jvp(GPT))/layer3/mlp_out/dot_general", "bwd"),
    ("jit(step)/jvp(GPT)/layer3/mlp_out/dot_general", "fwd"),
    ("jit(step)/jvp(jit(take_along_axis))/gather", "fwd"),
    ("jit(step)/add", "optimizer_apply"),
    ("", "unattributed"),
])
def test_a_dense_event_goes_to_the_first_marker_it_holds(op_name, want):
    seconds = phase_reduce.reduce_phases(_one_event(
        "%fusion.1 = f32[8]{0} fusion(%p.1), kind=kLoop", op_name),
        NAMES)["seconds"]
    assert {p for p, s in seconds.items() if s} == {want}


TRANSPOSED = "jit(step)/transpose(jvp(SolarLM))/"


@pytest.mark.parametrize("name, op_name, want", [
    # an attention kernel, by its own name (never an operand's) ...
    ("%hvd_flash_dq.5 = f32[8]{0} custom-call(%hvd_flash_fwd.3), "
     "custom_call_target=\"tpu_custom_call\"", "", "flash_dq"),
    (MOSAIC.format("hvd_flash_dkv.6"),
     "jit(step)/transpose(jvp(GPT))/layer0/attn/hvd_flash_dkv/pallas_call",
     "flash_dkv"),
    (MOSAIC.format("jvp_hvd_flash_fwd_.1"),
     "jit(loss)/jvp(hvd_flash_fwd)/pallas_call", "flash_fwd"),
    # ... and every other Mosaic call, to a part like any dense event: by
    # the scope it was called under,
    (MOSAIC.format("hvd_int8_dequantize.2"),
     "jit(step)/hvd_reduce/hvd_int8_dequantize/pallas_call", "bucket_other"),
    (MOSAIC.format("hvd_scale.2"),
     "jit(step)/shard_map/hvd_reduce/unpack/hvd_scale/pallas_call",
     "bucket_unpack"),
    (MOSAIC.format("hvd_lm_head_ce.2"), "", "lm_head"),    # by its name alone
    (MOSAIC.format("hvd_kda_fwd.7"),
     "jit(step)/jvp(SolarLM)/layer1/attn/hvd_kda/pallas_call", "fwd"),
    (MOSAIC.format("hvd_kda_bwd.9"), TRANSPOSED + "pallas_call", "bwd"),
    (MOSAIC.format("hvd_moe_experts_gmm.3"),
     TRANSPOSED + "layer1/moe/hvd_moe_experts/pallas_call", "bwd"),
    # where nothing names a part (XLA's kernels carry their own name
    # there), to a part of its own: not the optimizer's, not unattributed
    (MOSAIC.format("ragged-dot-none.4"), "ragged-dot-none", "other_kernel"),
    (MOSAIC.format("ragged-dot-metadata.4"), "ragged-dot-metadata",
     "other_kernel"),
    (MOSAIC.format("hvd_kda_bwd.9"), "", "other_kernel"),
    (MOSAIC.format("attn.36"), "", "other_kernel"),
    (MOSAIC.format("attn.36"), "jit(step)/add", "other_kernel"),
])
def test_a_mosaic_call_goes_to_the_kernel_or_the_layer_it_is_named_for(
        name, op_name, want):
    seconds = phase_reduce.reduce_phases(_one_event(name, op_name),
                                         NAMES)["seconds"]
    assert {p for p, s in seconds.items() if s} == {want}


def test_an_unnamed_mosaic_call_is_a_part_of_its_own_inside_dense_ms():
    """XLA's grouped-matmul kernels between a forward fusion, a flash
    kernel and ``apply_updates``: their time is in ``dense_ms``, in the
    part ``other_kernel`` and in no other: not in ``flash_ms`` (the
    attention kernels' alone) and not in ``optimizer_ms`` (the part of an
    event with an ``op_name`` that names no scope). The parts still sum to
    the two classes exactly."""
    line, start = [], 0.0
    for name, us, op_name in [
            ("%fusion.1 = f32[8]{0} fusion(%p.1), kind=kLoop", 100,
             "jit(step)/jvp(SolarLM)/layer1/moe/hvd_moe_experts/gather"),
            (MOSAIC.format("ragged-dot-metadata.2"), 2, "ragged-dot-metadata"),
            (MOSAIC.format("ragged-dot-none.3"), 50, "ragged-dot-none"),
            (MOSAIC.format("hvd_flash_fwd.4"), 20,
             "jit(step)/jvp(SolarLM)/layer0/attn/hvd_flash_fwd/pallas_call"),
            ("%fusion.5 = f32[8]{0} fusion(%p.1), kind=kLoop", 30,
             "jit(step)/add"),
            ("%fusion.6 = f32[8]{0} fusion(%p.1), kind=kLoop", 43,
             "jit(step)/hvd_update/mul")]:
        line.append([name, start, us * 1e3, "", op_name, 7])
        start += us * 1e3
    trace = {"devices": {"/device:TPU:0": line}}
    seconds = phase_reduce.reduce_phases(trace, NAMES)["seconds"]
    assert {p: round(s * 1e6, 6) for p, s in seconds.items() if s} == {
        "flash_fwd": 20.0, "other_kernel": 52.0, "fwd": 100.0,
        "optimizer_apply": 30.0, "optimizer_update": 43.0}
    mean = _as_trace_reduce_sees(trace)
    assert mean["flash_s"] == pytest.approx(20e-6)
    assert mean["dense_s"] == pytest.approx(225e-6)
    assert sum(s for p, s in seconds.items() if p not in FLASH_PARTS) \
        == pytest.approx(mean["dense_s"], rel=1e-12)
    phases = {phase: sum(seconds[p] for p in members)
              for phase, members in NAMES["phases"].items()}
    assert phases["other_kernel"] == pytest.approx(52e-6)
    assert phases["optimizer"] == pytest.approx(73e-6)
    assert sum(phases.values()) == pytest.approx(245e-6)


def test_the_flash_backward_is_one_phase_whichever_kernels_make_it(small):
    """``flash_dq`` is a part no call of today's program carries (0.0, a
    number: ``tests/test_scopes.py``) and no phase of its own: an older
    program's dq call counts with the dk/dv call in ``flash_dkv``, the
    whole flash backward, so the flash phases still sum to ``flash_ms``."""
    assert "flash_dq" not in NAMES["phases"]
    assert NAMES["phases"]["flash_dkv"] == ["flash_dq", "flash_dkv"]
    seconds = phase_reduce.reduce_phases(small, NAMES)["seconds"]
    assert seconds["flash_dq"] > 0 and seconds["flash_dkv"] > 0
    flash = sum(sum(seconds[p] for p in NAMES["phases"][phase])
                for phase in ("flash_fwd", "flash_dkv"))
    assert flash == pytest.approx(_as_trace_reduce_sees(small)["flash_s"])
    catalog = Catalog(ROOT)
    assert "flash_dq_ms" not in {m["name"]
                                 for m in catalog.index["per_layer"]}
    with pytest.raises(LookupError):
        catalog.module("layer_metrics", "flash_dq_ms")


def test_every_part_belongs_to_one_phase_and_every_phase_has_a_kind():
    parts = (FLASH_PARTS + [p for _, p in NAMES["dense_markers"]]
             + [NAMES["dense_default"], NAMES["no_op_name"],
                NAMES["kernel_default"]])
    members = [p for ms in NAMES["phases"].values() for p in ms]
    assert sorted(members) == sorted(parts)
    assert set(NEW_METRICS.values()) <= set(NAMES["phases"])


def test_a_program_without_the_names_gives_nothing_to_read(small):
    """The parent of the PR that brought the names: the same events,
    kernels called ``%attn.N`` and no ``hvd_`` scope in any ``op_name``."""
    devices = {k: [[e[0].replace("hvd_flash_", "attn_"), e[1], e[2], e[3],
                    "" if "hvd_" in e[4] else e[4], e[5]] for e in v]
               for k, v in small["devices"].items()}
    got = phase_reduce.reduce_phases({"devices": devices}, NAMES)
    assert got["named"] == {"flash": False, "dense": False}
    assert phase_reduce.reduce_phases({"devices": {"/device:TPU:0": []}},
                                      NAMES) == {}


# -- events XLA left nameless, named through the program's HLO -------------

def _hlo(**entries):
    """As ``read_hlo`` returns it, from ``name=(op_name, inner, operands)``;
    ``users`` follow from the operands."""
    hlo = {name.replace("_", "."): {"op_name": op, "inner": inner,
                                    "operands": operands, "users": []}
           for name, (op, inner, operands) in entries.items()}
    for name, entry in hlo.items():
        for operand in entry["operands"]:
            hlo[operand]["users"].append(name)
    return hlo


FWD = "jit(step)/jvp(GPT)/layer0/mlp_in/dot_general"
ROPE_BWD = ["jit(step)/transpose(jvp(GPT))/layer0/attn/mul",
            "jit(step)/transpose(jvp(GPT))/layer0/attn/sub",
            "jit(step)/jvp(GPT)/layer0/attn/mul"]
APPLY = "jit(step)/add"
HLO = _hlo(
    param_0=("", [], []),
    copy_1=("", [], ["param.0"]),
    copy_start_2=("", [], ["copy.1"]),
    copy_done_3=("", [], ["copy.start.2"]),
    fusion_4=(FWD, ["jit(step)/hvd_update/mul"], ["copy.done.3"]),
    fusion_5=("", ROPE_BWD, ["copy.done.3"]),
    fusion_6=(APPLY, [], ["fusion.5"]),
    copy_7=("", [], ["fusion.6"]),        # feeds the result only
    copy_8=("", [], ["param.0"]),         # and so does this, from nothing
    tuple_9=("", [], ["fusion.5", "copy.7", "copy.8"]))


@pytest.mark.parametrize("name, want", [
    ("fusion.4", [FWD]),                           # its own name
    ("fusion.5", ROPE_BWD),                        # a fusion's body
    ("copy.done.3", [FWD] + ROPE_BWD),             # its consumers
    ("copy.1", [FWD] + ROPE_BWD),                  # ... two steps on
    ("copy.7", [APPLY]),                           # no consumer: its producer
    ("copy.8", []),
    ("not.in.the.module", []),
])
def test_candidates_are_own_name_then_body_then_nearest_neighbours(name, want):
    assert phase_reduce.candidates(HLO, name) == want


def test_the_depth_of_the_search_for_consumers_is_bounded():
    chain = {f"copy.{i}": {"op_name": "", "inner": [], "operands": [],
                           "users": [f"copy.{i + 1}"]} for i in range(20)}
    chain["copy.20"] = {"op_name": "jit(step)/jvp(GPT)/mul", "inner": [],
                        "operands": [], "users": []}
    near = f"copy.{20 - phase_reduce.CONSUMER_DEPTH}"
    assert phase_reduce.candidates(chain, near) == ["jit(step)/jvp(GPT)/mul"]
    assert phase_reduce.candidates(chain, "copy.0") == []


def test_a_nameless_event_goes_to_the_part_most_of_its_candidates_give(
        monkeypatch):
    monkeypatch.setattr(phase_reduce, "read_hlo", lambda raw: HLO)
    trace = {"hlo": {7: b"ignored"}, "devices": {"/device:TPU:0": [
        ["%fusion.5 = (f32[8]{0}, f32[8]{0}) fusion(%p.1), kind=kLoop",
         0.0, 3e3, "", "", 7],
        ["%copy.8 = f32[8]{0} copy(%param.0)", 3e3, 1e3, "", "", 7]]}}
    got = phase_reduce.reduce_phases(trace, NAMES)
    assert {p: s for p, s in got["seconds"].items() if s} == {
        "bwd": pytest.approx(3e-6), "unattributed": pytest.approx(1e-6)}
    assert got["from_hlo_s"] == pytest.approx(3e-6)


# -- an excerpt of a trace recorded on the chip ----------------------------

# Recorded on the chip (PR 24, TPU v5 lite; ``gpt2s-s512`` on one device, and
# two of the four devices of ``bert-large-s512-dp4``), then cut so that each
# is still a valid ``XSpace``: of a device plane's 'XLA Ops' line the two
# events either side of the first event of each kind (the three flash
# kernels, the head forward and backward, ``apply_updates``, the scopes, an
# all-reduce, a rope fusion, a copy-done, a slice-done), with the metadata
# those events use (names cut to 1500 characters, byte stats dropped), and of
# the program's ``HloProto`` the instructions ``candidates`` visits for them,
# each with its name, ``op_name``, id, operand ids and called computations.

@pytest.fixture(scope="module")
def excerpt():
    return phase_reduce.read_trace(EXCERPT, NAMES)


def test_every_event_of_the_excerpt_finds_its_metadata(excerpt):
    """The events are ``ProfileData``'s, the metadata come from the wire,
    joined by name: none is left without its program's id."""
    with open(EXCERPT, "rb") as f:
        found = phase_reduce.read_metadata(f.read(), NAMES)
    trace = trace_reduce.load_xplane(EXCERPT, NAMES)
    assert sorted(found["events"]) == sorted(trace["devices"]) \
        == sorted(excerpt["devices"])
    (program, raw), = found["hlo"].items()
    assert program > 0 and len(raw) > 1000
    for plane, events in excerpt["devices"].items():
        assert [e[:4] for e in events] == trace["devices"][plane]
        assert all(e[0] in found["events"][plane] for e in events)
        assert {e[5] for e in events} == {program}


def test_the_excerpt_carries_op_names_where_profile_data_shows_none(excerpt):
    events = [e for evs in excerpt["devices"].values() for e in evs]
    assert len(events) >= 24
    assert all(e[3] == "" for e in events)          # the event's own stats
    with_name = [e[4] for e in events if e[4]]
    assert with_name and all(n.endswith(":") for n in with_name)
    assert any("jvp(GPT)/hvd_lm_head/" in n and "transpose(" not in n
               for n in with_name)
    assert any("transpose(jvp(GPT))/hvd_lm_head/" in n for n in with_name)
    assert any("/hvd_flash_dkv/pallas_call" in n for n in with_name)


def test_the_excerpts_nameless_events_are_named_through_its_hlo(excerpt):
    (program, raw), = excerpt["hlo"].items()
    hlo = phase_reduce.read_hlo(raw)
    nameless = [e for evs in excerpt["devices"].values() for e in evs
                if not e[4]]
    assert nameless and all(e[5] == program for e in nameless)
    named = [e for e in nameless if phase_reduce.candidates(
        hlo, trace_reduce.short_name(e[0]).lstrip("%"))]
    # What stays nameless feeds the program's result tuple and nothing else.
    assert sum(e[2] for e in named) > 0.8 * sum(e[2] for e in nameless)
    got = phase_reduce.reduce_phases(excerpt, NAMES)
    assert got["from_hlo_s"] == pytest.approx(sum(e[2] for e in named) / 1e9)
    # The rope fusion of one layer's q and k: a multi-output fusion whose
    # root is a tuple, 0.6 ms that the event's own stats leave nameless.
    rope = next(e for e in nameless if e[0].startswith("%fusion.41 "))
    votes = phase_reduce.candidates(hlo, "fusion.41")
    assert rope[2] > 5e5 and votes
    assert all("jvp(GPT)/layer" in v and "/attn/" in v for v in votes)
    without = phase_reduce.reduce_phases(
        {"devices": excerpt["devices"]}, NAMES)["seconds"]
    assert without["unattributed"] - got["seconds"]["unattributed"] \
        == pytest.approx(got["from_hlo_s"])


@pytest.mark.parametrize("path, collective", [(EXCERPT, False),
                                              (EXCERPT_DP4, True)],
                         ids=["gpt2s-s512", "bert-large-s512-dp4"])
def test_an_excerpt_partitions_as_trace_reduce_does(path, collective):
    trace = phase_reduce.read_trace(path, NAMES)
    got = phase_reduce.reduce_phases(trace, NAMES)
    mean = _as_trace_reduce_sees(trace)
    seconds = got["seconds"]
    assert got["named"] == {"flash": True, "dense": True}
    assert sum(seconds[p] for p in FLASH_PARTS) == pytest.approx(
        mean["flash_s"], rel=1e-9)
    assert sum(s for p, s in seconds.items() if p not in FLASH_PARTS) \
        == pytest.approx(mean["dense_s"], rel=1e-9)
    assert (mean["collective_s"] > 0) == collective
    assert seconds["other_kernel"] == 0.0
    assert all(seconds[p] > 0 for _, p in NAMES["flash_kernels"])


def test_the_four_chip_excerpt_shows_the_reduction_and_the_update():
    """Two of the dp4 cell's four devices, around one bucket: the pack, the
    all-reduce (a collective: in no phase), the unpack, and the inner
    update, which the all-reduce keeps out of the backward's fusions."""
    trace = phase_reduce.read_trace(EXCERPT_DP4, NAMES)
    assert len(trace["devices"]) == 2
    got = phase_reduce.reduce_phases(trace, NAMES)
    assert got["devices"] == 2
    for part in ("bucket_pack", "bucket_unpack", "optimizer_update"):
        assert got["seconds"][part] > 0, part
    assert got["seconds"]["unattributed"] == 0.0
    events = [e for evs in trace["devices"].values() for e in evs]
    reduce_ops = [e for e in events if "/hvd_reduce/psum" in e[4]]
    assert reduce_ops and all(
        trace_reduce.classify(e[0], e[3], NAMES) == "collective"
        for e in reduce_ops)
    alone = phase_reduce.reduce_phases(
        {"devices": {"d": reduce_ops}}, NAMES)["seconds"]
    assert not any(alone.values())


def test_no_flash_kernel_of_the_excerpt_is_counted_under_a_dense_phase(
        excerpt):
    kernels = {m for m, _ in NAMES["flash_kernels"]}
    for events in excerpt["devices"].values():
        flash = [e for e in events
                 if trace_reduce.classify(e[0], e[3], NAMES) == "flash"]
        assert flash
        assert all(any(k in trace_reduce.short_name(e[0]) for k in kernels)
                   for e in flash)
        only = phase_reduce.reduce_phases({"devices": {"d": flash}},
                                          NAMES)["seconds"]
        assert sum(s for p, s in only.items() if p not in FLASH_PARTS) == 0.0


# -- the seven per-layer metrics -------------------------------------------

@pytest.fixture()
def traced_root(tmp_path):
    """A checkout's root whose scratch holds the excerpt as a run's trace."""
    where = tmp_path / ".bench_scratch" / "cell" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    shutil.copy(EXCERPT, where / "host.xplane.pb")
    return str(tmp_path)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_metric_has_its_entry_and_its_file(metric):
    catalog = Catalog(ROOT)
    entry = next(m for m in catalog.index["per_layer"]
                 if m["name"] == metric)
    assert entry == {"name": metric, "unit": "ms/step", "better": "lower",
                     "source": "device_trace", "layer": entry["layer"],
                     "moves": "train_tokens_per_s"}
    assert callable(catalog.module("layer_metrics", metric).read)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
@pytest.mark.parametrize("record", [
    {}, {"trace": {}}, {"trace": {"steps": 0, "mean": {}}}],
    ids=["no-trace-key", "untraced", "no-steps"])
def test_a_new_metric_reads_nothing_from_an_untraced_record(metric, record):
    read = Catalog(ROOT).module("layer_metrics", metric).read
    assert read(dict(record)) is None


@pytest.mark.parametrize("phase", sorted(NEW_METRICS.values()))
def test_a_traced_record_without_a_file_reads_nothing(phase, tmp_path):
    record = {"trace": {"steps": 3, "mean": {}}}
    assert phase_reduce.per_step_ms(record, phase, root=str(tmp_path)) is None


def test_phases_reads_the_newest_trace_once_and_says_so(traced_root, capsys):
    record = {"trace": {"steps": 2, "mean": {}}}
    first = phase_reduce.per_step_ms(record, "flash_fwd", root=traced_root)
    notes = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [n["note"] for n in notes] == ["phases"]
    assert set(notes[0]["ms_a_step"]) == set(NAMES["phases"])
    assert notes[0]["ms_a_step"]["flash_fwd"] == first > 0
    assert "unattributed" in notes[0]["ms_a_step"]
    assert notes[0]["op_name_from"] == ["tf_op", "Hlo Proto"]
    assert notes[0]["named_through_hlo_ms"] > 0
    for phase in NEW_METRICS.values():
        assert phase_reduce.per_step_ms(record, phase, root=traced_root) \
            is not None
    assert capsys.readouterr().out == ""            # one note a run
    halved = phase_reduce.per_step_ms({"trace": {"steps": 4, "mean": {}}},
                                      "flash_fwd", root=traced_root)
    assert halved == pytest.approx(first / 2)
