"""From a profiler trace to per-device times.

Two steps, so that the arithmetic can be tested without a chip:

``load_xplane(path)`` reads the ``.xplane.pb`` the JAX profiler wrote
(``jax.profiler.ProfileData``, nothing but JAX) and keeps, as plain lists,
the events on each device's op line and the benchmark's own host spans.

``reduce_trace(trace, steps)`` is interval arithmetic on those lists. Busy
time is the UNION of the intervals in which an operation ran — durations
summed would count nested or overlapping events twice (the repo's only
banked reduction reported a busy share of 2.8 that way).

Device events fall into three classes by the name lists of
``hlo_counts.load_names``: collective, flash kernel (a Mosaic call that
carries the name of one of ``flash_kernels``), and every other operation
("dense"), XLA's own or any other Mosaic call. Per device:

    flash      = union(flash events)
    dense      = union(other events) - flash
    collective = union(collective events)
    exposed    = collective - union(other events) - flash
    busy       = union(all events) = dense + flash + exposed
    idle       = window - busy

so dense + flash + exposed + idle is the window, exactly. The window is
the span from the first device event's start to the last one's end, over
all devices.
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]


# -- interval arithmetic (nanoseconds, half-open) -------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(disjoint: Sequence[Interval]) -> float:
    return float(sum(end - start for start, end in disjoint))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a - b`` for two sorted disjoint lists."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# -- reading the profiler's file ------------------------------------------

def load_xplane(path: str, names: dict) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns, text], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``. ``text`` joins the
    event's own string stats: empty on TPU v5 lite with JAX 0.9.0, where
    the ``op_name`` is a stat of the event's metadata
    (``phase_reduce.read_metadata``) and an event's name is its whole HLO
    instruction, a Pallas kernel's ``name=`` in it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans = set(names["host_spans"])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(names["device_plane_prefix"]):
            events = devices.setdefault(plane.name, [])
            for line in plane.lines:
                ops = line.name in names["device_op_lines"]
                if not ops and line.name not in names["device_async_lines"]:
                    continue
                for ev in line.events:
                    text = " ".join(str(v) for _, v in ev.stats
                                    if isinstance(v, str))
                    # An async line shows each start..done span beside
                    # the op line's sequence; only a collective's span
                    # says something the op line does not.
                    if ops or classify(ev.name, text, names) == "collective":
                        events.append([ev.name, float(ev.start_ns),
                                       float(ev.duration_ns), text])
        elif plane.name.startswith(names["host_plane_prefix"]):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host": host}


# -- the reduction -----------------------------------------------------------

def short_name(name: str) -> str:
    """The chip's trace names a device event by its whole HLO
    instruction, ``%fusion.12 = f32[...] fusion(...), kind=...``: the
    part before `` = `` is the instruction's name."""
    return name.split(" = ", 1)[0]


def classify(name: str, text: str, names: dict) -> str:
    """``"collective"``, ``"flash"`` or ``"dense"``. A Mosaic call is a
    flash kernel only where its own name (never an operand's) or the
    text of its own stats holds the name of one of ``flash_kernels``: the
    attention kernels by what they are. Every other Mosaic call (a
    wire's, a recurrence's, a grouped matmul's, XLA's own) is dense work
    of the layer that owns it (``phase_reduce``), so that ``flash_ms``
    and ``flash_roofline_pct`` are the attention kernels' alone whatever
    kernels a later PR brings. A cell whose attention kernel carries
    another name lists it in a file of names (``hlo_counts.load_names``)."""
    bare = short_name(name).lstrip("%")
    if any(bare == op or bare.startswith(op + ".")
           or bare.startswith(op + "-start") or bare.startswith(op + "-done")
           for op in names["collective_opcodes"]):
        return "collective"
    if is_mosaic_call(name, text, names) and any(
            kernel in bare or kernel in text
            for kernel, _ in names["flash_kernels"]):
        return "flash"
    return "dense"


def is_mosaic_call(name: str, text: str, names: dict) -> bool:
    """Whether a device event is a Pallas (Mosaic) kernel's call, the
    repo's own or one XLA itself lowers to (``ragged-dot``)."""
    marker = names["mosaic_call_marker"]
    return marker in name or marker in text


MIN_GAP_NS = 2000.0   # shorter idle gaps are counted together, unattributed


def reduce_trace(trace: dict, steps: int, names: dict) -> dict:
    """Per-device class times (seconds), their means over devices, the
    ten operations that took most time and the idle gaps of the first
    device by what the host was doing. ``steps`` is the number of steps
    that ran inside the trace. Returns ``{}`` for a trace with no device
    event: there is nothing to read."""
    # One pass: each device's intervals by class, and the time of each
    # instruction and of each group of like-named ones (``%fusion.*``).
    by_device, groups, singles = {}, {}, {}
    for plane, events in sorted(trace["devices"].items()):
        classes = {"collective": [], "flash": [], "dense": []}
        for name, start, dur, text in events:
            classes[classify(name, text, names)].append((start, start + dur))
            short = short_name(name)
            stem, _, number = short.rpartition(".")
            group = stem + ".*" if number.isdigit() else short
            singles[short] = singles.get(short, 0.0) + dur / 1e9
            groups[group] = groups.get(group, 0.0) + dur / 1e9
        if events:
            by_device[plane] = {k: union(v) for k, v in classes.items()}
    if not by_device:
        return {}

    starts = [iv[0][0] for d in by_device.values() for iv in d.values() if iv]
    ends = [max(e for _, e in iv) for d in by_device.values()
            for iv in d.values() if iv]
    window = (min(starts), max(ends))
    window_ns = window[1] - window[0]

    per_device = {}
    for plane, d in by_device.items():
        flash = d["flash"]
        dense = subtract(d["dense"], flash)
        exposed = subtract(subtract(d["collective"], d["dense"]), flash)
        busy = union(flash + d["dense"] + d["collective"])
        per_device[plane] = {
            "busy_s": total(busy) / 1e9,
            "dense_s": total(dense) / 1e9,
            "flash_s": total(flash) / 1e9,
            "collective_s": total(d["collective"]) / 1e9,
            "exposed_collective_s": total(exposed) / 1e9,
            "idle_share": 1.0 - total(busy) / window_ns,
        }

    n = len(per_device)
    mean = {k: sum(d[k] for d in per_device.values()) / n
            for k in next(iter(per_device.values()))}
    worst = max(per_device, key=lambda p: per_device[p]["idle_share"])

    def top(seconds):
        return [[k, v / n] for k, v in sorted(
            seconds.items(), key=lambda kv: -kv[1])[:5]]

    device_ops = top(groups) + top(singles)

    first = sorted(by_device)[0]
    busy = union(by_device[first]["flash"] + by_device[first]["dense"]
                 + by_device[first]["collective"])
    gap_seconds = {}
    for gap in subtract([window], busy):
        if gap[1] - gap[0] < MIN_GAP_NS:
            label = "gaps under %g us" % (MIN_GAP_NS / 1e3)
        else:
            label, best = "host.other", 0.0
            for name, start, dur in trace["host"]:
                o = overlap(gap, (start, start + dur))
                if o > best:
                    label, best = name, o
        gap_seconds[label] = gap_seconds.get(label, 0.0) \
            + (gap[1] - gap[0]) / 1e9
    idle_gaps = sorted(gap_seconds.items(), key=lambda kv: -kv[1])[:10]

    return {"devices": n, "steps": int(steps), "window_s": window_ns / 1e9,
            "mean": mean, "per_device": per_device,
            "worst_idle_device": worst,
            "breakdown": {"device_ops": device_ops,
                          "idle_gaps": [[k, v] for k, v in idle_gaps]}}


def per_step_ms(record: dict, key: str):
    """``record["trace"]["mean"][key]`` in milliseconds a step, or ``None``
    where the run has no reduced trace: what the readers of the trace's
    class times (``layer_metrics/*_ms.py``) return."""
    trace = record.get("trace")
    if not trace or not trace["steps"]:
        return None
    return 1e3 * trace["mean"][key] / trace["steps"]
