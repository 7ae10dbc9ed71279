"""From a profiler trace (``*.xplane.pb``) to device busy time, idle share,
per-operation totals and idle gaps labelled by what the host was doing.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  A
device is a plane named ``/device:TPU:<n>``.  On such a plane the line
``XLA Ops`` holds one event for every operation the chip ran and the line
``XLA Modules`` one for every execution of a compiled program.  Busy time is
the union of the ``XLA Ops`` intervals (of ``XLA Modules`` where a trace has
no ops line), cut to the traced window; the idle share is one minus busy
over the window.

The window and the clock.  The harness wraps the region it measures in a
``jax.profiler.TraceAnnotation`` named ``WINDOW_NAME`` that carries
``mono_ns``, the host's monotonic clock at its start.  The annotation's own
start and length on the trace clock are the window, and the pair puts any
span timed on the monotonic clock (the harness's own, and the program's
telemetry spans, which record ``t_mono``) on the trace clock.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_NAME = "perfbench:window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SMALL_GAP_NS = 100_000  # gaps under 0.1 ms are summed under one label

Interval = Tuple[int, int]


def merge_intervals(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of half-open intervals as a sorted list of disjoint ones."""
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip_intervals(intervals: Sequence[Interval], lo: int, hi: int
                   ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps_between(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of ``[lo, hi)`` given merged busy intervals."""
    gaps = []
    cursor = lo
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def label_gap(gap: Interval, spans: Sequence[Tuple[str, int, int]]) -> str:
    """Name of the host span a gap belongs to: of the spans that cover at
    least half of it, the shortest, which is the most specific; if none
    covers half, the one that covers most.  ``spans`` are
    ``(name, start_ns, end_ns)`` on the trace clock."""
    half = (gap[1] - gap[0]) / 2
    specific, widest = None, None
    for name, start, end in spans:
        cover = min(end, gap[1]) - max(start, gap[0])
        if cover <= 0:
            continue
        if cover >= half and (specific is None or end - start < specific[0]):
            specific = (end - start, name)
        if widest is None or cover > widest[0]:
            widest = (cover, name)
    if specific is not None:
        return specific[1]
    return widest[1] if widest is not None else "(no host span)"


def attribute_gaps(gaps: Sequence[Interval],
                   spans: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Idle nanoseconds by host-span label."""
    out: Dict[str, int] = {}
    for gap in gaps:
        length = gap[1] - gap[0]
        label = ("(gaps under 0.1 ms)" if length < SMALL_GAP_NS
                 else label_gap(gap, spans))
        out[label] = out.get(label, 0) + length
    return out


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO text (``%fusion.12 =
    bf16[...] fusion(...)``); its name is what stands before the ``=``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Tuple[str, int, int]]:
    return [(short_name(ev.name), int(ev.start_ns),
             int(ev.start_ns + ev.duration_ns)) for ev in line.events]


def find_window(profile) -> Optional[Dict]:
    """The ``WINDOW_NAME`` annotation: its interval on the trace clock and
    the monotonic-clock time of its start."""
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_NAME:
                    stats = dict(ev.stats)
                    return {
                        "start_ns": int(ev.start_ns),
                        "end_ns": int(ev.start_ns + ev.duration_ns),
                        "mono_ns": int(stats["mono_ns"]),
                    }
    return None


def _cpu_stand_in(profile) -> Dict[str, Dict]:
    """For the CPU rehearsal only: the host plane's XLA operations as one
    stand-in device, so the code after the plane lookup runs without a chip.
    Nothing read from it is a device number."""
    ops = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            ops += [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events if "hlo_op" in dict(ev.stats)]
    return {"cpu-rehearsal": {"ops": ops, "modules": []}} if ops else {}


def reduce_profile(profile, host_spans: Sequence[Dict] = (),
                   rehearsal: bool = False) -> Dict:
    """The reduction.  ``host_spans`` are dicts with ``name``, ``t_mono``
    (seconds, monotonic clock) and ``dur_s``."""
    window = find_window(profile)
    devices: Dict[str, Dict] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        modules = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        devices[plane.name] = {"ops": ops, "modules": modules}
    if not devices and rehearsal:
        devices = _cpu_stand_in(profile)
    if not devices:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    if window is None:
        lo = min(s for d in devices.values() for _, s, _ in d["ops"] + d["modules"])
        hi = max(e for d in devices.values() for _, _, e in d["ops"] + d["modules"])
        offset = None
    else:
        lo, hi = window["start_ns"], window["end_ns"]
        offset = window["start_ns"] - window["mono_ns"]
    spans = []
    if offset is not None:
        for sp in host_spans:
            start = int(sp["t_mono"] * 1e9) + offset
            spans.append((sp["name"], start, start + int(sp["dur_s"] * 1e9)))

    per_device = {}
    for name, dev in devices.items():
        source = dev["ops"] or dev["modules"]
        busy = clip_intervals(
            merge_intervals((s, e) for _, s, e in source), lo, hi)
        busy_ns = sum(e - s for s, e in busy)
        op_ns: Dict[str, int] = {}
        for op, s, e in dev["ops"]:
            if e > lo and s < hi:
                op_ns[op] = op_ns.get(op, 0) + (min(e, hi) - max(s, lo))
        module_runs: Dict[str, List[int]] = {}
        for mod, s, e in dev["modules"]:
            if s >= lo and e <= hi:
                module_runs.setdefault(mod, []).append(e - s)
        gaps = gaps_between(busy, lo, hi)
        per_device[name] = {
            "busy_s": busy_ns / 1e9,
            "idle_share": 1.0 - busy_ns / max(1, hi - lo),
            "op_s": {k: v / 1e9 for k, v in op_ns.items()},
            "module_runs_s": {k: [d / 1e9 for d in v]
                              for k, v in module_runs.items()},
            "gap_s": {k: v / 1e9
                      for k, v in attribute_gaps(gaps, spans).items()},
            "longest_gap_s": max((e - s for s, e in gaps), default=0) / 1e9,
        }

    n = len(per_device)
    def mean_of(key):
        total: Dict[str, float] = {}
        for dev in per_device.values():
            for k, v in dev[key].items():
                total[k] = total.get(k, 0.0) + v / n
        return sorted(total.items(), key=lambda kv: -kv[1])

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "idle_share_mean": sum(d["idle_share"] for d in per_device.values()) / n,
        "idle_share_worst": max(d["idle_share"] for d in per_device.values()),
        "device_ops": mean_of("op_s"),
        "idle_gaps": mean_of("gap_s"),
        "devices": per_device,
    }


def reduce_file(path: str, host_spans: Sequence[Dict] = (),
                rehearsal: bool = False) -> Dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), host_spans, rehearsal)


def describe_file(path: str, events_per_line: int = 3) -> List[str]:
    """The planes and lines of a trace, a few events each: for reading one
    trace by hand before trusting the reduction on it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} line(s)")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} event(s)")
            for ev in events[:events_per_line]:
                out.append(
                    f"    {ev.name!r} start_ns={ev.start_ns} "
                    f"dur_ns={ev.duration_ns} stats={dict(ev.stats)}"
                )
    return out


def module_runs(reduced: Dict, pattern: str) -> List[float]:
    """Durations (seconds) of every execution, on the first device, of the
    programs whose name contains ``pattern``."""
    first = reduced["devices"][sorted(reduced["devices"])[0]]
    runs: List[float] = []
    for name, durations in first["module_runs_s"].items():
        if pattern in name:
            runs.extend(durations)
    return runs
