"""From a traced job's profile and the program's own map of its operations
(``music_analyst_tpu.profiling.op_scopes``) to device time by part of the
step program.

The trace names device operations as XLA does (``fusion.315``); the map says
under which ``jax.named_scope`` path each instruction of each compiled
program was traced.  Three rules make the sums exact:

* **By execution.**  Every ``XLA Ops`` event belongs to the ``XLA Modules``
  execution that contains its start on the same plane: two programs both
  have a ``fusion.1`` (``trace_reduce``'s ``op_s`` merges them by name).
* **Self time.**  At every instant the device's time goes to the innermost
  operation running: a ``while`` is one event that contains its body's, and
  its self time is its length less what runs inside it.  Cut to the traced
  window, the self times of a device add up to ``trace_reduce``'s
  ``busy_s``.
* **One map a traced program.**  A function compiled at several shapes has
  several maps under one module name.  A traced program (module name and
  the id the trace prints behind it) takes the map that holds all of its
  operations' names (the most of them, where none holds all); where
  several do and put a name into different parts its time goes to
  ``(ambiguous)``, a name none of them holds goes to ``(unmapped)``, and
  so does the time of a program no map names.

A part is a row of ``scope_parts.json``: for each step program an ordered
list of ``[part, scope components that select it]``, first match wins,
``(other)`` where none does.  Components are compared with the transforms
around them taken off (``vmap(LlamaModel)`` is ``LlamaModel``).

Reads the trace with ``jax.profiler.ProfileData`` alone.  Nothing here runs
inside a timed window: the harness calls it after the traced job, through
the readers of ``layer_metrics/``.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import common
import trace_reduce

PARTS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "scope_parts.json")
UNMAPPED, AMBIGUOUS, OTHER = "(unmapped)", "(ambiguous)", "(other)"
NO_MODULE = "(no module)"
TOP_OPS = 20

_MODULE_ID = re.compile(r"\(\d+\)$")
_TRANSFORM = re.compile(r"^\w+\((.*)\)$")

Event = Tuple[str, int, int]


def module_name(event_name: str) -> str:
    """``jit__score_labels(1469628866813)`` -> ``jit__score_labels``."""
    return _MODULE_ID.sub("", event_name)


def components(op_name: str) -> List[str]:
    """The scope components of an ``op_name``, each with the transforms
    around it taken off: ``jit(f)/labels/vmap(LlamaModel)/mla/exp`` ->
    ``f, labels, LlamaModel, mla, exp``."""
    out = []
    for component in op_name.split("/"):
        while True:
            inner = _TRANSFORM.match(component)
            if not inner:
                break
            component = inner.group(1)
        out.append(component)
    return out


def part_of(op_name: str, table: Sequence[Sequence[Any]]) -> str:
    """The first row of ``table`` all of whose components the path has."""
    have = set(components(op_name))
    for part, needed in table:
        if have.issuperset(needed):
            return part
    return OTHER


def self_times(intervals: Sequence[Tuple[int, int]]) -> List[int]:
    """For each ``(start, end)``: the time within it in which no interval
    that started later (a nested one, where they nest) is running.  The
    results add up to the length of the union, whatever the overlaps."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    out = [0] * len(intervals)
    stack: List[int] = []  # running intervals, the latest started on top
    cursor = 0

    def run_until(limit: int) -> None:
        nonlocal cursor
        while stack and cursor < limit:
            top = stack[-1]
            end = intervals[top][1]
            if end <= cursor:
                stack.pop()
                continue
            stop = min(end, limit)
            out[top] += stop - cursor
            cursor = stop

    for i in order:
        start, end = intervals[i]
        if end <= start:
            continue
        run_until(start)
        cursor = max(cursor, start)
        stack.append(i)
    run_until(max((e for _, e in intervals), default=0))
    return out


def _execution_of(modules: Sequence[Event], starts: Sequence[int],
                  at: int) -> str:
    """The execution (its event's whole name) that is running at ``at``."""
    i = bisect.bisect_right(starts, at) - 1
    if i >= 0 and at < modules[i][2]:
        return modules[i][0]
    return NO_MODULE


def device_self_ns(ops: Sequence[Event], modules: Sequence[Event],
                   lo: int, hi: int) -> Dict[str, Dict[str, int]]:
    """``{execution name: {operation: self nanoseconds in [lo, hi)}}`` of
    one device."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    clipped = [(max(s, lo), min(e, hi)) for _, s, e in ops]
    out: Dict[str, Dict[str, int]] = {}
    for (name, start, _), own in zip(ops, self_times(clipped)):
        if own:
            by_op = out.setdefault(_execution_of(modules, starts, start), {})
            by_op[name] = by_op.get(name, 0) + own
    return out


def _paths_of(names: Iterable[str], maps: Sequence[Dict[str, Any]]
              ) -> Dict[str, List[str]]:
    """For each operation of one traced program the ``op_name`` paths given
    it by the maps that hold the most of the program's names (no path: none
    of them holds the name)."""
    names = list(names)
    held = [sum(n in ops for n in names) for ops in maps]
    best = [ops for ops, n in zip(maps, held) if n == max(held)]  # [] of []
    return {name: [ops[name][0] for ops in best if name in ops]
            for name in names}


def reduce_devices(devices: Dict[str, Dict[str, List[Event]]], lo: int,
                   hi: int, scopes: Sequence[Dict[str, Any]],
                   parts: Dict[str, Sequence[Sequence[Any]]]) -> Dict:
    """The reduction proper.  ``devices``: ``{plane: {"ops": events,
    "modules": events}}``; seconds are means over the devices, as
    ``trace_reduce``'s are."""
    maps: Dict[str, List[Dict[str, Any]]] = {}
    for entry in scopes:
        if entry.get("ops") is not None:
            maps.setdefault(entry["module"], []).append(entry["ops"])
    share = 1e-9 / len(devices)
    modules: Dict[str, Dict[str, Any]] = {}
    operations: Dict[Tuple[str, str, str, str], float] = {}
    for device in devices.values():
        by_execution = device_self_ns(device["ops"], device["modules"],
                                      lo, hi)
        runs: Dict[str, int] = {}
        for name, start, end in device["modules"]:
            if end > lo and start < hi:
                runs[name] = runs.get(name, 0) + 1
        for execution, by_op in by_execution.items():
            module = module_name(execution)
            table = parts.get(module, ())
            paths = _paths_of(by_op, maps.get(module, ()))
            summed = modules.setdefault(
                module, {"seconds": 0.0, "executions": 0.0, "parts": {}})
            summed["executions"] += runs.get(execution, 0) / len(devices)
            for op, own in by_op.items():
                found = {part_of(p, table) for p in paths[op]}
                part = (UNMAPPED if not found else
                        found.pop() if len(found) == 1 else AMBIGUOUS)
                seconds = own * share
                summed["seconds"] += seconds
                summed["parts"][part] = (
                    summed["parts"].get(part, 0.0) + seconds)
                key = (module, op, part, paths[op][0] if paths[op] else "")
                operations[key] = operations.get(key, 0.0) + seconds
    lost = sum(m["parts"].get(UNMAPPED, 0.0) + m["parts"].get(AMBIGUOUS, 0.0)
               for m in modules.values())
    longest = sorted(operations.items(), key=lambda kv: -kv[1])[:TOP_OPS]
    return {
        "busy_s": sum(m["seconds"] for m in modules.values()),
        "unmapped_s": lost,
        "modules": modules,
        "top_ops": [{"module": module, "op": op, "part": part,
                     "seconds": seconds, "op_name": path}
                    for (module, op, part, path), seconds in longest],
    }


def _events(line) -> List[Event]:
    return [(trace_reduce.short_name(ev.name), int(ev.start_ns),
             int(ev.start_ns + ev.duration_ns)) for ev in line.events]


def reduce_profile(profile, scopes: Sequence[Dict[str, Any]],
                   parts: Dict[str, Sequence[Sequence[Any]]]
                   ) -> Optional[Dict]:
    """``reduce_devices`` of a profile's ``/device:TPU:<n>`` planes over the
    window ``trace_reduce`` uses; ``None`` where no plane has an ``XLA Ops``
    line (a CPU rehearsal)."""
    devices = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: _events(line) for line in plane.lines
                 if line.name in (trace_reduce.OPS_LINE,
                                  trace_reduce.MODULES_LINE)}
        if trace_reduce.OPS_LINE in lines:
            devices[plane.name] = {
                "ops": lines[trace_reduce.OPS_LINE],
                "modules": lines.get(trace_reduce.MODULES_LINE, []),
            }
    events = [e for d in devices.values() for e in d["ops"] + d["modules"]]
    if not events:
        return None
    window = trace_reduce.find_window(profile)
    if window is None:
        lo, hi = min(s for _, s, _ in events), max(e for _, _, e in events)
    else:
        lo, hi = window["start_ns"], window["end_ns"]
    return reduce_devices(devices, lo, hi, scopes, parts)


def load_parts() -> Dict[str, Sequence[Sequence[Any]]]:
    with open(PARTS_PATH, encoding="utf-8") as fh:
        return json.load(fh)["programs"]


def traced_run_dir(artifacts: Dict[str, Any]) -> Optional[str]:
    """``<cell's out dir>/run`` of a run that traced its first job, found
    from that job's first part (``.../run/job0/<part>``)."""
    jobs = artifacts.get("jobs") or ()
    if not artifacts.get("trace") or not jobs or not jobs[0].get("parts"):
        return None
    part = next(iter(jobs[0]["parts"].values()))
    return os.path.normpath(os.path.join(part["dir"], os.pardir, os.pardir))


def for_artifacts(artifacts: Dict[str, Any]) -> Optional[Dict]:
    """The reduction of a run's traced job, made once a run (kept on
    ``artifacts``) and written as ``scope_reduced.json`` beside
    ``trace_reduced.json``; ``None`` where there is no trace, the program
    has no ``op_scopes`` or the trace no ``XLA Ops`` line."""
    if "scope_reduced" not in artifacts:
        artifacts["scope_reduced"] = _reduce_run(traced_run_dir(artifacts))
    return artifacts["scope_reduced"]


def _reduce_run(run_dir: Optional[str]) -> Optional[Dict]:
    found = run_dir and sorted(glob.glob(os.path.join(
        run_dir, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    try:
        from music_analyst_tpu.profiling.compile import op_scopes
    except ImportError:  # a program older than the map
        return None
    from jax.profiler import ProfileData

    t0 = time.monotonic()
    scopes = op_scopes()
    t1 = time.monotonic()
    reduced = reduce_profile(
        ProfileData.from_file(found[-1]), scopes, load_parts())
    if reduced is None:
        return None
    # what the instrument costs when it is on, outside every timed window
    reduced["cost"] = {"op_scopes_s": t1 - t0,
                       "reduce_s": time.monotonic() - t1}
    with open(os.path.join(os.path.dirname(run_dir), "scope_reduced.json"),
              "w", encoding="utf-8") as fh:
        json.dump(reduced, fh)
    common.note(scope_reduce=reduced["cost"], busy_s=reduced["busy_s"],
                unmapped_s=reduced["unmapped_s"])
    return reduced


def kind(part: str) -> str:
    """``prefill.kda.proj`` -> ``kda.proj``: a part less its phase."""
    return part.partition(".")[2]


def part_share(artifacts: Dict[str, Any], modules: Sequence[str],
               chosen) -> Optional[float]:
    """Share (%) of the summed device time of ``modules`` (step programs,
    by module name) that the parts ``chosen(part)`` accepts take over the
    traced job; ``None`` where the reduction is or the programs' time is
    nothing."""
    reduced = for_artifacts(artifacts)
    if not reduced:
        return None
    total = taken = 0.0
    for module in modules:
        summed = reduced["modules"].get(module)
        if summed:
            total += summed["seconds"]
            taken += sum(seconds for part, seconds in summed["parts"].items()
                         if chosen(part))
    return 100.0 * taken / total if total else None
