"""Reduce a JAX profiler trace of the device to the benchmark's numbers.

Two steps, kept apart so the second can be checked on a recorded trace:

``load``    reads the ``.xplane.pb`` the profiler wrote and keeps the
            device planes (``/device:TPU:<n>``): per plane, per line, the
            events as ``[name, start_ns, duration_ns]``.
``reduce``  turns those events into busy time (the union of the
            intervals in which an operation ran), device time per program
            (the ``XLA Modules`` line, names without their ``(id)``
            suffix), the operations that took most self time (``XLA
            Ops``, nested operations such as a loop's body subtracted from
            the loop, each named ``<program>:<instruction>``) and the
            longest idle gaps, each named by the program that ran before
            it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under "
                                f"{trace_dir}")
    return paths[0]


def load(path: str) -> dict:
    """{"planes": {plane: {line: [[name, start_ns, dur_ns], ...]}}} of
    the device planes of one profiler trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict[str, dict[str, list]] = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines[line.name] = [[ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)]
                                for ev in line.events]
    return {"planes": planes}


def program_name(name: str) -> str:
    return _SUFFIX.sub("", name.strip())


def op_name(name: str) -> str:
    """An operation's HLO instruction name (the trace names it by the
    whole instruction text)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(ops: list) -> list[tuple[float, float, str]]:
    """(start, self seconds, name) of each operation: its duration less
    that of the operations nested in it."""
    evs = sorted((s, -d, n) for n, s, d in ops)
    out: list[list] = []
    stack: list[int] = []
    for s, negd, n in evs:
        e = s - negd
        while stack and out[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(e, out[stack[-1]][3]) - s
        out.append([s, -negd, n, e])
        stack.append(len(out) - 1)
    return [(s, d * 1e-9, n) for s, d, n, _ in out]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy_line(lines: dict) -> list:
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def reduce(events: dict) -> dict:
    """Busy seconds (mean over the device planes), device seconds and
    counts per program, the top operations and the longest idle gaps."""
    planes = events["planes"]
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy = []
    programs: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    ops: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, str]] = []
    for lines in planes.values():
        spans = union([(s, s + d) for _, s, d in _busy_line(lines)])
        busy.append(sum(e - s for s, e in spans) * 1e-9)
        mods = sorted((s, s + d, program_name(n))
                      for n, s, d in lines.get(MODULES_LINE, []))
        for s, e, n in mods:
            programs[n][0] += (e - s) * 1e-9
            programs[n][1] += 1
        starts = [m[0] for m in mods]
        for s, d, n in _self_times(lines.get(OPS_LINE, [])):
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            ops[f"{prog}:{op_name(n)}"] += d
        j = 0
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            while j + 1 < len(mods) and mods[j + 1][0] < e0:
                j += 1
            before = mods[j][2] if mods and mods[j][0] < e0 else "start"
            gaps.append(((s1 - e0) * 1e-9, f"after {before}"))
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(busy) / len(busy),
        "programs": {n: {"s": v[0], "n": v[1]} for n, v in programs.items()},
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[n, s] for s, n in gaps[:TOP]],
        "lines": sorted({ln for lines in planes.values() for ln in lines}),
    }


def program_seconds(reduced: dict, fragment: str) -> tuple[float, int]:
    """Device seconds and count of the programs whose name holds
    ``fragment``."""
    s, n = 0.0, 0
    for name, v in reduced["programs"].items():
        if fragment in name:
            s += v["s"]
            n += v["n"]
    return s, n
