"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle,
device time per compiled program, the operations that took most time, the
longest idle gaps with what the host was doing in them, and which program's
runs end the host's waits for a result.

Reads the trace with ``jax.profiler.ProfileData`` alone. Importing this
module loads no TPU library and describes no topology. Planes named
``/device:TPU:<n>`` are devices; on each, the line ``XLA Ops`` holds one
event per executed operation and ``XLA Modules`` one per run of a compiled
program. Host threads are the lines of ``/host:CPU``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE, HOST_PLANE = "XLA Ops", "XLA Modules", "/host:CPU"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Any:
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line: Any) -> list[tuple[float, float, str]]:
    """(start_s, end_s, name), sorted by start."""
    out = [
        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
        for ev in line.events
    ]
    out.sort()
    return out


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


_OPCODE = re.compile(r"[\}\)] ([a-z][\w\-]*)\(")
CONTAINERS = ("while", "conditional", "call")  # their time is their children's


def short_op(event_name: str) -> tuple[str, str]:
    """An ``XLA Ops`` event is named by its whole HLO instruction;
    -> (``<name> <result shape> <opcode>``, opcode). ``%copy.2 = bf16[8,128]{1,0} copy(...)``
    becomes ``copy.2 bf16[8,128] copy``; a tuple-shaped result is left out."""
    name, sep, rest = event_name.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name[:120], ""
    found = _OPCODE.search(rest)
    opcode = found.group(1) if found else ""
    shape = "" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    return " ".join(x for x in (name, shape, opcode) if x)[:120], opcode


def _host_activity(host_lines: list[tuple[str, list]], start: float, end: float) -> str:
    """The host event that covers most of ``[start, end]``, as
    ``<thread>:<event>``; ``unattributed`` if none overlaps."""
    best, best_cover = "unattributed", 0.0
    for thread, events in host_lines:
        for ev_start, ev_end, name in events:
            if ev_start >= end:
                break
            cover = min(end, ev_end) - max(start, ev_start)
            if cover > best_cover:
                best, best_cover = f"{thread}:{name}", cover
    return best


WAIT_SPAN = ".fetch_wait"  # the program's spans in which the host waits for a result
BLOCKED_S = 0.5e-3  # a shorter wait found its result there: it waited for no run
HELPER_S = 1e-3  # a shorter run is a slice, a convert or a row write, not what a wait is for
SKEW_S = 0.2e-3  # host and device clocks of one trace agree to within this
RETURN_S = 10e-3  # a result is back within this of its run's end; later, something else held the wait


def released_by(host_lines: list[tuple[str, list]], modules: list) -> dict[str, dict[str, int]]:
    """Wait span name -> program -> how many of those waits a run of that
    program ended. The device runs its stream in order and a result is
    fetched as its program's run ends, so a blocked wait is released by the
    last run (helpers left out) to end before the wait does, provided it
    ended inside the wait and no more than ``RETURN_S`` before its end (a
    wait held by a stalled transfer ends long after its run, and after
    other programs' runs); a wait that no run ended is counted nowhere.
    This is what tells the pool's jitted lambda from the solo fallback's:
    neither has a name, each ends the waits of its own span."""
    runs = sorted((end, name) for start, end, name in modules if end - start >= HELPER_S)
    ends = [end for end, _ in runs]
    out: dict[str, dict[str, int]] = {}
    for _, events in host_lines:
        for start, end, name in events:
            if not name.endswith(WAIT_SPAN) or end - start < BLOCKED_S:
                continue
            at = bisect.bisect_right(ends, end + SKEW_S) - 1
            if at >= 0 and ends[at] >= max(start, end - RETURN_S):
                by = out.setdefault(name, {})
                by[runs[at][1]] = by.get(runs[at][1], 0) + 1
    return out


def reduce_trace(data: Any, top: int = 10) -> dict[str, Any]:
    """-> ``window_s`` (first device event to last, over all devices),
    ``busy_s`` and ``idle_share`` (averaged over devices), ``programs``
    (module name with its id -> {seconds, runs}, device 0), ``released``
    (``released_by`` on device 0), ``device_ops`` and ``idle_gaps`` (at most
    ``top`` ``[name, seconds]`` pairs each), ``n_devices``."""
    devices = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    per_device = []
    for plane in sorted(devices, key=lambda p: p.name):
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        per_device.append((_events(lines[OPS_LINE]),
                           _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []))
    if not per_device or not any(ops for ops, _ in per_device):
        raise ValueError("no operation ran on a device inside the trace")
    t0 = min(ops[0][0] for ops, _ in per_device if ops)
    t1 = max(max(e[1] for e in ops) for ops, _ in per_device if ops)
    busy = []
    for ops, _ in per_device:
        busy.append(sum(b - a for a, b in union((s, e) for s, e, _ in ops)))
    ops0, modules0 = per_device[0]
    programs: dict[str, dict[str, float]] = {}
    for start, end, name in modules0:
        slot = programs.setdefault(name, {"seconds": 0.0, "runs": 0})
        slot["seconds"] += end - start
        slot["runs"] += 1
    by_op: dict[str, float] = {}
    for start, end, name in ops0:
        label, opcode = short_op(name)
        if opcode not in CONTAINERS:
            by_op[label] = by_op.get(label, 0.0) + (end - start)
    host = next((p for p in data.planes if p.name == HOST_PLANE), None)
    host_lines = [(line.name, _events(line)) for line in host.lines] if host else []
    merged = union((s, e) for s, e, _ in ops0)
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    by_gap: dict[str, float] = {}
    for length, start, end in gaps[: 20 * top]:
        what = _host_activity(host_lines, start, end)
        by_gap[what] = by_gap.get(what, 0.0) + length
    window = t1 - t0
    busy_s = sum(busy) / len(busy)
    return {
        "window_s": window, "busy_s": busy_s, "n_devices": len(per_device),
        "idle_share": 1.0 - busy_s / window if window > 0 else None,
        "programs": programs, "released": released_by(host_lines, modules0),
        "device_ops": sorted(([n, s] for n, s in by_op.items()), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in by_gap.items()), key=lambda x: -x[1])[:top],
    }


def describe(data: Any, per_line: int = 8) -> list[str]:
    """Planes, lines and their commonest event names: what a person looks
    at before writing a reader against a new trace."""
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            names: dict[str, list[float]] = {}
            for ev in line.events:
                slot = names.setdefault(ev.name, [0, 0.0])
                slot[0] += 1
                slot[1] += ev.duration_ns * 1e-9
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:per_line]
            out.append(f"  line {line.name!r}: {sum(v[0] for v in names.values())} events")
            out.extend(f"    {n[:90]!r} x{int(c)} {s:.4f}s" for n, (c, s) in top)
    return out
