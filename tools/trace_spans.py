"""Join the program's phase spans with the device's program runs in one
profiler trace, by hand, on a trace a benchmark run kept:

    BENCH_KEEP_TRACE=1 python -m benchmark.run --workload <cell> --seed 7 --seconds 51 --trace 1
    python tools/trace_spans.py benchmark/out/<cell>.7.1.trace [--ops 10] [--shape S] [--json]

The host plane (``/host:CPU``) holds the ``gofr.*`` annotations of
gofr_tpu/profiling.py, each tagged with its ``dispatch_id``; the device
plane's ``XLA Modules`` line holds one event per run of a compiled program.
Both are on one clock. For every dispatch whose issue span and fetch-wait
span lie in the trace this finds the dispatch's own run (the latest run of
its program that ended before the fetch returned and began after the issue
began: a fetch that blocked returns right behind its program), the wait
from the end of the issue to the start of the run, and the pooled decode
chunks the device finished in that wait. ``--ops`` also lists the device
operations that took most time, each with the scope (``jax.named_scope``
path) and the line of source the compiler recorded for it; ``--shape
bf16[32,6,8,2048,128]`` lists the operations that write a result of that
shape (the compiler's own copies carry no scope: their shape finds them).

Reads the trace with ``benchmark.trace_reduce`` and ``jax.profiler.ProfileData``
alone; nothing here imports a TPU library or tensorflow. The scope and the
source of an operation are statistics of its event *metadata*, which
``ProfileData`` does not hand out: ``op_metadata`` walks the file's protobuf
wire format for just those, and skips the events.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
from typing import Any, Iterator, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce as TR  # noqa: E402

PREFILL_PROGRAM = "jit__prefill_fn"
LAMBDA_PROGRAM = "jit__lambda"
# a fetch shorter than this did not wait for the device: its program may
# have ended long before, so the dispatch cannot be placed by it
BLOCKED_FETCH_S = 0.5e-3
# the span names of one dispatch kind: (issue, fetch wait)
KINDS = {
    "prefill": ("gofr.prefill.issue", "gofr.prefill.fetch_wait"),
    "decode_chunk": ("gofr.pool.issue", "gofr.pool.fetch_wait"),
    "decode_solo": ("gofr.solo.issue", "gofr.solo.fetch_wait"),
}
OP_STATS = ("tf_op", "source")  # the scope path, and file:line


def host_spans(data: Any) -> dict[str, dict[int, tuple[float, float]]]:
    """``gofr.*`` span name -> dispatch_id -> (start_s, end_s)."""
    out: dict[str, dict[int, tuple[float, float]]] = {}
    for plane in data.planes:
        if plane.name != TR.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("gofr."):
                    continue
                did = dict(ev.stats).get("dispatch_id")
                if did is not None:
                    out.setdefault(ev.name, {})[int(did)] = (
                        ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
    return out


def device_lines(data: Any) -> dict[str, Any]:
    devices = sorted((p for p in data.planes if TR.DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    return {line.name: line for line in devices[0].lines}


def program_runs(data: Any) -> dict[str, list[tuple[float, float]]]:
    """Program name -> its runs on device 0 as (start_s, end_s), by start."""
    runs: dict[str, list[tuple[float, float]]] = {}
    line = device_lines(data).get(TR.MODULES_LINE)
    for start, end, name in (TR._events(line) if line is not None else []):
        runs.setdefault(name, []).append((start, end))
    return runs


def programs_of(runs: dict[str, list]) -> dict[str, list[str]]:
    """Which programs a dispatch kind runs. The pooled chunk is taken as
    the ``jit__lambda`` program with most device time, the solo chunk as the
    one with the next most (the pool's token write is a lambda too, a tiny
    one). That is this tool's own rule, by hand and below the knee; the
    benchmark's readers left it in PR 32 (``benchmark/readers.pooled_program``
    takes the program whose runs end the pool's own waits), because past the
    knee the solo fallback's lambda has more of the trace than the pool's."""
    lambdas = sorted((n for n in runs if n.startswith(LAMBDA_PROGRAM)),
                     key=lambda n: -sum(e - s for s, e in runs[n]))
    return {
        "prefill": [n for n in runs if n.startswith(PREFILL_PROGRAM)],
        "decode_chunk": lambdas[:1],
        "decode_solo": lambdas[1:2],
    }


def join(data: Any, runs: dict[str, list[tuple[float, float]]]) -> dict[str, list[dict]]:
    """Per dispatch kind, one row per dispatch placed on the device."""
    spans, programs = host_spans(data), programs_of(runs)
    pool_runs = [r for n in programs["decode_chunk"] for r in runs[n]]
    out: dict[str, list[dict]] = {}
    for kind, (issue_name, fetch_name) in KINDS.items():
        mine = sorted(r for n in programs[kind] for r in runs[n])
        rows = []
        for did, (i0, i1) in sorted(spans.get(issue_name, {}).items()):
            fetch = spans.get(fetch_name, {}).get(did)
            if fetch is None or fetch[1] - fetch[0] < BLOCKED_FETCH_S:
                continue
            own = [r for r in mine if r[0] >= i0 and r[1] <= fetch[1]]
            if not own:
                continue
            r0, r1 = own[-1]
            ahead = [p for p in pool_runs if i0 < p[1] <= r0]
            rows.append({
                "dispatch_id": did, "issue_ms": (i1 - i0) * 1e3,
                "wait_ms": (r0 - i1) * 1e3, "run_ms": (r1 - r0) * 1e3,
                "after_run_ms": (fetch[1] - r1) * 1e3,
                "chunks_in_wait": len(ahead),
                "chunk_ms_in_wait": sum(min(e, r0) - max(s, i0) for s, e in ahead) * 1e3,
            })
        out[kind] = rows
    return out


def _fields(buf: memoryview) -> Iterator[tuple[int, int, Any]]:
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a view, not descended into."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while i < n:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            yield number, wire, varint()
        elif wire == 2:
            size = varint()
            yield number, wire, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield number, wire, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def op_metadata(raw: bytes) -> dict[str, dict[str, str]]:
    """HLO instruction text (the name of an ``XLA Ops`` event) ->
    {``tf_op``: scope path, ``source``: file:line}, from the event metadata
    of the first device plane of a serialized XSpace. Field numbers are
    those of xplane.proto: XSpace.planes 1; XPlane.name 2, event_metadata 4,
    stat_metadata 5 (maps: key 1, value 2); XEventMetadata.name 2, stats 5;
    XStatMetadata.id 1, name 2; XStat.metadata_id 1, str_value 5."""
    for number, _, plane in _fields(memoryview(raw)):
        if number != 1:
            continue
        name, stat_names, metadata = "", {}, []
        for field, _, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                metadata.extend(v for f, _, v in _fields(value) if f == 2)
            elif field == 5:
                for f, _, v in _fields(value):
                    if f == 2:
                        entry = {a: c for a, _, c in _fields(v)}
                        stat_names[entry.get(1)] = bytes(entry.get(2, b"")).decode()
        if not TR.DEVICE_PLANE.match(name):
            continue
        out: dict[str, dict[str, str]] = {}
        for meta in metadata:
            text, stats = "", {}
            for field, _, value in _fields(meta):
                if field == 2:
                    text = bytes(value).decode()
                elif field == 5:
                    stat = {a: c for a, _, c in _fields(value)}
                    key = stat_names.get(stat.get(1))
                    if key in OP_STATS and 5 in stat:
                        stats[key] = bytes(stat[5]).decode()
            out.setdefault(text, stats)
        return out
    return {}


_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def result_shapes(text: str) -> list[str]:
    """The result shapes of an HLO instruction's text, layouts left out:
    ``%copy.2 = bf16[8,128]{1,0} copy(...)`` -> ``["bf16[8,128]"]``; a
    tuple-shaped result gives one shape for each element."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return []
    found = TR._OPCODE.search(rest)
    return _SHAPE.findall(rest[:found.start() + 1] if found else rest)


def op_scopes(data: Any, top: int, metadata: dict[str, dict[str, str]],
              shapes: tuple[str, ...] = ()) -> list[dict]:
    """The ``top`` device operations by time (the labels of
    ``trace_reduce``'s ``device_ops``), each with its scope, its source and
    the number of times it ran. With ``shapes``, only the operations one of
    whose results has one of those shapes (``bf16[32,6,8,2048,128]``): an
    operation no ``named_scope`` reaches is found by what it writes; ``top``
    0 then lists them all."""
    line = device_lines(data).get(TR.OPS_LINE)
    totals: dict[str, list] = {}  # by instruction text: a million events, some hundred texts
    for ev in (line.events if line is not None else []):
        slot = totals.setdefault(ev.name, [0.0, 0])
        slot[0] += ev.duration_ns * 1e-9
        slot[1] += 1
    by_op: dict[str, dict] = {}
    for text, (total, count) in totals.items():
        label, opcode = TR.short_op(text)
        if opcode in TR.CONTAINERS:
            continue
        if shapes and not set(shapes) & set(result_shapes(text)):
            continue
        stats = metadata.get(text, {})
        slot = by_op.setdefault(label, {"op": label, "seconds": 0.0, "count": 0,
                                        "scope": stats.get("tf_op", ""),
                                        "source": stats.get("source", "")})
        slot["seconds"] += total
        slot["count"] += count
    ranked = sorted(by_op.values(), key=lambda s: -s["seconds"])
    return ranked[:top] if top else ranked


def _median(values: list[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def summary(data: Any, ops: int = 0, raw: bytes = b"",
            shapes: tuple[str, ...] = ()) -> dict:
    """The whole join; with ``ops`` or ``shapes``, ``raw`` is the serialized
    XSpace that ``data`` was read from (for the operations' scopes)."""
    runs = program_runs(data)
    rows = join(data, runs)
    out: dict[str, Any] = {
        "programs": {n: {"runs": len(r), "seconds": sum(e - s for s, e in r),
                         "ms_per_run": 1e3 * sum(e - s for s, e in r) / len(r)}
                     for n, r in sorted(runs.items())},
        "kinds": {},
    }
    for plane in data.planes:  # unix ns of the session's start: event times count from it
        for key, value in plane.stats:
            if key == "profile_start_time":
                out["profile_start_time_ns"] = int(value)
    for kind, kind_rows in rows.items():
        counts: dict[int, int] = {}
        for row in kind_rows:
            counts[row["chunks_in_wait"]] = counts.get(row["chunks_in_wait"], 0) + 1
        out["kinds"][kind] = {
            "placed": len(kind_rows),
            "issue_ms_p50": _median([r["issue_ms"] for r in kind_rows]),
            "wait_ms_p50": _median([r["wait_ms"] for r in kind_rows]),
            "run_ms_p50": _median([r["run_ms"] for r in kind_rows]),
            "after_run_ms_p50": _median([r["after_run_ms"] for r in kind_rows]),
            "chunks_in_wait_mean": (sum(r["chunks_in_wait"] for r in kind_rows) / len(kind_rows)
                                    if kind_rows else None),
            "chunks_in_wait_counts": dict(sorted(counts.items())),
            "rows": kind_rows,
        }
    if ops or shapes:
        out["ops"] = op_scopes(data, ops, op_metadata(raw), shapes)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a kept trace directory or an .xplane.pb file")
    ap.add_argument("--ops", type=int, default=0, help="also list this many device operations")
    ap.add_argument("--shape", action="append", default=[], metavar="TYPE[DIMS]",
                    help="list only the operations with a result of this shape, e.g. "
                         "bf16[32,6,8,2048,128] (repeatable; all of them unless --ops limits)")
    ap.add_argument("--json", action="store_true", help="print the whole join as JSON")
    args = ap.parse_args()
    path = args.trace if args.trace.endswith(".pb") else TR.find_xplane(args.trace)
    raw = b""
    if args.ops or args.shape:
        with open(path, "rb") as fh:
            raw = fh.read()
    out = summary(TR.load(path), args.ops, raw, tuple(args.shape))
    if args.json:
        print(json.dumps(out))
        return 0
    for name, p in out["programs"].items():
        print(f"program {name}: {p['runs']} runs, {p['seconds']:.4f} s, "
              f"{p['ms_per_run']:.2f} ms a run")
    for kind, k in out["kinds"].items():
        if not k["placed"]:
            print(f"{kind}: no dispatch placed")
            continue
        print(f"{kind}: {k['placed']} placed; ms p50: issue {k['issue_ms_p50']:.2f}, issue end -> "
              f"run start {k['wait_ms_p50']:.2f}, run {k['run_ms_p50']:.2f}, run end -> fetch "
              f"returned {k['after_run_ms_p50']:.2f}; pooled chunks finished in the wait: mean "
              f"{k['chunks_in_wait_mean']:.2f}, counts {k['chunks_in_wait_counts']}")
    for op in out.get("ops", []):
        print(f"op {op['seconds']:.4f} s x{op['count']} {op['op']} <- {op['scope']} {op['source']}")
    if args.shape and not out["ops"]:
        print(f"no device operation has a result of shape {' or '.join(args.shape)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
