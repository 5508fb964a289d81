"""Pretty-print a postmortem black-box bundle (gofr_tpu/postmortem.py).

    python tools/postmortem_view.py                      # newest bundle in ./postmortems
    python tools/postmortem_view.py postmortems          # newest bundle in a dir
    python tools/postmortem_view.py postmortem-...json   # a specific bundle
    python tools/postmortem_view.py ... --json           # machine-readable digest

Renders the operator's triage view: the header (reason, time, engine
state + last transitions), versions and config fingerprint, the
dispatch-timeline tail (the wedged dispatch shows `running`), the
watchdog's stalled entries, the in-flight + recent flight records, the
timebase coverage, and a per-thread STACK DIGEST (threads grouped by
identical stacks — the wedged thread's unique stack stands out instead
of drowning in 60 idle pool threads).

Exit codes: 0 rendered, 1 no bundle found, 2 bundle unparseable (CI's
postmortem smoke gates on this).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Optional


def find_bundle(target: str) -> Optional[str]:
    """Resolve a path argument: a bundle file as-is, a directory to its
    newest bundle."""
    if os.path.isfile(target):
        return target
    if os.path.isdir(target):
        bundles = sorted(
            n for n in os.listdir(target)
            if n.startswith("postmortem-") and n.endswith(".json")
        )
        if bundles:
            return os.path.join(target, bundles[-1])
    return None


def load_bundle(path: str) -> dict[str, Any]:
    """Parse + structurally validate a bundle; raises ValueError when it
    is not a postmortem bundle (CI smoke gates on this)."""
    with open(path, encoding="utf-8") as fh:
        bundle = json.load(fh)
    if not isinstance(bundle, dict) or not str(
        bundle.get("schema", "")
    ).startswith("gofr-postmortem/"):
        raise ValueError(f"{path}: not a gofr postmortem bundle")
    for field in ("reason", "ts", "versions", "config", "threads"):
        if field not in bundle:
            raise ValueError(f"{path}: bundle missing required field {field!r}")
    return bundle


def stack_digest(threads: list[dict[str, Any]]) -> list[dict[str, Any]]:
    """Group threads by identical stack; most-unusual (smallest group)
    first — the wedged thread is the one that looks like nothing else."""
    groups: dict[str, list[str]] = {}
    for t in threads:
        groups.setdefault(t.get("stack", ""), []).append(t.get("name", "?"))
    out = [
        {"threads": sorted(names), "stack": stack}
        for stack, names in groups.items()
    ]
    out.sort(key=lambda g: (len(g["threads"]), g["threads"]))
    return out


def digest(bundle: dict[str, Any]) -> dict[str, Any]:
    """The machine-readable summary (--json)."""
    engine = bundle.get("engine") or {}
    state = (engine.get("engine") or {}).get("state")
    dispatches = bundle.get("dispatches") or []
    running = [d for d in dispatches if d.get("status") == "running"]
    watchdog = engine.get("watchdog") or {}
    stalled = [w for w in watchdog.get("watching", []) if w.get("stalled")]
    return {
        "reason": bundle.get("reason"),
        "detail": bundle.get("detail"),
        "iso": bundle.get("iso"),
        "engine_state": state,
        "versions": bundle.get("versions"),
        "config_fingerprint": (bundle.get("config") or {}).get("fingerprint"),
        "dispatches": len(dispatches),
        "dispatches_running": [d.get("dispatch_id") for d in running],
        "stalled_watches": stalled,
        "requests": len(bundle.get("requests") or []),
        "requests_in_flight": len(bundle.get("requests_in_flight") or []),
        "timebase_snapshots": len(bundle.get("timebase") or []),
        "threads": len(bundle.get("threads") or []),
        "unique_stacks": len(stack_digest(bundle.get("threads") or [])),
    }


def _fmt_ts(ts: Any) -> str:
    try:
        return time.strftime("%H:%M:%S", time.gmtime(float(ts)))
    except (TypeError, ValueError):
        return "?"


def render(bundle: dict[str, Any], out=sys.stdout) -> None:
    p = lambda line="": print(line, file=out)  # noqa: E731
    d = digest(bundle)
    p("=" * 72)
    p(f"POSTMORTEM  reason={d['reason']}  at {bundle.get('iso')}")
    if d["detail"]:
        p(f"  detail: {d['detail']}")
    p(f"  versions: {d['versions']}  config fingerprint: "
      f"{d['config_fingerprint']}")
    engine = bundle.get("engine") or {}
    machine = engine.get("engine") or {}
    p(f"  engine state: {machine.get('state')}"
      + (f" ({machine.get('detail')})" if machine.get("detail") else ""))
    history = machine.get("history") or []
    for h in history[-5:]:
        p(f"    {_fmt_ts(h.get('ts'))}  -> {h.get('state')}"
          + (f"  {h.get('detail')}" if h.get("detail") else ""))

    watchdog = engine.get("watchdog") or {}
    if d["stalled_watches"]:
        p("-" * 72)
        p("STALLED DISPATCHES (watchdog):")
        for w in d["stalled_watches"]:
            p(f"  dispatch {w.get('dispatch_id')}  kind={w.get('kind')}  "
              f"elapsed={w.get('elapsed_s')}s")
    elif watchdog.get("stalls"):
        p(f"  past stalls: {watchdog['stalls']}")

    dispatches = bundle.get("dispatches") or []
    if dispatches:
        p("-" * 72)
        p(f"DISPATCH TAIL (newest of {len(dispatches)}):")
        for rec in dispatches[:10]:
            dur = rec.get("duration_s")
            p(f"  #{rec.get('dispatch_id')}  {rec.get('kind'):<15s} "
              f"{rec.get('status'):<8s} "
              f"dur={f'{dur:.4f}s' if dur is not None else 'IN FLIGHT'}")

    in_flight = bundle.get("requests_in_flight") or []
    if in_flight:
        p("-" * 72)
        p(f"REQUESTS IN FLIGHT ({len(in_flight)}):")
        for rec in in_flight[:10]:
            p(f"  {rec.get('trace_id')}  {rec.get('model')}  "
              f"{rec.get('endpoint')}  dispatch_ids={rec.get('dispatch_ids')}")
    recent = bundle.get("requests") or []
    if recent:
        p(f"recent completed requests: {len(recent)} "
          f"(errored: {sum(1 for r in recent if r.get('status') != 'ok')})")

    snaps = bundle.get("timebase") or []
    p("-" * 72)
    if snaps:
        p(f"TIMEBASE: {len(snaps)} snapshots, "
          f"{_fmt_ts(snaps[0].get('ts'))} .. {_fmt_ts(snaps[-1].get('ts'))}")
    else:
        p("TIMEBASE: no snapshots (sampler off or bundle written at boot)")

    p("-" * 72)
    groups = stack_digest(bundle.get("threads") or [])
    p(f"THREAD STACK DIGEST ({d['threads']} threads, "
      f"{len(groups)} unique stacks; most unusual first):")
    for g in groups:
        p(f"  [{', '.join(g['threads'][:6])}"
          + (f" +{len(g['threads']) - 6} more" if len(g["threads"]) > 6 else "")
          + "]")
        tail = [ln for ln in g["stack"].splitlines() if ln.strip()][-6:]
        for line in tail:
            p(f"    {line.rstrip()}")
        p()
    p("=" * 72)


def main(argv: list[str]) -> int:
    args = [a for a in argv if not a.startswith("--")]
    as_json = "--json" in argv
    target = args[0] if args else "./postmortems"
    path = find_bundle(target)
    if path is None:
        print(f"no postmortem bundle at {target}", file=sys.stderr)
        return 1
    try:
        bundle = load_bundle(path)
    except (ValueError, OSError, json.JSONDecodeError) as exc:
        print(f"unparseable bundle: {exc}", file=sys.stderr)
        return 2
    if as_json:
        print(json.dumps({"path": path, **digest(bundle)}, indent=1))
    else:
        print(f"bundle: {path}")
        render(bundle)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
