"""Shared substrate for gofrlint: rule table, violation record,
suppression directives, and the blocking-call classifier both the
per-file pass (GFL004 local) and the whole-program pass (GFL004
interprocedural summaries) agree on. Stdlib only."""

from __future__ import annotations

import ast
import io
import re
import tokenize
from pathlib import Path
from typing import Optional

RULES = {
    "GFL001": "raw environment read outside config.py",
    "GFL002": "time.time() without a wall-clock annotation",
    "GFL003": "threading.Thread hygiene (name + daemon-or-joined)",
    "GFL004": "blocking call while holding a lock",
    "GFL005": "metric name violates the naming convention",
    "GFL006": "swallowed exception in an engine path",
    "GFL007": "metric contract drift across registration sites",
    "GFL008": "config-key provenance (undeclared read / inert knob)",
    "GFL009": "admin-surface parity (code vs README route table)",
}

_DISABLE_RE = re.compile(r"#\s*gofrlint:\s*disable=([A-Z0-9,\s]+)")
_WALL_RE = re.compile(r"#\s*gofrlint:\s*wall-clock")

# GFL005: mirrored from tests/test_metric_naming.py — the static half
# of the same convention
_COUNTER_SUFFIXES = ("_total",)
_HISTOGRAM_SUFFIXES = ("_seconds", "_bytes", "_size")
_GAUGE_SUFFIXES = (  # keep in lockstep with tests/test_metric_naming.py
    "_seconds", "_bytes", "_total", "_depth", "_ratio", "_entries",
    "_active", "_acceptance", "_state", "_blocks", "_size", "_level",
    "_per_dispatch", "_rate", "_remaining",
)

# GFL004 heuristics (shared with the interprocedural summaries)
_LOCKISH_RE = re.compile(r"(lock|mutex|_mu)\b", re.IGNORECASE)
_QUEUEISH_RE = re.compile(r"(queue|(^|\.)q$|_q$)", re.IGNORECASE)
_EVENTISH_RE = re.compile(r"(event|_stop$|_ready$|stopped)", re.IGNORECASE)
_THREADISH_RE = re.compile(r"(thread|worker|proc)", re.IGNORECASE)


class Violation:
    __slots__ = ("rule", "path", "line", "col", "message")

    def __init__(self, rule: str, path: str, line: int, col: int, message: str):
        self.rule = rule
        self.path = path
        self.line = line
        self.col = col
        self.message = message

    def as_dict(self) -> dict:
        return {
            "file": self.path, "line": self.line, "col": self.col,
            "rule": self.rule, "message": self.message,
        }


def src_of(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # very old nodes / synthetic trees
        return ""


def collect_comments(source: str) -> dict[int, str]:
    """line number -> comment text (tokenize-accurate: a ``# gofrlint``
    inside a string literal never counts)."""
    out: dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError):
        pass
    return out


class Directives:
    """Per-file suppression/annotation directives. Comment-only lines
    pass their directives down to the next CODE line (cascading through
    blank lines and further comment lines, so a multi-line reason block
    above a statement works)."""

    def __init__(self, source: str):
        self.comments = collect_comments(source)
        lines = source.splitlines()
        self._directive_lines: dict[int, str] = {}
        for lineno, comment in self.comments.items():
            line = lines[lineno - 1]
            code = line[: line.index("#")] if "#" in line else line
            target = lineno
            if not code.strip():
                target = lineno + 1
                while target <= len(lines):
                    stripped = lines[target - 1].strip()
                    if stripped and not stripped.startswith("#"):
                        break
                    target += 1
            self._directive_lines.setdefault(target, "")
            self._directive_lines[target] += " " + comment

    def at(self, lineno: int) -> str:
        return self._directive_lines.get(lineno, "")

    def suppressed(self, rule: str, lineno: int) -> bool:
        m = _DISABLE_RE.search(self.at(lineno))
        if not m:
            return False
        codes = {c.strip() for c in m.group(1).split(",")}
        return rule in codes

    def wall_annotated(self, lineno: int) -> bool:
        return bool(_WALL_RE.search(self.at(lineno)))

    def disable_counts(self) -> dict[str, int]:
        """Per-rule count of disable-directive mentions in this file —
        one increment per rule per directive comment (the suppression
        LEDGER the ratchet sums)."""
        counts: dict[str, int] = {}
        for comment in self.comments.values():
            m = _DISABLE_RE.search(comment)
            if not m:
                continue
            for code in m.group(1).split(","):
                code = code.strip()
                if code:
                    counts[code] = counts.get(code, 0) + 1
        return counts


def lockish(expr: ast.AST) -> bool:
    return bool(_LOCKISH_RE.search(src_of(expr)))


def has_timeout(call: ast.Call) -> bool:
    if any(kw.arg == "timeout" for kw in call.keywords):
        return True
    # Queue.get(block, timeout) positional form
    return len(call.args) >= 2


def classify_blocking(call: ast.Call, held: Optional[list] = None) -> Optional[str]:
    """The label of a blocking call, or None. ``held`` is the lock
    stack for the (local) under-a-lock context; summary mode passes
    None and counts socket reads unconditionally — a function that
    reads a socket MAY block, whether or not its own body holds a
    lock."""
    fn = call.func
    if isinstance(fn, ast.Name):
        return "sleep()" if fn.id == "sleep" else None
    if not isinstance(fn, ast.Attribute):
        return None
    receiver = src_of(fn.value)
    attr = fn.attr
    if attr == "sleep" and receiver == "time":
        return "time.sleep()"
    if attr == "join" and not call.args and not has_timeout(call) \
            and _THREADISH_RE.search(receiver):
        # join(timeout=...) is a BOUNDED wait (teardown idiom) — only
        # the indefinite form counts as blocking
        return f"{receiver}.join()"
    if attr in ("get", "put") and _QUEUEISH_RE.search(receiver) \
            and not has_timeout(call):
        return f"timeout-less {receiver}.{attr}()"
    if attr == "wait" and _EVENTISH_RE.search(receiver) and \
            not has_timeout(call) and not call.args:
        return f"timeout-less {receiver}.wait()"
    if attr in ("accept", "recv", "recvfrom"):
        if held is None or _LOCKISH_RE.search(" ".join(held)):
            return f"socket .{attr}()"
        return None
    if attr in ("fsync", "fdatasync") and receiver == "os":
        # durability barriers stall for the device, not the GIL — the
        # PR 14 WAL-under-journal-lock hazard class
        return f"os.{attr}()"
    if receiver == "subprocess" and attr in (
        "run", "call", "check_call", "check_output"
    ):
        return f"subprocess.{attr}()"
    if receiver in ("requests", "urllib.request") or attr == "urlopen":
        return f"{receiver}.{attr}()"
    return None


def iter_files(paths: list[str]) -> list[Path]:
    out: list[Path] = []
    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            out.extend(
                f for f in sorted(p.rglob("*.py"))
                if "__pycache__" not in f.parts
            )
        elif p.suffix == ".py":
            out.append(p)
    return out
