"""The per-file rules (GFL001–GFL006) — unchanged semantics from
gofrlint v1, now layered on the shared substrate in ``base``. The
whole-program rules (interprocedural GFL004, GFL007–009) live in
``interproc``/``contracts`` and run from ``cli.lint_paths``."""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Optional

from .base import (
    _COUNTER_SUFFIXES,
    _GAUGE_SUFFIXES,
    _HISTOGRAM_SUFFIXES,
    Directives,
    Violation,
    classify_blocking,
    lockish,
    src_of,
)

# GFL001: os.environ methods that WRITE (allowed anywhere — scripts and
# test scaffolding set the process environment; only reads must route
# through config.py accessors)
_ENV_WRITE_METHODS = {"update", "pop", "setdefault", "clear", "__setitem__"}

# GFL006: modules whose code runs on (or under the locks of) engine
# threads — a swallowed exception there is a silent wedge
_ENGINE_MODULES = {
    "telemetry.py", "timebase.py", "tracing.py", "postmortem.py",
    "metrics.py", "profiling.py",
}

_LOCKISH_RE = re.compile(r"(lock|mutex|_mu)\b", re.IGNORECASE)


class FileLinter:
    def __init__(self, path: Path, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.directives = Directives(source)
        self.comments = self.directives.comments
        self.violations: list[Violation] = []
        self.in_package = "gofr_tpu" in Path(rel).parts
        parts = Path(rel).parts
        self.is_engine = (
            ("tpu" in parts and self.in_package)
            or Path(rel).name in _ENGINE_MODULES and self.in_package
        )

    # -- directives -----------------------------------------------------------
    def suppressed(self, rule: str, lineno: int) -> bool:
        return self.directives.suppressed(rule, lineno)

    def wall_annotated(self, lineno: int) -> bool:
        return self.directives.wall_annotated(lineno)

    def report(self, rule: str, node: ast.AST, message: str) -> None:
        lineno = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        if self.suppressed(rule, lineno):
            return
        self.violations.append(Violation(rule, self.rel, lineno, col, message))

    # -- entry ----------------------------------------------------------------
    def run(self) -> list[Violation]:
        try:
            tree = ast.parse(self.source)
        except SyntaxError as exc:
            self.violations.append(Violation(
                "GFL000", self.rel, exc.lineno or 1, 0,
                f"syntax error: {exc.msg}",
            ))
            return self.violations
        parents: dict[int, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                parents[id(child)] = parent
        self._parents = parents
        module_joins = self._module_has_thread_join(tree)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                self._check_env_read_call(node)
                self._check_wall_clock(node)
                self._check_thread(node, module_joins)
                self._check_metric_name(node)
            elif isinstance(node, ast.Attribute):
                self._check_environ_use(node)
            elif isinstance(node, ast.ExceptHandler):
                self._check_except(node)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_lock_holds(node)
        return self.violations

    # -- GFL001 ---------------------------------------------------------------
    def _gfl001_active(self) -> bool:
        return self.in_package and Path(self.rel).name != "config.py"

    def _check_env_read_call(self, node: ast.Call) -> None:
        if not self._gfl001_active():
            return
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr == "getenv" and \
                isinstance(fn.value, ast.Name) and fn.value.id == "os":
            self.report(
                "GFL001", node,
                "os.getenv() outside config.py — use a config.py accessor "
                "(get_env/env_flag)",
            )

    def _check_environ_use(self, node: ast.Attribute) -> None:
        if not self._gfl001_active():
            return
        if node.attr != "environ" or not (
            isinstance(node.value, ast.Name) and node.value.id == "os"
        ):
            return
        parent = self._parents.get(id(node))
        # allowed: write-method calls and item writes/deletes
        if isinstance(parent, ast.Attribute) and \
                parent.attr in _ENV_WRITE_METHODS:
            return
        if isinstance(parent, ast.Subscript) and isinstance(
            parent.ctx, (ast.Store, ast.Del)
        ):
            return
        self.report(
            "GFL001", node,
            "raw os.environ read outside config.py — use a config.py "
            "accessor (get_env/env_flag/environ_snapshot)",
        )

    # -- GFL002 ---------------------------------------------------------------
    def _check_wall_clock(self, node: ast.Call) -> None:
        fn = node.func
        is_time_time = (
            isinstance(fn, ast.Attribute) and fn.attr == "time"
            and isinstance(fn.value, ast.Name) and fn.value.id == "time"
        )
        if not is_time_time:
            return
        if self.wall_annotated(node.lineno):
            return
        self.report(
            "GFL002", node,
            "time.time() — use time.monotonic()/perf_counter() for "
            "durations and ordering; annotate true presentation sites "
            "with '# gofrlint: wall-clock — <why>'",
        )

    # -- GFL003 ---------------------------------------------------------------
    @staticmethod
    def _module_has_thread_join(tree: ast.Module) -> bool:
        """A zero-positional-arg ``.join()`` call anywhere in the module
        (``t.join()``, ``self._thread.join(timeout=5)``). ``str.join``
        and ``os.path.join`` always take positional args."""
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "join"
                and not node.args
            ):
                return True
        return False

    def _check_thread(self, node: ast.Call, module_joins: bool) -> None:
        fn = node.func
        is_thread = (
            isinstance(fn, ast.Attribute) and fn.attr == "Thread"
            and isinstance(fn.value, ast.Name) and fn.value.id == "threading"
        ) or (isinstance(fn, ast.Name) and fn.id == "Thread")
        if not is_thread:
            return
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        if "name" not in kwargs:
            self.report(
                "GFL003", node,
                "unnamed thread — pass name=... so stacks, the watchdog, "
                "and the leak detector can attribute it",
            )
        daemon = kwargs.get("daemon")
        is_daemon = isinstance(daemon, ast.Constant) and daemon.value is True
        if not is_daemon and not module_joins:
            self.report(
                "GFL003", node,
                "non-daemon thread with no .join() in this module — "
                "daemonize it or join it in close()",
            )

    # -- GFL004 (local: blocking primitive directly under a held lock) --------
    def _check_lock_holds(self, func: ast.AST) -> None:
        self._walk_stmts(list(getattr(func, "body", [])), held=[])

    def _walk_stmts(self, stmts: list, held: list) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs are visited on their own
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                acquired = [
                    src_of(item.context_expr)
                    for item in stmt.items
                    if lockish(item.context_expr)
                ]
                held.extend(acquired)
                self._walk_stmts(stmt.body, held)
                for _ in acquired:
                    held.pop()
                continue
            lock_op = self._acquire_release(stmt)
            if lock_op is not None:
                op, name = lock_op
                if op == "acquire":
                    held.append(name)
                elif name in held:
                    held.remove(name)
                continue
            if held:
                for call in (
                    n for n in ast.walk(stmt) if isinstance(n, ast.Call)
                ):
                    self._check_blocking(call, held)
            else:
                for attr in ("body", "orelse", "finalbody"):
                    self._walk_stmts(list(getattr(stmt, attr, [])), held)
                for handler in getattr(stmt, "handlers", []):
                    self._walk_stmts(list(handler.body), held)

    def _acquire_release(self, stmt: ast.stmt) -> Optional[tuple]:
        if not (isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call)):
            return None
        call = stmt.value
        if not isinstance(call.func, ast.Attribute):
            return None
        if call.func.attr not in ("acquire", "release"):
            return None
        receiver = src_of(call.func.value)
        if not _LOCKISH_RE.search(receiver):
            return None
        return (call.func.attr, receiver)

    def _check_blocking(self, call: ast.Call, held: list) -> None:
        label = classify_blocking(call, held)
        if label is None:
            return
        self.report(
            "GFL004", call,
            f"{label} while holding {held[-1]!r} — blocking under a lock "
            "stalls every contending thread (move it outside the "
            "critical section)",
        )

    # -- GFL005 ---------------------------------------------------------------
    def _check_metric_name(self, node: ast.Call) -> None:
        fn = node.func
        if not (
            isinstance(fn, ast.Attribute)
            and fn.attr in ("counter", "gauge", "histogram")
        ):
            return
        if not node.args or not (
            isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            return
        name = node.args[0].value
        kind = fn.attr
        problem = None
        if not name.startswith("gofr_"):
            problem = "missing gofr_ prefix"
        elif not re.fullmatch(r"[a-z][a-z0-9_]*", name) or "__" in name:
            problem = "not snake_case"
        elif kind == "counter" and not name.endswith(_COUNTER_SUFFIXES):
            problem = "counter must end in _total"
        elif kind == "histogram" and not name.endswith(_HISTOGRAM_SUFFIXES):
            problem = f"histogram needs a unit suffix {_HISTOGRAM_SUFFIXES}"
        elif kind == "gauge" and not name.endswith(_GAUGE_SUFFIXES):
            problem = f"gauge needs a unit/dimension suffix {_GAUGE_SUFFIXES}"
        if problem:
            self.report("GFL005", node, f"metric {name!r}: {problem}")

    # -- GFL006 ---------------------------------------------------------------
    def _check_except(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self.report(
                "GFL006", node,
                "bare except: — catch a concrete exception type",
            )
            return
        if not self.is_engine:
            return
        broad = isinstance(node.type, ast.Name) and node.type.id in (
            "Exception", "BaseException"
        )
        body_is_pass = all(
            isinstance(s, ast.Pass)
            or (isinstance(s, ast.Expr) and isinstance(s.value, ast.Constant))
            for s in node.body
        )
        if broad and body_is_pass:
            # report at the pass statement: the suppression comment (the
            # ledger entry) belongs next to the swallow itself
            self.report(
                "GFL006", node.body[0],
                f"except {node.type.id}: pass in an engine path — a "
                "swallowed exception on an engine thread is a silent "
                "wedge; log it, re-raise, or narrow the type",
            )
