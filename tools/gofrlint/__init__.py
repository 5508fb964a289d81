"""gofrlint v2 — whole-program project-invariant linter.

ruff holds the style/complexity line; gofrlint holds the PROJECT
invariants generic linters cannot know. v1 was per-file; v2 adds a
whole-program pass (symbol table + conservative call graph) so the
rules see through attribute dispatch — the PR 14 class of hazard (a
WAL fsync reached while the per-token journal lock is held) — plus
cross-module contract registries for metrics, config keys, and the
admin surface.

Rules
-----
GFL001  no raw ``os.environ``/``os.getenv`` READS outside config.py
        (package code; writes and entry-point scripts exempt).
GFL002  ``time.time()`` only at sites annotated
        ``# gofrlint: wall-clock — <why>``.
GFL003  every ``threading.Thread`` named and daemon-or-joined.
GFL004  no blocking call while holding a lock — per-file AND
        interprocedurally: per-function {may-block, acquires}
        summaries to a fixpoint over the call graph.
GFL005  metric naming convention, statically.
GFL006  no swallowed exceptions in engine paths.
GFL007  metric contract: one registration home per family, help and
        labels consistent at every touch point, a row in
        tests/test_metric_naming.py.
GFL008  config-key provenance: reads declared in config.py
        DECLARED_KEYS; declared keys read somewhere (inert knobs).
GFL009  admin-surface parity: /admin/* registrations ↔ README table.

Suppression: ``# gofrlint: disable=GFLnnn — <reason>`` on (or on a
comment line directly above) the reported line. Suppressions are the
violation LEDGER (``--ledger``), ratcheted by ``--ledger-check`` —
the committed ledger only shrinks.

The static lock-order graph (``--emit-lock-graph``) shares node ids
with the runtime sanitizer's observed graph (lock CREATION SITES,
``path:lineno``); tools/lockgraph_check.py fails on cycles in the
union. See docs/advanced-guide/static-analysis.md."""

from .base import (  # noqa: F401
    _COUNTER_SUFFIXES,
    _GAUGE_SUFFIXES,
    _HISTOGRAM_SUFFIXES,
    RULES,
    Violation,
    iter_files,
)
from .cli import LintRun, check_ledger, lint_paths, main  # noqa: F401
from .contracts import contract_violations  # noqa: F401
from .interproc import WholeProgram  # noqa: F401
from .local import FileLinter  # noqa: F401
from .model import Project  # noqa: F401
