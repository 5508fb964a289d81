#!/usr/bin/env python3
"""gofrlint — project-invariant linter for the gofr_tpu tree.

This file is the stable entry point (CI and the test suite invoke
``python tools/gofrlint.py`` / import it by path); the implementation
lives in the ``tools/gofrlint/`` package. See that package's
``__init__`` docstring for the rule table (GFL001–GFL009), the
suppression-ledger contract, and the whole-program analysis model —
or docs/advanced-guide/static-analysis.md for the prose version.

Usage
-----
    python tools/gofrlint.py [--format=text|json] [--ledger]
        [--ledger-check FILE] [--emit-lock-graph FILE] PATH [PATH...]

Exit status 0 when clean, 1 when violations were reported (or the
suppression ledger grew past the committed baseline).
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

_PKG_DIR = Path(__file__).resolve().parent / "gofrlint"


def _load_impl():
    cached = sys.modules.get("_gofrlint_impl")
    if cached is not None:
        return cached
    spec = importlib.util.spec_from_file_location(
        "_gofrlint_impl",
        _PKG_DIR / "__init__.py",
        submodule_search_locations=[str(_PKG_DIR)],
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules["_gofrlint_impl"] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        sys.modules.pop("_gofrlint_impl", None)
        raise
    return module


_impl = _load_impl()

RULES = _impl.RULES
Violation = _impl.Violation
FileLinter = _impl.FileLinter
LintRun = _impl.LintRun
Project = _impl.Project
WholeProgram = _impl.WholeProgram
check_ledger = _impl.check_ledger
contract_violations = _impl.contract_violations
iter_files = _impl.iter_files
lint_paths = _impl.lint_paths
main = _impl.main
_COUNTER_SUFFIXES = _impl._COUNTER_SUFFIXES
_HISTOGRAM_SUFFIXES = _impl._HISTOGRAM_SUFFIXES
_GAUGE_SUFFIXES = _impl._GAUGE_SUFFIXES

if __name__ == "__main__":
    sys.exit(main())
