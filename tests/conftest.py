"""Test environment: force JAX onto a virtual 8-device CPU mesh BEFORE jax
is imported anywhere, mirroring the reference CI's strategy of running
against local fakes (SURVEY.md §4: sqlmock/miniredis ↔ CPU PJRT here).
"""

import os

# HARD override: a chip machine's default platform is the TPU; tests
# must run on the virtual 8-device CPU mesh regardless.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# belt and braces for a jax imported before this file ran (the env var is
# read at import); must run before any backend initialization
jax.config.update("jax_platforms", "cpu")

# tests write no persistent compile cache: the in-checkout default
# (tpu/device.py configure_compile_cache) stays empty and small — the chip
# tool copies the tree as it stands on disk
jax.config.update("jax_enable_compilation_cache", False)

# this jax build computes f32 matmuls at reduced precision by default (TPU
# convention); numeric tests need exact f32 accumulation
jax.config.update("jax_default_matmul_precision", "highest")

import json
import socket
import threading

import pytest

from gofr_tpu.devtools import sanitizer as _sanitizer

# GOFR_SANITIZE=1: rebind threading.Lock/RLock to the instrumented
# wrappers BEFORE any engine object builds its locks — the whole suite
# then runs under lock-order cycle detection, hold-time tracking, and
# the per-test thread-leak check below (CI runs this as the `sanitize`
# tier-1 variant, serial so the graph sees real interleavings).
if _sanitizer.enabled():
    _sanitizer.install()
    # fresh report per session: the per-test writes below append, so a
    # leftover file would misattribute a previous run's findings
    try:
        os.unlink(os.environ.get("GOFR_SANITIZE_REPORT",
                                 "sanitizer-report.jsonl"))
    except OSError:
        pass


def _format_finding(v: dict) -> str:
    lines = [v.get("summary") or v.get("kind", "finding")]
    for key in ("this_edge", "reverse_edge"):
        edge = v.get(key)
        if edge:
            lines.append(f"  {key}: {edge['from']} -> {edge['to']} "
                         f"on thread {edge['thread']}")
            lines.extend(f"    {frame}" for frame in edge["acquire_stack"][:6])
    return "\n".join(lines)


@pytest.fixture(autouse=True)
def gofr_sanitize(request):
    """Per-test concurrency verdict under GOFR_SANITIZE=1: fail the
    test that recorded a lock-order cycle or leaked an unjoined
    non-daemon thread (allowlisted singletons exempt). Findings also
    land in GOFR_SANITIZE_REPORT (default sanitizer-report.jsonl) so CI
    can upload them as an artifact."""
    if not _sanitizer.enabled():
        yield
        return
    before = set(threading.enumerate())
    yield
    leaked = _sanitizer.leaked_threads(before)
    report = _sanitizer.drain()
    problems = [_format_finding(v) for v in report["violations"]]
    if leaked:
        problems.append(
            "leaked non-daemon thread(s): "
            + ", ".join(sorted(t.name for t in leaked))
            + " — join them in close()/shutdown() or daemonize"
        )
    if problems or report["hold_warnings"]:
        path = os.environ.get("GOFR_SANITIZE_REPORT", "sanitizer-report.jsonl")
        try:
            with open(path, "a", encoding="utf-8") as fh:
                fh.write(json.dumps({
                    "test": request.node.nodeid,
                    "violations": report["violations"],
                    "hold_warnings": report["hold_warnings"],
                    "leaked_threads": sorted(t.name for t in leaked),
                }) + "\n")
        except OSError:
            pass
    if problems:
        pytest.fail(
            "concurrency sanitizer:\n" + "\n".join(problems), pytrace=False
        )


def pytest_sessionfinish(session):
    """GOFR_SANITIZE_GRAPH=<file>: write the whole session's OBSERVED
    lock-order graph (the edge graph survives drain() on purpose) in
    the static exporter's schema, for the static∪runtime cycle check
    in tools/lockgraph_check.py."""
    graph_path = os.environ.get("GOFR_SANITIZE_GRAPH")
    if graph_path and _sanitizer.enabled():
        try:
            _sanitizer.export_graph(graph_path)
        except OSError:
            pass


@pytest.fixture
def free_port():
    def _get():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    return _get


@pytest.fixture
def make_plain_app(free_port, monkeypatch, tmp_path):
    """ONE place that builds a datasource-free App for transport tests
    (http/app/protocol suites shared this setup as drifting copies: the
    env-scrub list must grow in ONE spot when the container gains a new
    datasource host). Returns a builder; the caller registers routes and
    calls start(). Teardown shuts the app down."""
    import gofr_tpu

    built = []

    def _build():
        monkeypatch.setenv("HTTP_PORT", str(free_port()))
        monkeypatch.setenv("LOG_LEVEL", "FATAL")
        for key in ("REDIS_HOST", "DB_NAME", "DB_HOST", "TPU_ENABLED",
                    "MODEL_NAME"):
            monkeypatch.delenv(key, raising=False)
        monkeypatch.chdir(tmp_path)
        application = gofr_tpu.new()
        built.append(application)
        return application

    yield _build
    for application in built:
        application.shutdown()
