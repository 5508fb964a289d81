"""gofrlint's own test suite: positive/negative fixture snippets per
rule, suppression comments, the JSON output schema — and the tree gate
itself (the whole package + tools must lint clean, same contract as
``ruff check .``)."""

import importlib.util
import io
import json
import pathlib
import sys
from contextlib import redirect_stdout

REPO = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "gofrlint", REPO / "tools" / "gofrlint.py"
)
gofrlint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gofrlint)


def lint(source: str, rel: str = "gofr_tpu/somemod.py") -> list:
    """Lint a snippet as though it lived at ``rel`` (path scoping —
    package vs script vs engine module — is part of the rules)."""
    return gofrlint.FileLinter(pathlib.Path(rel), rel, source).run()


def rules_of(violations) -> list:
    return [v.rule for v in violations]


# -- GFL001: env discipline ---------------------------------------------------

def test_gfl001_flags_raw_reads_in_package_code():
    assert rules_of(lint('import os\nx = os.environ.get("K")\n')) == ["GFL001"]
    assert rules_of(lint('import os\nx = os.getenv("K")\n')) == ["GFL001"]
    assert rules_of(lint('import os\nx = os.environ["K"]\n')) == ["GFL001"]
    assert rules_of(lint(
        "import os\nfor k in sorted(os.environ):\n    pass\n"
    )) == ["GFL001"]


def test_gfl001_allows_writes_scripts_and_config():
    assert lint('import os\nos.environ["K"] = "1"\n') == []
    assert lint('import os\nos.environ.setdefault("K", "1")\n') == []
    assert lint('import os\nos.environ.pop("K", None)\n') == []
    assert lint('import os\nos.environ.update({"K": "1"})\n') == []
    # entry-point scripts configure the process env before boot
    assert lint('import os\nx = os.environ.get("K")\n', rel="tools/x.py") == []
    assert lint('import os\nx = os.getenv("K")\n', rel="chip_smoke.py") == []
    # config.py IS the sanctioned reader
    assert lint(
        'import os\nx = os.environ.get("K")\n', rel="gofr_tpu/config.py"
    ) == []


def test_gfl001_suppression_comment():
    src = 'import os\nx = os.environ.get("K")  # gofrlint: disable=GFL001 — bootstrap\n'
    assert lint(src) == []


# -- GFL002: timestamp discipline ---------------------------------------------

def test_gfl002_flags_unannotated_time_time():
    assert rules_of(lint("import time\nt = time.time()\n")) == ["GFL002"]
    # scripts are not exempt — durations there drift the same way
    assert rules_of(
        lint("import time\nt = time.time()\n", rel="tools/x.py")
    ) == ["GFL002"]


def test_gfl002_monotonic_and_annotated_sites_pass():
    assert lint("import time\nt = time.monotonic()\n") == []
    assert lint("import time\nt = time.perf_counter()\n") == []
    assert lint(
        "import time\nt = time.time()  # gofrlint: wall-clock — log ts\n"
    ) == []
    # the annotation may ride a comment-only line directly above
    assert lint(
        "import time\n# gofrlint: wall-clock — api field\nt = time.time()\n"
    ) == []


# -- GFL003: thread hygiene ---------------------------------------------------

def test_gfl003_unnamed_or_unjoined_threads():
    src = "import threading\nthreading.Thread(target=print).start()\n"
    assert rules_of(lint(src)) == ["GFL003", "GFL003"]  # unnamed AND unjoined
    named_daemon = (
        "import threading\n"
        'threading.Thread(target=print, name="t", daemon=True).start()\n'
    )
    assert lint(named_daemon) == []
    named_joined = (
        "import threading\n"
        't = threading.Thread(target=print, name="t")\n'
        "t.start()\nt.join()\n"
    )
    assert lint(named_joined) == []


def test_gfl003_str_and_path_join_do_not_count_as_thread_joins():
    src = (
        "import threading, os\n"
        't = threading.Thread(target=print, name="t")\n'
        'x = ",".join(["a"])\ny = os.path.join("a", "b")\n'
    )
    assert rules_of(lint(src)) == ["GFL003"]  # still unjoined


# -- GFL004: no blocking under a lock -----------------------------------------

def test_gfl004_sleep_and_timeoutless_queue_get_under_lock():
    src = (
        "import threading, time\n"
        "class C:\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1)\n"
    )
    assert rules_of(lint(src)) == ["GFL004"]
    src_q = (
        "class C:\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            item = self.queue.get()\n"
    )
    assert rules_of(lint(src_q)) == ["GFL004"]


def test_gfl004_allows_timeouts_condition_wait_and_unlocked_calls():
    ok = (
        "import time\n"
        "class C:\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            x = self.queue.get(timeout=1)\n"
        "            self._work.wait()\n"  # Condition releases its lock
        "        time.sleep(1)\n"  # outside the critical section
    )
    assert lint(ok) == []


def test_gfl004_acquire_release_tracking():
    src = (
        "import time\n"
        "def f(lock):\n"
        "    lock.acquire()\n"
        "    time.sleep(1)\n"
        "    lock.release()\n"
        "    time.sleep(1)\n"
    )
    assert rules_of(lint(src)) == ["GFL004"]  # only the held sleep


def test_gfl004_thread_join_under_lock():
    src = (
        "class C:\n"
        "    def close(self):\n"
        "        with self._lock:\n"
        "            self._thread.join()\n"
    )
    assert rules_of(lint(src)) == ["GFL004"]


# -- GFL005: metric naming ----------------------------------------------------

def test_gfl005_convention_enforced_statically():
    bad = 'm.counter("gofr_tpu_requests", "r")\n'
    assert rules_of(lint(bad)) == ["GFL005"]
    assert rules_of(lint('m.histogram("gofr_tpu_latency", "l")\n')) == ["GFL005"]
    assert rules_of(lint('m.gauge("gofr_tpu_stuff", "s")\n')) == ["GFL005"]
    assert rules_of(lint('m.counter("tpu_x_total", "x")\n')) == ["GFL005"]
    assert lint('m.counter("gofr_tpu_requests_total", "r")\n') == []
    assert lint('m.histogram("gofr_tpu_latency_seconds", "l")\n') == []
    # dynamically composed names are the runtime test's job, not ours
    assert lint("m.counter(name, 'x')\n") == []


def test_gfl005_mesh_family_covered():
    """The sharded-serving family (tpu/device.py): the _size gauge
    suffix (gofr_tpu_mesh_axis_size{axis}) and the degrade counter pass;
    suffix drift within the family still fails."""
    assert lint('m.gauge("gofr_tpu_mesh_axis_size", "a")\n') == []
    assert lint('m.counter("gofr_tpu_mesh_degrade_total", "d")\n') == []
    assert rules_of(lint('m.gauge("gofr_tpu_mesh_axes", "a")\n')) == \
        ["GFL005"]


def test_gfl005_deadline_family_covered():
    """The deadline/brownout family (deadline.py, batcher.py,
    decode_pool.py): the _level gauge suffix and the stage/cause
    counters pass; suffix drift within the family still fails."""
    assert lint('m.gauge("gofr_tpu_brownout_level", "b")\n') == []
    assert lint('m.counter("gofr_tpu_deadline_exceeded_total", "d")\n') == []
    assert lint('m.counter("gofr_tpu_cancellations_total", "c")\n') == []
    assert lint('m.counter("gofr_tpu_brownout_shed_total", "s")\n') == []
    assert rules_of(lint('m.gauge("gofr_tpu_brownout", "b")\n')) == \
        ["GFL005"]
    assert rules_of(lint('m.counter("gofr_tpu_deadline_exceeded", "d")\n')) \
        == ["GFL005"]


def test_gfl005_spec_family_covered():
    """The pooled-speculative-decoding family (tpu/spec_pool.py): the
    _ratio and _per_dispatch gauge suffixes pass; suffix drift within
    the family still fails."""
    assert lint('m.gauge("gofr_tpu_spec_accept_ratio", "a")\n') == []
    assert lint(
        'm.gauge("gofr_tpu_spec_tokens_per_dispatch", "t")\n'
    ) == []
    assert rules_of(lint('m.gauge("gofr_tpu_spec_accept", "a")\n')) == \
        ["GFL005"]
    assert rules_of(lint('m.gauge("gofr_tpu_spec_tokens", "t")\n')) == \
        ["GFL005"]


def test_gfl005_router_family_covered():
    """The gofr_tpu_router_* family (fleet/router.py) rides the same
    convention: the suffix table must keep accepting its gauges (_state,
    _depth) and rejecting drift within the family."""
    assert lint('m.gauge("gofr_tpu_router_breaker_state", "b")\n') == []
    assert lint('m.gauge("gofr_tpu_router_outstanding_depth", "o")\n') == []
    assert lint('m.counter("gofr_tpu_router_shed_total", "s")\n') == []
    assert lint('m.histogram("gofr_tpu_router_upstream_seconds", "u")\n') == []
    assert rules_of(lint('m.gauge("gofr_tpu_router_breakers", "b")\n')) == \
        ["GFL005"]
    assert rules_of(lint('m.counter("gofr_tpu_router_sheds", "s")\n')) == \
        ["GFL005"]


def test_gfl005_trace_family_covered():
    """The fleet-tracing family (PR 16): the per-hop latency histogram
    (router.py) and the zipkin exporter drop counter (tracing.py) pass;
    suffix drift within the family still fails."""
    assert lint('m.histogram("gofr_tpu_router_hop_seconds", "h")\n') == []
    assert lint(
        'm.counter("gofr_tpu_trace_export_failures_total", "z")\n'
    ) == []
    assert rules_of(lint('m.histogram("gofr_tpu_router_hop", "h")\n')) == \
        ["GFL005"]
    assert rules_of(
        lint('m.counter("gofr_tpu_trace_export_failures", "z")\n')
    ) == ["GFL005"]


def test_gfl005_engine_family_covered():
    """The engine-introspection family (tpu/introspect.py): the state
    gauge (``_state``), the dispatch counter (``_total``) and the
    dispatch histogram (``_seconds``) pass; suffix drift within the
    family still fails."""
    assert lint('m.gauge("gofr_tpu_engine_state", "s")\n') == []
    assert lint('m.counter("gofr_tpu_dispatches_total", "d")\n') == []
    assert lint('m.histogram("gofr_tpu_dispatch_seconds", "d")\n') == []
    assert rules_of(
        lint('m.gauge("gofr_tpu_engine", "s")\n')
    ) == ["GFL005"]
    assert rules_of(
        lint('m.counter("gofr_tpu_dispatches", "d")\n')
    ) == ["GFL005"]
    assert rules_of(
        lint('m.histogram("gofr_tpu_dispatch", "d")\n')
    ) == ["GFL005"]


def test_gfl005_slo_tenant_family_covered():
    """The SLO/tenant-metering family (slo.py + telemetry.TenantLedger):
    the burn-rate and budget gauges (``_rate``, ``_remaining``), the
    alert counter, and the ledger's tracked-entries gauge all pass;
    suffix drift within the family still fails."""
    assert lint('m.gauge("gofr_tpu_slo_burn_rate", "b")\n') == []
    assert lint('m.gauge("gofr_tpu_slo_budget_remaining", "b")\n') == []
    assert lint(
        'm.counter("gofr_tpu_slo_burn_alerts_total", "a")\n'
    ) == []
    assert lint(
        'm.gauge("gofr_tpu_tenants_tracked_entries", "t")\n'
    ) == []
    assert lint(
        'm.counter("gofr_tpu_tenant_overflow_total", "o")\n'
    ) == []
    assert rules_of(
        lint('m.gauge("gofr_tpu_slo_burn", "b")\n')
    ) == ["GFL005"]
    assert rules_of(
        lint('m.counter("gofr_tpu_slo_burn_alerts", "a")\n')
    ) == ["GFL005"]


# -- GFL006: swallowed exceptions ---------------------------------------------

def test_gfl006_bare_except_everywhere():
    src = "try:\n    x = 1\nexcept:\n    pass\n"
    assert rules_of(lint(src, rel="tools/x.py")) == ["GFL006"]


def test_gfl006_broad_swallow_only_in_engine_paths():
    src = "try:\n    x = 1\nexcept Exception:\n    pass\n"
    assert rules_of(lint(src, rel="gofr_tpu/tpu/x.py")) == ["GFL006"]
    assert rules_of(lint(src, rel="gofr_tpu/timebase.py")) == ["GFL006"]
    assert lint(src, rel="gofr_tpu/handler.py") == []  # request path
    narrow = "try:\n    x = 1\nexcept ValueError:\n    pass\n"
    assert lint(narrow, rel="gofr_tpu/tpu/x.py") == []
    handled = (
        "try:\n    x = 1\nexcept Exception as exc:\n    log(exc)\n"
    )
    assert lint(handled, rel="gofr_tpu/tpu/x.py") == []


def test_gfl006_suppression_sits_on_the_pass_line():
    src = (
        "try:\n    x = 1\nexcept Exception:\n"
        "    pass  # gofrlint: disable=GFL006 — last-resort guard\n"
    )
    assert lint(src, rel="gofr_tpu/tpu/x.py") == []


# -- suppression / annotation robustness --------------------------------------

def test_directives_inside_strings_are_ignored():
    src = 'x = "# gofrlint: disable=GFL002"\nimport time\nt = time.time()\n'
    assert rules_of(lint(src)) == ["GFL002"]


def test_directive_cascades_through_comment_blocks():
    src = (
        "try:\n    x = 1\nexcept Exception:\n"
        "    # gofrlint: disable=GFL006 — reason line one\n"
        "    # ...reason continued on a second line\n"
        "    pass\n"
    )
    assert lint(src, rel="gofr_tpu/tpu/x.py") == []


def test_multi_rule_suppression():
    src = (
        "import os, time\n"
        't = time.time(); x = os.getenv("K")'
        "  # gofrlint: disable=GFL001,GFL002 — fixture\n"
    )
    assert lint(src) == []


# -- output formats / CLI -----------------------------------------------------

def test_json_output_schema(tmp_path):
    bad = tmp_path / "gofr_tpu" / "mod.py"
    bad.parent.mkdir()
    bad.write_text('import os\nx = os.getenv("K")\nimport time\nt = time.time()\n')
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = gofrlint.main(["--format=json", str(tmp_path)])
    assert rc == 1
    out = json.loads(buf.getvalue())
    assert out["version"] == 1
    assert out["files_scanned"] == 1
    assert out["counts_by_rule"] == {"GFL001": 1, "GFL002": 1}
    for v in out["violations"]:
        assert set(v) == {"file", "line", "col", "rule", "message"}
        assert v["rule"] in gofrlint.RULES


def test_clean_tree_exits_zero(tmp_path):
    good = tmp_path / "ok.py"
    good.write_text("import time\nt = time.monotonic()\n")
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = gofrlint.main([str(tmp_path)])
    assert rc == 0
    assert "clean" in buf.getvalue()


def test_syntax_error_is_reported_not_crashed(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    violations, scanned = gofrlint.lint_paths([str(tmp_path)])
    assert scanned == 1
    assert [v.rule for v in violations] == ["GFL000"]


# -- the tree gate ------------------------------------------------------------

def test_the_real_tree_is_clean():
    """The acceptance contract, runnable as a test: the package, tools
    and chip_smoke.py carry zero unsuppressed violations. Same
    "only shrinks" policy as the ruff debt ledger — fix new violations
    or suppress them IN-FILE with a reason."""
    violations, scanned = gofrlint.lint_paths([
        str(REPO / "gofr_tpu"), str(REPO / "tools"),
        str(REPO / "chip_smoke.py"),
    ])
    assert scanned > 50
    assert violations == [], "\n".join(
        f"{v.path}:{v.line}: {v.rule} {v.message}" for v in violations
    )


def test_cli_entrypoint_runs(tmp_path):
    """``python tools/gofrlint.py`` stays invocable as a script (the CI
    lint job calls it exactly that way)."""
    import subprocess

    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "gofrlint.py"), str(ok)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- GFL004 interprocedural (whole-program) -----------------------------------

def interproc(sources: dict) -> list:
    """Run only the whole-program half over a {rel: source} tree."""
    project = gofrlint.Project.from_sources(sources)
    return gofrlint.WholeProgram(project).violations()


def test_interproc_direct_call():
    out = interproc({"gofr_tpu/m.py": (
        "import time, threading\n"
        "_LOCK = threading.Lock()\n"
        "def helper():\n"
        "    time.sleep(1)\n"
        "def f():\n"
        "    with _LOCK:\n"
        "        helper()\n"
    )})
    assert [v.rule for v in out] == ["GFL004"]
    assert "helper" in out[0].message and "time.sleep" in out[0].message


def test_interproc_self_method_under_foreign_lock():
    out = interproc({"gofr_tpu/m.py": (
        "import time, threading\n"
        "_LOCK = threading.Lock()\n"
        "class C:\n"
        "    def run(self):\n"
        "        with _LOCK:\n"
        "            self._drain()\n"
        "    def _drain(self):\n"
        "        time.sleep(1)\n"
    )})
    assert [v.rule for v in out] == ["GFL004"]


def test_interproc_class_typed_attribute_dispatch():
    """``self.attr.method()`` resolves through the attribute type
    inferred from the ``__init__`` assignment — the dispatch shape the
    per-file rule cannot see."""
    out = interproc({"gofr_tpu/m.py": (
        "import time, threading\n"
        "class Worker:\n"
        "    def pump(self):\n"
        "        time.sleep(1)\n"
        "class Owner:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._worker = Worker()\n"
        "    def step(self):\n"
        "        with self._lock:\n"
        "            self._worker.pump()\n"
    )})
    assert [v.rule for v in out] == ["GFL004"]
    assert "pump" in out[0].message


def test_interproc_two_hop_chain_carries_a_witness():
    out = interproc({"gofr_tpu/m.py": (
        "import time, threading\n"
        "_LOCK = threading.Lock()\n"
        "def c():\n"
        "    time.sleep(1)\n"
        "def b():\n"
        "    c()\n"
        "def a():\n"
        "    with _LOCK:\n"
        "        b()\n"
    )})
    assert [v.rule for v in out] == ["GFL004"]
    # the finding names the path, not just the endpoint
    assert "b" in out[0].message and "c" in out[0].message


def test_interproc_suppression_on_the_call_line():
    out = interproc({"gofr_tpu/m.py": (
        "import time, threading\n"
        "_LOCK = threading.Lock()\n"
        "def helper():\n"
        "    time.sleep(1)\n"
        "def f():\n"
        "    with _LOCK:\n"
        "        helper()  # gofrlint: disable=GFL004 — fixture\n"
    )})
    assert out == []


def test_interproc_resource_guard_exemption():
    """A class serializing its OWN blocking resource behind its own
    lock (the JournalWAL fsync shape) is exempt: every may-block path
    stays inside the class. The cross-object variant in the committed
    WAL fixture must still be flagged (next test)."""
    out = interproc({"gofr_tpu/m.py": (
        "import os, threading\n"
        "class Wal:\n"
        "    def __init__(self, fd):\n"
        "        self._lock = threading.Lock()\n"
        "        self._fd = fd\n"
        "    def append(self, b):\n"
        "        with self._lock:\n"
        "            os.write(self._fd, b)\n"
        "            self._sync()\n"
        "    def _sync(self):\n"
        "        os.fsync(self._fd)\n"
    )})
    assert out == []


def test_interproc_bounded_join_is_not_blocking():
    """join(timeout=...) is a bounded teardown wait — the device.py
    recovery path (reinit under _reinit_lock → teardown → pool close
    with a bounded join) must stay clean."""
    out = interproc({"gofr_tpu/m.py": (
        "import threading\n"
        "class C:\n"
        "    def close(self):\n"
        "        with self._lock:\n"
        "            self._teardown()\n"
        "    def _teardown(self):\n"
        "        self._thread.join(timeout=2.0)\n"
    )})
    assert out == []


def test_wal_under_lock_fixture_is_caught():
    """The PR 14 regression contract: the committed cross-object
    WAL-under-journal-lock fixture is flagged by the interprocedural
    pass — at the reach-through call in Journal.record, with the fsync
    chain as witness — while WalWriter's own-lock fsync (the
    resource-guard shape) is not."""
    fixture = REPO / "tests" / "fixtures" / "wal_under_lock.py"
    violations, scanned = gofrlint.lint_paths([str(fixture)])
    assert scanned == 1
    assert [v.rule for v in violations] == ["GFL004"]
    v = violations[0]
    assert "append_tokens" in v.message and "os.fsync" in v.message
    # the finding sits on Journal.record's call, not inside WalWriter
    source = fixture.read_text().splitlines()
    assert "self._wal.append_tokens" in source[v.line - 1]


# -- GFL007: metric contract registries ---------------------------------------

def run_tree(tmp_path, files: dict) -> list:
    """Materialize {rel: source} under tmp_path and run the full lint."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    violations, _ = gofrlint.lint_paths([str(tmp_path)])
    return violations


def test_gfl007_duplicate_registration_home(tmp_path):
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/a.py":
            'm.counter("gofr_tpu_x_total", "things", labels=("op",))\n',
        "gofr_tpu/b.py":
            'm.counter("gofr_tpu_x_total", "things", labels=("op",))\n',
    })
    assert [v.rule for v in out] == ["GFL007"]
    assert "duplicate registration home" in out[0].message


def test_gfl007_kind_flip_and_help_divergence(tmp_path):
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/a.py": 'm.counter("gofr_tpu_x_total", "things")\n',
        "gofr_tpu/b.py": 'm.gauge("gofr_tpu_x_total")\n',
    })
    assert "GFL007" in [v.rule for v in out]
    assert any("kind" in v.message for v in out)


def test_gfl007_label_disagreement(tmp_path):
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/a.py":
            'm.counter("gofr_tpu_x_total", "t", labels=("model",))\n',
        "gofr_tpu/b.py":
            'm.counter("gofr_tpu_x_total", labels=("op",))\n',
    })
    assert any(
        v.rule == "GFL007" and "label" in v.message for v in out
    )


def test_gfl007_lookup_sites_are_fine(tmp_path):
    """One home with help text + N help-less lookups is the sanctioned
    idiom (decode_pool.py looks up device.py's registrations)."""
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/a.py":
            'm.counter("gofr_tpu_x_total", "things", labels=("op",))\n',
        "gofr_tpu/b.py":
            'm.counter("gofr_tpu_x_total", labels=("op",))\n',
    })
    assert out == []


def test_gfl007_requires_a_naming_test_row(tmp_path):
    """With a tests/test_metric_naming.py present, every registered
    family needs a row in it — the drift-proof link between the
    registry and the convention test."""
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/a.py": 'm.counter("gofr_tpu_x_total", "t")\n',
        "tests/test_metric_naming.py": "# no rows here\n",
    })
    assert [v.rule for v in out] == ["GFL007"]
    assert "test_metric_naming" in out[0].message


# -- GFL008: config-key provenance --------------------------------------------

def test_gfl008_undeclared_package_read(tmp_path):
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/config.py": 'DECLARED_KEYS = {"GOOD_KEY": "doc"}\n',
        "gofr_tpu/m.py": (
            "from gofr_tpu.config import get_env\n"
            'x = get_env("MYSTERY_KEY")\n'
            'y = get_env("GOOD_KEY")\n'
        ),
    })
    assert [v.rule for v in out] == ["GFL008"]
    assert "MYSTERY_KEY" in out[0].message


def test_gfl008_inert_declared_knob(tmp_path):
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/config.py": 'DECLARED_KEYS = {"NEVER_READ": "doc"}\n',
    })
    assert [v.rule for v in out] == ["GFL008"]
    assert "NEVER_READ" in out[0].message and "inert" in out[0].message


def test_gfl008_wrapper_and_harness_reads_count(tmp_path):
    """A one-hop wrapper read (the fleet ``_f`` idiom) traces to the
    key; a harness-only read (tools, scripts) proves a declared key live
    but is NOT itself held to the package registry."""
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/config.py": 'DECLARED_KEYS = {"WRAPPED_KEY": "doc"}\n',
        "gofr_tpu/m.py": (
            "from gofr_tpu.config import get_env\n"
            "def _f(key, default):\n"
            "    return get_env(key) or default\n"
            'x = _f("WRAPPED_KEY", "1")\n'
        ),
        "chip_smoke.py": (
            "import os\n"
            'y = os.getenv("SMOKE_ONLY_KEY")\n'
        ),
    })
    assert out == []


# -- GFL009: admin-surface parity ---------------------------------------------

def test_gfl009_code_route_missing_from_readme(tmp_path):
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/app.py": 'app.get("/admin/newthing", handler)\n',
        "README.md": "| `/admin/other` | something |\n",
    })
    rules = [v.rule for v in out]
    assert rules.count("GFL009") == 2  # missing route AND stale row
    assert any("/admin/newthing" in v.message for v in out)
    assert any("stale" in v.message for v in out)


def test_gfl009_param_spelling_does_not_break_parity(tmp_path):
    """Code's ``{hash}`` vs the README's ``{prompt_hash}`` is the same
    route — parity guards the surface's shape, not parameter names."""
    out = run_tree(tmp_path, {
        "gofr_tpu/__init__.py": "",
        "gofr_tpu/app.py": 'app.get("/admin/kv/{hash}", handler)\n',
        "README.md": "| `/admin/kv/{prompt_hash}` | kv export |\n",
    })
    assert out == []


# -- suppression ledger ratchet -----------------------------------------------

def test_ledger_emission_and_ratchet(tmp_path):
    src = tmp_path / "gofr_tpu" / "m.py"
    src.parent.mkdir()
    src.write_text(
        "import time\n"
        "t = time.time()  # gofrlint: disable=GFL002 — fixture\n"
        "u = time.time()  # gofrlint: disable=GFL002 — fixture\n"
    )
    run = gofrlint.LintRun([str(tmp_path)])
    assert run.ledger == {"GFL002": 2}
    baseline = tmp_path / "ledger.json"
    baseline.write_text(json.dumps({"version": 1, "counts": {"GFL002": 2}}))
    assert gofrlint.check_ledger(run.ledger, str(baseline)) == []
    # ratchet: baseline of 1 means the second disable is growth
    baseline.write_text(json.dumps({"version": 1, "counts": {"GFL002": 1}}))
    errors = gofrlint.check_ledger(run.ledger, str(baseline))
    assert len(errors) == 1 and "grew" in errors[0]
    # a rule absent from the baseline is allowed zero
    baseline.write_text(json.dumps({"version": 1, "counts": {}}))
    assert len(gofrlint.check_ledger(run.ledger, str(baseline))) == 1


def test_committed_ledger_matches_the_tree():
    """The baseline in tools/gofrlint_ledger.json IS the current tree's
    ledger — the ratchet starts tight (a stale-but-loose baseline would
    let new suppressions ride in under old headroom)."""
    run = gofrlint.LintRun([
        str(REPO / "gofr_tpu"), str(REPO / "tools")
    ])
    committed = json.loads(
        (REPO / "tools" / "gofrlint_ledger.json").read_text()
    )["counts"]
    assert run.ledger == committed


# -- lock-order graph (static + merge) ----------------------------------------

def test_static_lock_graph_schema_and_edges():
    project = gofrlint.Project.from_sources({"gofr_tpu/m.py": (
        "import threading\n"
        "_a_lock = threading.Lock()\n"
        "_b_lock = threading.Lock()\n"
        "def f():\n"
        "    with _a_lock:\n"
        "        with _b_lock:\n"
        "            pass\n"
    )})
    graph = gofrlint.WholeProgram(project).lock_graph()
    assert graph["version"] == 1 and graph["source"] == "static"
    ids = {n["id"] for n in graph["nodes"]}
    assert ids == {"gofr_tpu/m.py:2", "gofr_tpu/m.py:3"}
    assert [(e["from"], e["to"]) for e in graph["edges"]] == [
        ("gofr_tpu/m.py:2", "gofr_tpu/m.py:3")
    ]


def _load_lockgraph_check():
    spec = importlib.util.spec_from_file_location(
        "lockgraph_check", REPO / "tools" / "lockgraph_check.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lockgraph_merge_finds_cross_tool_cycle(tmp_path):
    """The point of the union: A→B proved statically, B→A observed at
    runtime — a deadlock neither graph contains alone."""
    lgc = _load_lockgraph_check()
    static = {"version": 1, "source": "static", "nodes": [], "edges": [
        {"from": "gofr_tpu/a.py:10", "to": "gofr_tpu/b.py:20", "site": "s"},
    ]}
    runtime = {"version": 1, "source": "runtime", "nodes": [], "edges": [
        {"from": "/ci/work/repo/gofr_tpu/b.py:20",
         "to": "/ci/work/repo/gofr_tpu/a.py:10", "site": "r"},
    ]}
    for name, doc in (("s.json", static), ("r.json", runtime)):
        (tmp_path / name).write_text(json.dumps(doc))
    assert lgc.main(["lockgraph_check", str(tmp_path / "s.json")]) == 0
    assert lgc.main([
        "lockgraph_check", str(tmp_path / "s.json"), str(tmp_path / "r.json")
    ]) == 1


def test_lockgraph_normalization_and_self_loops():
    lgc = _load_lockgraph_check()
    assert lgc.normalize("/home/ci/repo/gofr_tpu/x.py:12") == \
        "gofr_tpu/x.py:12"
    assert lgc.normalize("gofr_tpu/x.py:12") == "gofr_tpu/x.py:12"
    assert lgc.normalize("gofr_tpu/m.py::C._lock") == "gofr_tpu/m.py::C._lock"
    # two instances created at one site collapse — the resulting
    # self-loop must NOT count as a cycle
    adj = lgc.merge([{"source": "runtime", "edges": [
        {"from": "/r/gofr_tpu/x.py:5", "to": "/r/gofr_tpu/x.py:5",
         "site": "s"},
    ]}])
    assert lgc.find_cycles(adj) == []
