"""Contract registries — the cross-module drift rules.

GFL007  metric contract: a family has ONE registration home carrying
        its help text (every other touch point is a lookup), all
        literal label declarations agree, the kind never flips, and
        the family has a row in tests/test_metric_naming.py.
GFL008  config-key provenance: every key read through a config
        accessor is declared in config.py's DECLARED_KEYS registry,
        and every declared key is read somewhere (inert-knob
        detection — the SPEC_FAKE_ACCEPT class).
GFL009  admin-surface parity: every /admin/* route registered in code
        appears in the README route table and vice versa.

Each rule deactivates itself when its repo artifact is absent from
the scanned tree (no config.py → no GFL008), so linting a snippet
directory stays meaningful."""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Optional

from .base import Violation, src_of
from .model import Project

_UPPER_KEY_RE = re.compile(r"^[A-Z][A-Z0-9_]{2,}$")
_CONFIGISH_RE = re.compile(r"(\b|_)(config|cfg)\b", re.IGNORECASE)

# environment keys the process reads but does not own — platform
# surface, not framework config, so they need no DECLARED_KEYS entry
_EXTERNAL_KEYS = {
    "HOME", "PATH", "PWD", "TMPDIR", "XDG_CACHE_HOME", "JAX_PLATFORMS",
}

# regexes for the auxiliary read scan over tests/ (read-evidence only:
# a test SETTING a key does not make the knob live)
_AUX_READ_RES = (
    re.compile(
        r"(?:get_env|env_flag|get_or_default|getenv|environ\.get)\(\s*"
        r"['\"]([A-Z][A-Z0-9_]{2,})['\"]"
    ),
    re.compile(r"environ\[\s*['\"]([A-Z][A-Z0-9_]{2,})['\"]\]"),
)

_ROUTE_METHODS = {"add", "get", "post", "put", "delete", "add_route", "route"}
_README_ROUTE_RE = re.compile(r"`(/admin/[^`\s]*)`")


def _norm_route(path: str) -> str:
    return re.sub(r"<([^>]+)>", r"{\1}", path.rstrip("/")) or "/"


def _route_key(path: str) -> str:
    # parity is about the SHAPE of the surface, not parameter spelling:
    # code's /admin/kv/{hash} and the README's /admin/kv/{prompt_hash}
    # are the same route
    return re.sub(r"\{[^}]*\}", "{}", _norm_route(path))


def _suppressed(project: Project, rel: str, rule: str, line: int) -> bool:
    mod = project.modules.get(rel)
    return bool(mod and mod.directives.suppressed(rule, line))


# -- GFL007: metric contract --------------------------------------------------

class _MetricSite:
    __slots__ = ("name", "kind", "help", "has_help", "labels", "rel", "line")

    def __init__(self, name, kind, help_, has_help, labels, rel, line):
        self.name = name
        self.kind = kind
        self.help = help_        # str | None (None = dynamic/absent)
        self.has_help = has_help
        self.labels = labels     # sorted tuple | None (None = dynamic/absent)
        self.rel = rel
        self.line = line


def _metric_sites(project: Project) -> dict[str, list[_MetricSite]]:
    families: dict[str, list[_MetricSite]] = {}
    for rel, mod in project.modules.items():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (isinstance(fn, ast.Attribute) and
                    fn.attr in ("counter", "gauge", "histogram")):
                continue
            if not node.args or not (
                isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                continue
            name = node.args[0].value
            if not name.startswith("gofr_"):
                continue
            help_, has_help = None, False
            if len(node.args) >= 2:
                has_help = True
                if isinstance(node.args[1], ast.Constant) and \
                        isinstance(node.args[1].value, str):
                    help_ = node.args[1].value
            labels: Optional[tuple] = None
            for kw in node.keywords:
                if kw.arg in ("help_", "help"):
                    has_help = True
                    if isinstance(kw.value, ast.Constant) and \
                            isinstance(kw.value.value, str):
                        help_ = kw.value.value
                elif kw.arg == "labels" and isinstance(
                    kw.value, (ast.Tuple, ast.List)
                ):
                    elts = kw.value.elts
                    if all(isinstance(e, ast.Constant) and
                           isinstance(e.value, str) for e in elts):
                        labels = tuple(sorted(e.value for e in elts))
            families.setdefault(name, []).append(_MetricSite(
                name, fn.attr, help_, has_help, labels, rel, node.lineno,
            ))
    return families


def check_metrics(project: Project, root: Path) -> list[Violation]:
    out: list[Violation] = []
    naming_test = root / "tests" / "test_metric_naming.py"
    naming_text = ""
    if naming_test.is_file():
        try:
            naming_text = naming_test.read_text(encoding="utf-8")
        except OSError:
            pass
    for name, sites in sorted(_metric_sites(project).items()):
        sites.sort(key=lambda s: (s.rel, s.line))

        def flag(site, message, name=name):
            if not _suppressed(project, site.rel, "GFL007", site.line):
                out.append(Violation(
                    "GFL007", site.rel, site.line, 0,
                    f"metric {name!r}: {message}",
                ))

        first = sites[0]
        for site in sites[1:]:
            if site.kind != first.kind:
                flag(site, f"registered as a {first.kind} at "
                           f"{first.rel}:{first.line} but as a "
                           f"{site.kind} here — the registry keeps the "
                           "first kind and this site reads the wrong "
                           "shape")
        helped = [s for s in sites if s.has_help and s.help]
        for site in helped[1:]:
            if site.help != helped[0].help:
                flag(site, "help text diverges from the registration "
                           f"home at {helped[0].rel}:{helped[0].line} "
                           "— registration order decides which string "
                           "serves, silently")
            else:
                flag(site, "duplicate registration home (same help "
                           f"declared at {helped[0].rel}:"
                           f"{helped[0].line}) — keep ONE home and "
                           "make other touch points lookups, or the "
                           "copies drift apart")
        labeled = [s for s in sites if s.labels is not None]
        for site in labeled[1:]:
            if site.labels != labeled[0].labels:
                flag(site, f"labels {site.labels} disagree with "
                           f"{labeled[0].labels} declared at "
                           f"{labeled[0].rel}:{labeled[0].line}")
        if naming_text and f'"{name}"' not in naming_text:
            home = helped[0] if helped else first
            flag(home, "no row in tests/test_metric_naming.py — add "
                       "the family to the known-registrations sweep so "
                       "a refactor cannot silently drop it")
    return out


# -- GFL008: config-key provenance --------------------------------------------

def _declared_keys(mod) -> Optional[dict[str, int]]:
    for stmt in mod.tree.body:
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
            value = stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets = [stmt.target]
            value = stmt.value
        else:
            continue
        if not any(isinstance(t, ast.Name) and t.id == "DECLARED_KEYS"
                   for t in targets):
            continue
        if not isinstance(value, ast.Dict):
            return {}
        out = {}
        for key in value.keys:
            if isinstance(key, ast.Constant) and isinstance(key.value, str):
                out[key.value] = key.lineno
        return out
    return None


def _is_read_call(call: ast.Call) -> bool:
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id in ("get_env", "env_flag")
    if not isinstance(fn, ast.Attribute):
        return False
    if fn.attr in ("get_env", "env_flag"):
        return True
    if fn.attr == "getenv":
        return isinstance(fn.value, ast.Name) and fn.value.id == "os"
    if fn.attr in ("get", "get_or_default"):
        receiver = src_of(fn.value)
        if receiver == "os.environ":
            return fn.attr == "get"
        return bool(_CONFIGISH_RE.search(receiver))
    return False


def _key_reads(project: Project) -> dict[str, list[tuple[str, int]]]:
    """key -> [(rel, line), ...] across every scanned module, including
    one-hop wrappers (a function whose first parameter feeds a config
    accessor — the fleet ``_f``/``_i`` idiom)."""
    reads: dict[str, list[tuple[str, int]]] = {}

    def record(key: str, rel: str, line: int) -> None:
        if _UPPER_KEY_RE.match(key):
            reads.setdefault(key, []).append((rel, line))

    for rel, mod in project.modules.items():
        wrappers: set[str] = set()
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            params = node.args.args
            if not params:
                continue
            first = params[0].arg
            if first == "self" and len(params) > 1:
                first = params[1].arg
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) and _is_read_call(sub) and \
                        sub.args and isinstance(sub.args[0], ast.Name) and \
                        sub.args[0].id == first:
                    wrappers.add(node.name)
                    break
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            arg0 = node.args[0] if node.args else None
            literal = (
                arg0.value
                if isinstance(arg0, ast.Constant) and
                isinstance(arg0.value, str) else None
            )
            if literal is None:
                continue
            if _is_read_call(node):
                record(literal, rel, node.lineno)
            elif isinstance(node.func, ast.Name) and \
                    node.func.id in wrappers:
                record(literal, rel, node.lineno)
            # os.environ["KEY"] reads are Subscripts, handled below
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Subscript) and \
                    src_of(node.value) == "os.environ" and \
                    isinstance(node.ctx, ast.Load) and \
                    isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                record(node.slice.value, rel, node.lineno)
    return reads


def _aux_reads(root: Path) -> set[str]:
    """Read-evidence from the tests tree (e.g. GOFR_SANITIZE_REPORT is
    consumed only by tests/conftest.py) — enough to prove a declared
    knob live, never enough to excuse an undeclared package read."""
    found: set[str] = set()
    tests_dir = root / "tests"
    if not tests_dir.is_dir():
        return found
    for path in sorted(tests_dir.rglob("*.py")):
        try:
            text = path.read_text(encoding="utf-8")
        except (OSError, UnicodeDecodeError):
            continue
        for pattern in _AUX_READ_RES:
            found.update(pattern.findall(text))
    return found


def check_config_keys(project: Project, root: Path) -> list[Violation]:
    config_mod = None
    for rel, mod in project.modules.items():
        parts = Path(rel).parts
        if Path(rel).name == "config.py" and "gofr_tpu" in parts:
            config_mod = mod
            break
    if config_mod is None:
        return []
    out: list[Violation] = []
    declared = _declared_keys(config_mod)
    if declared is None:
        return [Violation(
            "GFL008", config_mod.rel, 1, 0,
            "config.py declares no DECLARED_KEYS registry — the "
            "config surface has no provenance anchor",
        )]
    reads = _key_reads(project)
    # provenance is a PACKAGE contract: a read inside the gofr_tpu
    # package must trace to DECLARED_KEYS; harness knobs (tools/) are out
    # of the package's config surface, though their reads still prove
    # a declared key live below
    pkg_prefix = str(Path(config_mod.rel).parent).replace("\\", "/") + "/"
    for key in sorted(reads):
        if key in declared or key in _EXTERNAL_KEYS:
            continue
        pkg_sites = sorted(
            s for s in reads[key] if s[0].startswith(pkg_prefix)
        )
        if not pkg_sites:
            continue
        rel, line = pkg_sites[0]
        if _suppressed(project, rel, "GFL008", line):
            continue
        out.append(Violation(
            "GFL008", rel, line, 0,
            f"config key {key!r} is read here but not declared in "
            "config.py DECLARED_KEYS — declare and document it (or it "
            "is invisible to operators)",
        ))
    aux = _aux_reads(root)
    for key, line in sorted(declared.items()):
        if key in reads or key in aux:
            continue
        if _suppressed(project, config_mod.rel, "GFL008", line):
            continue
        out.append(Violation(
            "GFL008", config_mod.rel, line, 0,
            f"declared config key {key!r} is never read in the scanned "
            "tree — an inert knob (the SPEC_FAKE_ACCEPT class): wire "
            "it or delete the declaration",
        ))
    return out


# -- GFL009: admin-surface parity ---------------------------------------------

def _code_routes(project: Project) -> dict[str, tuple[str, int]]:
    routes: dict[str, tuple[str, int]] = {}
    for rel, mod in project.modules.items():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (isinstance(fn, ast.Attribute) and
                    fn.attr in _ROUTE_METHODS):
                continue
            for arg in node.args[:3]:
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str) and \
                        arg.value.startswith("/admin/"):
                    routes.setdefault(
                        _norm_route(arg.value), (rel, node.lineno)
                    )
                    break
    return routes


def check_admin_routes(project: Project, root: Path) -> list[Violation]:
    readme = root / "README.md"
    if not readme.is_file():
        return []
    try:
        text = readme.read_text(encoding="utf-8")
    except OSError:
        return []
    routes = _code_routes(project)
    if not routes:
        return []  # partial scan with no registration sites in view
    documented: set[str] = set()
    claimed: dict[str, int] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        for raw in _README_ROUTE_RE.findall(line):
            path = _norm_route(raw)
            documented.add(_route_key(path))
            if line.lstrip().startswith("|"):
                claimed.setdefault(path, lineno)
    code_keys = {_route_key(p) for p in routes}
    out: list[Violation] = []
    for path, (rel, line) in sorted(routes.items()):
        if _route_key(path) in documented:
            continue
        if _suppressed(project, rel, "GFL009", line):
            continue
        out.append(Violation(
            "GFL009", rel, line, 0,
            f"admin route '{path}' is registered here but missing from "
            "the README route table — operators discover the admin "
            "plane from that table",
        ))
    for path, lineno in sorted(claimed.items()):
        if _route_key(path) in code_keys:
            continue
        out.append(Violation(
            "GFL009", str(readme), lineno, 0,
            f"README route table lists '{path}' but no registration "
            "for it exists in the scanned tree — stale row",
        ))
    return out


def contract_violations(project: Project, root: Path) -> list[Violation]:
    out = check_metrics(project, root)
    out.extend(check_config_keys(project, root))
    out.extend(check_admin_routes(project, root))
    return out
