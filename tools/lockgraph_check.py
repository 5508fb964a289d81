#!/usr/bin/env python3
"""Cycle check over the UNION of lock-order graphs.

Two producers emit the same schema (``{"version": 1, "source": ...,
"nodes": [{"id"}], "edges": [{"from", "to", "site"}]}``):

- static: ``python tools/gofrlint.py --emit-lock-graph static.json ...``
  — acquisition edges the whole-program pass can PROVE from the source
  (including interprocedural ones: a call made under lock A to a
  function that may take B).
- runtime: the concurrency sanitizer's observed graph
  (``GOFR_SANITIZE_GRAPH=<file>`` under the test suite, or
  ``tools/fleetsim.py --emit-graph`` under fleet chaos load) — edges
  that actually happened in some interleaving.

Each alone has blind spots: the static graph can't see lock use behind
dynamic dispatch it can't resolve, the runtime graph only sees
interleavings that ran. A cycle in the MERGED graph — e.g. A→B proved
statically, B→A observed at runtime in a path the linter can't type —
is a deadlock neither tool finds alone, so CI fails on it.

Node identity is the lock CREATION SITE. Runtime labels carry absolute
paths; they are normalized to repo-relative here before the merge.
Self-loops after normalization are dropped: two instances of the same
class taken in sequence collapse to one site, and site granularity
cannot order instances (an address-ordered hierarchy would be the fix,
not a report here).

Usage::

    python tools/lockgraph_check.py static.json [runtime.json ...]

Exit 0 when the merged graph is acyclic, 1 when a cycle exists (each
cycle printed with the edges' provenance), 2 on unreadable input.
"""

from __future__ import annotations

import json
import sys

# path components that anchor a repo-relative spelling inside an
# absolute one — everything before the LAST occurrence is machine-local
_ROOTS = ("gofr_tpu", "tests", "tools")


def normalize(node: str) -> str:
    """``/home/ci/repo/gofr_tpu/x.py:12`` -> ``gofr_tpu/x.py:12``;
    repo-relative and synthetic (``rel::Class.attr``) ids unchanged."""
    if "::" in node:
        return node
    path, sep, line = node.rpartition(":")
    if not sep or not line.isdigit():
        path, line = node, ""
    parts = path.replace("\\", "/").split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in _ROOTS:
            path = "/".join(parts[i:])
            break
    return f"{path}:{line}" if line else path


def load_graph(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "edges" not in doc:
        raise ValueError(f"{path}: not a lock-graph document")
    return doc


def merge(graphs: list[dict]) -> dict[str, dict[str, dict]]:
    """adjacency: from -> {to -> provenance edge dict}."""
    adj: dict[str, dict[str, dict]] = {}
    for doc in graphs:
        source = doc.get("source", "?")
        for edge in doc["edges"]:
            a = normalize(edge["from"])
            b = normalize(edge["to"])
            if a == b:
                continue  # site-granularity alias (see module docstring)
            info = dict(edge)
            info["source"] = source
            adj.setdefault(a, {}).setdefault(b, info)
            adj.setdefault(b, {})
    return adj


def find_cycles(adj: dict[str, dict[str, dict]]) -> list[list[str]]:
    """Tarjan SCCs; every SCC with more than one node (or a 2-cycle
    within it) is an ordering violation. Iterative — graph size is
    bounded by lock count, but recursion limits are not a failure mode
    a checker should have."""
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    sccs: list[list[str]] = []
    counter = [0]

    for root in sorted(adj):
        if root in index:
            continue
        work = [(root, iter(sorted(adj[root])))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        on_stack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in index:
                    index[nxt] = low[nxt] = counter[0]
                    counter[0] += 1
                    stack.append(nxt)
                    on_stack.add(nxt)
                    work.append((nxt, iter(sorted(adj[nxt]))))
                    advanced = True
                    break
                if nxt in on_stack:
                    low[node] = min(low[node], index[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    member = stack.pop()
                    on_stack.discard(member)
                    scc.append(member)
                    if member == node:
                        break
                if len(scc) > 1:
                    sccs.append(sorted(scc))
    return sccs


def main(argv: list[str] | None = None) -> int:
    args = (argv if argv is not None else sys.argv)[1:]
    if not args:
        print(__doc__, file=sys.stderr)
        return 2
    graphs = []
    for path in args:
        try:
            graphs.append(load_graph(path))
        except (OSError, ValueError, json.JSONDecodeError) as exc:
            print(f"lockgraph_check: {exc}", file=sys.stderr)
            return 2
    adj = merge(graphs)
    n_edges = sum(len(v) for v in adj.values())
    cycles = find_cycles(adj)
    if not cycles:
        print(
            f"lockgraph_check: OK — {len(adj)} locks, {n_edges} ordered "
            f"edges across {len(graphs)} graph(s), no cycles"
        )
        return 0
    for scc in cycles:
        print(f"lockgraph_check: CYCLE among {len(scc)} lock(s):")
        members = set(scc)
        for a in scc:
            for b, info in sorted(adj.get(a, {}).items()):
                if b in members:
                    print(
                        f"  {a} -> {b}  [{info.get('source', '?')}"
                        f" @ {info.get('site', '?')}]"
                    )
    print(
        "lockgraph_check: a static∪runtime cycle is a deadlock neither "
        "tool proves alone — fix the acquisition order",
        file=sys.stderr,
    )
    return 1


if __name__ == "__main__":
    sys.exit(main())
