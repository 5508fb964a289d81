"""Where the benchmark's data lives and how a name becomes a file.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration and
a traffic mix. Each is a file found by name, so a later PR adds a cell, a
configuration, a mix or a per-layer metric with new files and new entries
and edits nothing that is there:

- ``configs/<config>.json``       sizes as run, source, reduced, assumed
- ``mixes/<traffic>.json``        lengths, arrivals, loop, limits
- ``cells/<cell>.json``           the cell's fixed load (rate or clients)
- ``layer_metrics/<metric>.py``   one reader: ``read(run) -> float | None``
- ``kernels/<kernel>.py``         operations and bytes of one program
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(Exception):
    """The manifest or one of the files it names is wrong."""


def load_json(path: str) -> Any:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_manifest(path: str | None = None) -> dict:
    """``BENCHMARK.json`` (or a rehearsal manifest of the same shape)."""
    manifest = load_json(path or os.path.join(ROOT, "BENCHMARK.json"))
    manifest["_dir"] = os.path.dirname(os.path.abspath(path)) if path else ROOT
    return manifest


def _data_dir(manifest: dict) -> str:
    # the real manifest sits at the root and its data under benchmark/;
    # a rehearsal manifest keeps its data beside itself
    return HERE if manifest["_dir"] == ROOT else manifest["_dir"]


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in manifest["workloads"])
    raise SpecError(f"no workload {name!r} in the manifest (has: {known})")


def load_config(manifest: dict, name: str) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            path = os.path.join(manifest["_dir"], entry["file"])
            cfg = load_json(path)
            cfg["_name"] = name
            return cfg
    raise SpecError(f"no configuration {name!r} in the manifest")


def load_mix(manifest: dict, traffic: str) -> dict:
    return load_json(os.path.join(_data_dir(manifest), "mixes", traffic + ".json"))


def load_cell_load(manifest: dict, cell: str) -> dict:
    return load_json(os.path.join(_data_dir(manifest), "cells", cell + ".json"))


def load_module(kind: str, name: str) -> Any:
    """``layer_metrics/<name>.py`` or ``kernels/<name>.py`` as a module.
    Names carry dots, so the file is loaded by path, not by import."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"{kind}/{name}.py does not exist")
    safe = re.sub(r"[^A-Za-z0-9_]", "_", f"benchmark_{kind}_{name}")
    spec = importlib.util.spec_from_file_location(safe, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of_cell(manifest: dict, cell: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports. A metric
    without ``workloads`` belongs to every cell; a per-layer metric then
    only to cells that report the end-to-end metric it moves."""
    e2e = [
        m for m in manifest["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    ]
    if section == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in manifest["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
