"""Where the benchmark's data lives and how a name becomes a file.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration and
a traffic mix. Each is a file found by name, so a later PR adds a cell, a
configuration, an architecture, a mix or a per-layer metric with new files
and new entries and edits nothing that is there:

- ``configs/<config>.json``       sizes as run, source, reduced, assumed,
                                  and its ``architecture`` (no default)
- ``architectures/<name>.py``     what a model IS: leaves, weights, the seam
                                  into the program, the plain reference
- ``mixes/<traffic>.json``        lengths, arrivals, loop, limits
- ``cells/<cell>.json``           the cell's fixed load (rate or clients)
- ``layer_metrics/<metric>.py``   one reader: ``read(run) -> float | None``
- ``kernels/<kernel>.py``         operations and bytes of one program

What every architecture shares and none owns stays in plain modules:
``weights.py`` (seeded noise, a leaf from shape, fan-in, quant, dtype and
id), ``reference.py`` (``rms``, ``rope``, ``degrade_weight``, ``pack``,
``served_gaps``), ``model_work.py`` (the dense decoder's work sheets).

**The contract of an architecture module** (``ARCHITECTURE_CONTRACT``). It
is what ``run.py`` calls and nothing else; ``load_architecture`` refuses a
module that lacks one. Only an architecture module imports the program's
model code.

- ``sizes_of(cfg) -> dict``: the sizes the module and its work sheets need,
  from the configuration file's keys; kept as ``run.sizes``. It carries
  ``vocab``: the traffic generator draws token ids below it.
- ``make_params(seed, sz)``: the served tree, in one jitted call from the
  seed. The values the module's reference takes come from the same leaf
  function, so neither takes what the other made.
- ``register(run) -> str``: puts the configuration into the program's table
  and the seeded tree in place of the program's init, and returns the
  ``MODEL_NAME`` to serve. It reads ``run.cfg``, ``run.sizes``, ``run.seed``
  and ``run.log``, and raises ``SpecError`` for a configuration the program
  cannot serve as stated.
- ``logits_at(seed, cfg, blocks, mode=None)``: the plain forward pass
  (float32, ``jax.default_matmul_precision("highest")``, one layer resident,
  no cache): per block ``(tokens [S, T], rows, cols)`` the logits ``[N, V]``
  at those positions; ``mode`` is the control's lower precision.
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
ARCHITECTURE_CONTRACT = ("sizes_of", "make_params", "register", "logits_at")


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
            if not isinstance(cfg.get("architecture"), str):
                raise SpecError(f"{entry['file']} names no \"architecture\" "
                                "(a file under architectures/; there is no default)")
            cfg["_name"] = name
            return cfg
    raise SpecError(f"no configuration {name!r} in the manifest")


def load_mix(manifest: dict, traffic: str) -> dict:
    return load_json(os.path.join(_data_dir(manifest), "mixes", traffic + ".json"))


def load_cell_load(manifest: dict, cell: str) -> dict:
    return load_json(os.path.join(_data_dir(manifest), "cells", cell + ".json"))


def load_module(kind: str, name: str, base: str = HERE) -> Any:
    """``<kind>/<name>.py`` (a reader, a work sheet, an architecture) as a
    module of its own. Names carry dots, so the file is loaded by path, not
    by import."""
    path = os.path.join(base, kind, name + ".py")
    if not NAME_RE.match(name) or not os.path.isfile(path):
        raise SpecError(f"{kind}/{name}.py does not exist")
    safe = re.sub(r"[^A-Za-z0-9_]", "_", f"benchmark_{kind}_{name}")
    spec = importlib.util.spec_from_file_location(safe, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_architecture(manifest: dict, cfg: dict) -> Any:
    """The module a configuration's ``architecture`` names. A rehearsal
    manifest finds it under its own data directory first, as it finds its
    mixes and cells."""
    name, data = cfg["architecture"], _data_dir(manifest)
    local = os.path.isfile(os.path.join(data, "architectures", name + ".py"))
    module = load_module("architectures", name, data if local else HERE)
    missing = [n for n in ARCHITECTURE_CONTRACT if not callable(getattr(module, n, None))]
    if missing:
        raise SpecError(f"architectures/{name}.py lacks {', '.join(missing)}")
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
