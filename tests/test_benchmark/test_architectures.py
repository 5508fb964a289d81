"""The architecture as a file (``benchmark/architectures/<name>.py``): the
contract every configuration's module keeps, the values of ``dense_gqa``
frozen from before it was a file, and a second architecture added to a
copy of the rehearsal data as files only. No chip."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import spec

MANIFEST = spec.load_manifest()
REHEARSE_DIR = os.path.join(spec.HERE, "fixtures", "rehearse")
REHEARSAL = spec.load_manifest(os.path.join(REHEARSE_DIR, "BENCHMARK.json"))
CONFIGS = [(m, e["name"]) for m in (MANIFEST, REHEARSAL) for e in m["configs"]]


# -- (a) the contract --------------------------------------------------------------

@pytest.mark.parametrize("manifest,name", CONFIGS, ids=lambda v: v if isinstance(v, str) else "")
def test_every_configuration_names_an_architecture_that_keeps_the_contract(manifest, name):
    cfg = spec.load_config(manifest, name)
    module = spec.load_architecture(manifest, cfg)
    assert spec.ARCHITECTURE_CONTRACT == ("sizes_of", "make_params", "register", "logits_at")
    for attr in spec.ARCHITECTURE_CONTRACT:
        assert callable(getattr(module, attr))
    assert module.sizes_of(cfg)["vocab"] == cfg["vocab_size"]  # the traffic draws ids below it


def _copy_of_rehearsal(tmp_path, edit):
    data = tmp_path / "rehearse"
    shutil.copytree(REHEARSE_DIR, data)
    edit(data)
    return spec.load_manifest(str(data / "BENCHMARK.json"))


def test_a_configuration_without_the_key_is_refused(tmp_path):
    def drop(data):
        cfg = spec.load_json(str(data / "tiny-rehearse.json"))
        del cfg["architecture"]
        (data / "tiny-rehearse.json").write_text(json.dumps(cfg))

    with pytest.raises(spec.SpecError, match="architecture"):
        spec.load_config(_copy_of_rehearsal(tmp_path, drop), "tiny-rehearse")


@pytest.mark.parametrize("source,why", [
    (None, "does not exist"),
    ("sizes_of = make_params = register = len\n", "lacks logits_at"),
])
def test_a_missing_or_short_architecture_module_is_refused(tmp_path, source, why):
    def add(data):
        if source is not None:
            os.makedirs(data / "architectures")
            (data / "architectures" / "other.py").write_text(source)

    manifest = _copy_of_rehearsal(tmp_path, add)
    cfg = dict(spec.load_config(manifest, "tiny-rehearse"), architecture="other")
    with pytest.raises(spec.SpecError, match=why):
        spec.load_architecture(manifest, cfg)


def test_only_an_architecture_module_imports_the_programs_models():
    hits = []
    for folder, _, files in os.walk(spec.HERE):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith((".py", ".json")) and "gofr_tpu.models" in open(path, encoding="utf-8").read():
                hits.append(os.path.relpath(path, spec.HERE))
    assert hits and all(h.startswith("architectures" + os.sep) for h in hits), hits


# -- (b) frozen values ------------------------------------------------------------------
# Recorded from the parent commit f490e8f (PR 26), where this code was
# benchmark/weights.py and benchmark/reference.py: W.make_params and
# reference.logits_at at the rehearsal's tiny shape with serving dtype
# bfloat16, seed 2147483659, on this CPU backend. The move may change no bit
# of a weight and no digit of a logit.

FROZEN = {
    "": {
        "params": "b57fab4d09cd41c029b0a378f720933dd23e6ab5d9205ec3bcac8aa32965af0b",
        None: ("3582c0d560ca77a9d0bf1355720b5fbc1fd207e6af47e266da89ec4ce19ac3bb",
               [0.046468332409858704, -0.09670286625623703, 0.9504573941230774], -43.049923570943065),
        "int8": ("2d9bc4bade142eefcf88fad6d5b20acaca95a0eb859de8f70bff21f4a0870a42",
                 [0.05146481469273567, -0.10773862153291702, 0.9489673972129822], -41.971868509892374),
    },
    "int8": {
        "params": "c5d942dfdb60c0bfe0ffa70317fdcd7a79701496e574a3a89ba2c32cb7554dfc",
        None: ("b5ee6138ce2ff188c0688ea3887c405e7b9d7e93e6b38ee84a6f2f961eb9d8af",
               [0.12363797426223755, -0.24458713829517365, 1.0571861267089844], -37.73444899299648),
        "int4": ("88a0f60c9db8be8be98dc30f4f5f5f7164d3a2e8d1b20b63fa0a0bc795c64191",
                 [0.1392563283443451, -0.7130427360534668, 1.0345226526260376], -36.64876557816751),
    },
}
FROZEN_SEED = 2147483659


def _tiny(quant):
    cfg = spec.load_json(os.path.join(REHEARSE_DIR, "tiny-rehearse.json"))
    cfg["serving"] = dict(cfg["serving"], quant=quant, dtype="bfloat16")
    return cfg


def _tree_digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("quant", ["", "int8"], ids=["bf16", "int8"])
def test_make_params_gives_the_bytes_recorded_before_the_move(quant):
    arch = spec.load_module("architectures", "dense_gqa")
    tree = arch.make_params(FROZEN_SEED, arch.sizes_of(_tiny(quant)))
    assert _tree_digest(tree) == FROZEN[quant]["params"]


@pytest.mark.parametrize("quant,mode", [("", None), ("", "int8"), ("int8", None), ("int8", "int4")],
                         ids=["bf16", "bf16-control-int8", "int8", "int8-control-int4"])
def test_logits_at_gives_the_float32_values_recorded_before_the_move(quant, mode):
    arch = spec.load_module("architectures", "dense_gqa")
    tokens = (np.arange(2 * 24, dtype=np.int32).reshape(2, 24) * 7 + 3) % 256
    block = (tokens, np.array([0, 0, 1, 1]), np.array([5, 23, 0, 17]))
    (logits,) = list(arch.logits_at(FROZEN_SEED, _tiny(quant), [block], mode))
    got = np.asarray(logits)
    digest, first, total = FROZEN[quant][mode]
    assert got.dtype == np.float32 and got.shape == (4, 256)
    assert got[0, :3].tolist() == first and float(got.astype(np.float64).sum()) == total
    assert hashlib.sha256(got.tobytes()).hexdigest() == digest


# -- (c) a second architecture, added as files only ---------------------------------------

TWIN = '''"""The dense block under another name: other leaf ids, so other weights."""
from benchmark import spec

_dense = spec.load_module("architectures", "dense_gqa")  # a copy of its own
_dense.LEAF_IDS = {name: i + 100 for name, i in _dense.LEAF_IDS.items()}
sizes_of, make_params, register = _dense.sizes_of, _dense.make_params, _dense.register
logits_at = %s
'''
OWN_REFERENCE = "_dense.logits_at"
DENSE_GQA_REFERENCE = 'spec.load_module("architectures", "dense_gqa").logits_at'
SEED = 27


@pytest.fixture(scope="module")
def with_twins(tmp_path_factory):
    """A copy of the rehearsal data with two architectures, a configuration
    and a cell of each ADDED: new files, and new entries in the manifest."""
    data = tmp_path_factory.mktemp("data") / "rehearse"
    shutil.copytree(REHEARSE_DIR, data)
    before = {p: p.read_bytes() for p in data.rglob("*") if p.is_file() and p.name != "BENCHMARK.json"}
    manifest = spec.load_json(str(data / "BENCHMARK.json"))
    os.makedirs(data / "architectures")
    for arch, reference in (("dense_twin", OWN_REFERENCE), ("dense_twin_wrong", DENSE_GQA_REFERENCE)):
        (data / "architectures" / f"{arch}.py").write_text(TWIN % reference)
        cfg = spec.load_json(str(data / "tiny-rehearse.json"))
        cfg["architecture"] = arch
        (data / f"{arch}.json").write_text(json.dumps(cfg))
        shutil.copy(data / "cells" / "tiny.open.json", data / "cells" / f"{arch}.open.json")
        manifest["configs"].append({"name": arch, "source": "tests", "file": f"{arch}.json",
                                    "reduced": [], "why": "an architecture added as files"})
        manifest["workloads"].append({"name": f"{arch}.open", "config": arch,
                                      "traffic": "rehearse-open", "chips": 1, "why": "as tiny.open"})
        manifest["end_to_end"][0]["workloads"].append(f"{arch}.open")
    (data / "BENCHMARK.json").write_text(json.dumps(manifest))
    yield str(data / "BENCHMARK.json")
    assert all(p.read_bytes() == raw for p, raw in before.items()), "a file that was there changed"


def _rehearse(manifest, workload):
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--rehearse", manifest, "--workload", workload,
         "--seed", str(SEED), "--seconds", "3", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    records = spec.load_json(os.path.join(spec.HERE, "out", f"{workload}.{SEED}.0.records.json"))
    served = {r["id"]: r["tokens"] for r in records["records"]}
    return json.loads(proc.stdout.strip().splitlines()[-1]), served


def test_an_architecture_added_as_files_is_served_and_checked_by_its_own_reference(with_twins):
    result, served = _rehearse(with_twins, "dense_twin.open")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert list(result)[-1] == "check" and result["check"][0]["agree_share"] == 1.0
    # its weights came from the new module: other tokens than tiny-rehearse serves
    base, base_served = _rehearse(with_twins, "tiny.open")
    assert base["correct"] is True
    assert served.keys() == base_served.keys()
    assert [len(t) for t in served.values()] == [len(t) for t in base_served.values()]
    assert served != base_served


def test_with_another_architectures_reference_the_same_cell_is_not_correct(with_twins):
    """No call goes round the seam: the reference that decides ``correct``
    is the one the configuration's architecture module gives."""
    result, _ = _rehearse(with_twins, "dense_twin_wrong.open")
    assert result["correct"] is False and result["failed"] == 0
    assert any(n["value"] > n["limit"] for n in result["check"])


# -- (d) every per-layer metric says where it is read (the cells' lists: test_manifest_floors.py) --

def test_every_per_layer_metric_lists_its_cells():
    """A cell a later PR adds inherits no reader written for another model:
    it appends its name where the reader holds for it."""
    cells = {c["name"] for c in MANIFEST["workloads"]}
    for metric in MANIFEST["per_layer"]:
        assert metric.get("workloads") and set(metric["workloads"]) <= cells, metric["name"]
