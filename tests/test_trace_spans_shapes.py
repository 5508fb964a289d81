"""tools/trace_spans.py --shape on the benchmark's hand-built trace: the
operations that write a given shape, found without a scope. No chip."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "trace_spans.textproto")
POOL_CACHE = "bf16[32,6,2048,8,128]"


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(ROOT, "tools", "trace_spans.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    with open(FIXTURE, encoding="utf-8") as fh:
        raw = ProfileData.text_proto_to_serialized_xspace(fh.read())
    return ProfileData.from_serialized_xspace(raw), raw


@pytest.mark.parametrize("text, shapes", [
    ("%copy.2 = bf16[8,128]{1,0:T(8,128)(2,1)} copy(bf16[8,128]{1,0} %p)", ["bf16[8,128]"]),
    ("%f.1 = (bf16[6,8]{1,0}, f32[6,1]{1,0}) fusion(s32[6]{0} %a)", ["bf16[6,8]", "f32[6,1]"]),
    ("%t = s32[]{:T(128)} add(s32[] %a, s32[] %b)", ["s32[]"]),
    ("custom-call.7", []),
])
def test_result_shapes_of_an_instruction(tool, text, shapes):
    assert tool.result_shapes(text) == shapes


def test_shape_filter_finds_the_copy_no_scope_reaches(tool, trace):
    data, raw = trace
    (op,) = tool.summary(data, raw=raw, shapes=(POOL_CACHE,))["ops"]
    assert op["op"].startswith("copy.245 ") and op["count"] == 1
    assert op["seconds"] == pytest.approx(4e-3)
    assert op["scope"] == "jit(<lambda>)/while:" and op["source"] == ""


def test_a_shape_nothing_writes_lists_nothing(tool, trace):
    data, raw = trace
    assert tool.summary(data, raw=raw, shapes=("bf16[6,2048,8,128]",))["ops"] == []
    # the operands of an instruction are not its results
    assert tool.summary(data, raw=raw, shapes=("s32[6]",))["ops"] == []


def test_the_limit_applies_after_the_filter(tool, trace):
    data, raw = trace
    both = (POOL_CACHE, "bf16[6,32,128]")
    assert len(tool.summary(data, raw=raw, shapes=both)["ops"]) == 2
    (first,) = tool.summary(data, ops=1, raw=raw, shapes=both)["ops"]
    assert first["op"].startswith("fusion.9 ") and first["count"] == 2
