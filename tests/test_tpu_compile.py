"""Compile the cached forward for a described TPU v5e (no chip attached)
and read what the chip's compiler made of the KV cache: nothing copies,
reshapes, transposes or slices it, inside the program's loops or where the
cache enters and leaves; the Pallas kernel reads the buffer the program
was handed, in the order it is stored. What the CPU backend and interpret
mode cannot show. A compile is not a run: no time is read here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU's library, and every xdist worker
imports every test file."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from gofr_tpu.models import transformer as T

# InternLM2-1.8B's widths and depth (benchmark/configs) with a small
# vocabulary (the sampler is most of the compile; the layers are one
# scanned body, so depth costs nothing here). The cache has the size it
# has on the chip: a cache of two layers the compiler parks in another
# memory space and fetches back, which no deployment's cache fits
CFG = T.TransformerConfig(
    vocab_size=4096, dim=2048, n_layers=24, n_heads=16, n_kv_heads=8,
    hidden_dim=8192, max_seq=2048, rope_theta=1e6,
)
SLOTS = 12
_MOVERS = ("copy", "reshape", "transpose", "dynamic-slice", "fusion", "slice",
           "copy-done", "slice-done")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """``attention()`` asks the default backend whether the Pallas path
    runs and whether to interpret it: answer as the chip would. The
    server's matmul precision, not the ``highest`` that conftest.py sets
    for CPU numerics (Mosaic takes no fp32 contraction of bf16)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision("default"):
        yield


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _compiled(fn, donate, one_chip, *trees):
    args = [_shapes(jax.eval_shape(lambda t=t: t() if callable(t) else t), one_chip)
            for t in trees]
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def texts() -> dict[tuple, str]:
    """Compiled text by (program, configuration): the cases that read what
    a program does with its WEIGHTS read the text the case about its cache
    compiled (one file, one worker, one process: the module's docstring)."""
    return {}


def _pooled_chunk(texts, one_chip, name, cfg, slots, params):
    """The pooled decode chunk of 8 steps over ``slots`` rows, as the pool
    calls it: cache and key donated."""
    if ("chunk", name) not in texts:
        texts["chunk", name] = _compiled(
            lambda p, t, c, key, temp, tk, tp, mp: T.decode_chunk_pool(
                p, t, c, cfg, 8, key, temp, tk, tp, mp),
            (2, 3), one_chip,
            params, jnp.zeros((slots, 1), jnp.int32),
            lambda: T.init_cache(cfg, slots), lambda: jax.random.key(0),
            jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.float32),
        )
    return texts["chunk", name]


def _prefill(texts, one_chip, name, cfg, rows, bucket, params, donated=False):
    """A prefill bucket as the server calls it (``donated=False``: the
    caller keeps the cache it passed)."""
    key = ("prefill", name, rows, bucket, donated)
    if key not in texts:
        texts[key] = _compiled(
            lambda p, t, c, l: T.prefill(p, t, c, cfg, l), (2,) if donated else (), one_chip,
            params, jnp.zeros((rows, bucket), jnp.int32),
            lambda: T.init_cache(cfg, rows), jnp.zeros((rows,), jnp.int32),
        )
    return texts[key]


def _instructions(hlo: str):
    """(computation, name, result shape, its layout's minor-to-major order,
    opcode) of every instruction with an array result; the computation is
    "ENTRY" or the name of a loop body, a fused computation, a called one."""
    computation = ""
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            computation = "ENTRY" if head.group(1) else head.group(2)
            continue
        match = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+?)\{([\d,]*)[^ ]* ([\w\-]+)\(", line)
        if match:
            yield (computation, *match.groups())


def _cache_movers(hlo: str, batch: int, cfg=CFG) -> dict[str, list[str]]:
    """computation name -> the instructions in it that write a result of
    the whole cache's or one layer slab's shape by moving data: in the
    order the cache is stored, [L, B, Hkv, S, D], or in the one it had
    before, [L, B, S, Hkv, D] (a relayout would show as either)."""
    shapes = set()
    for slab in (f"{batch},{cfg.n_kv_heads},{cfg.max_seq},{cfg.head_dim}",
                 f"{batch},{cfg.max_seq},{cfg.n_kv_heads},{cfg.head_dim}"):
        shapes |= {f"bf16[{cfg.n_layers},{slab}]", f"bf16[1,{slab}]", f"bf16[{slab}]"}
    found: dict[str, list[str]] = {}
    for computation, name, shape, _, opcode in _instructions(hlo):
        if shape in shapes and opcode in _MOVERS:
            found.setdefault(computation, []).append(name)
    return found


def _abstract_params():
    return lambda: T.init_transformer(jax.random.key(0), CFG)


def test_pooled_chunk_moves_its_cache_nowhere(texts, one_chip, as_on_tpu):
    hlo = _pooled_chunk(texts, one_chip, "internlm2-bf16", CFG, SLOTS, _abstract_params())
    assert "tpu_custom_call" in hlo  # the Mosaic kernel, not interpret mode
    # the cache is stored in the order the kernel reads and donated through
    # the chunk: the token writes and the kernel work on the buffer itself
    assert _cache_movers(hlo, SLOTS) == {}


def test_pooled_chunk_compiles_for_four_chips_under_tp(topo, as_on_tpu):
    """``TPU_MESH=tp=4``: the kernel runs per shard under ``shard_map``,
    2 of the 8 kv heads and their q heads to a chip; K and V left in HBM
    and the copies out of them must compile there too. The cache's heads
    are its third axis (``cache_specs``)."""
    from jax.sharding import NamedSharding

    from gofr_tpu.parallel.mesh import make_mesh, mesh_shape_for
    from gofr_tpu.parallel.sharding import cache_specs, param_specs

    mesh = make_mesh(mesh_shape_for(4, tp=4), devices=topo.devices)
    cfg = dataclasses.replace(CFG, mesh=mesh)

    def placed(tree, specs):
        return jax.tree.map(
            lambda x, spec: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
            tree, specs)

    params = jax.eval_shape(_abstract_params())
    cache = jax.eval_shape(lambda: T.init_cache(cfg, SLOTS))
    rest = jax.eval_shape(lambda: (
        jnp.zeros((SLOTS, 1), jnp.int32), jax.random.key(0),
        jnp.zeros((SLOTS,), jnp.float32), jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.float32), jnp.zeros((SLOTS,), jnp.float32)))
    whole = jax.sharding.PartitionSpec()
    tok, *sampling = placed(rest, jax.tree.map(lambda _: whole, rest))
    hlo = jax.jit(
        lambda p, t, c, key, temp, tk, tp, mp: T.decode_chunk_pool(
            p, t, c, cfg, 8, key, temp, tk, tp, mp),
        donate_argnums=(2, 3),
    ).lower(
        placed(params, param_specs(params)), tok,
        placed(cache, cache_specs(cache)), *sampling,
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert cache_specs(cache)["k"][2] == "tp"
    per_chip = dataclasses.replace(CFG, n_kv_heads=CFG.n_kv_heads // 4)
    assert _cache_movers(hlo, SLOTS, per_chip) == {}
    # each shard's q, k and v are products over its columns of the stacks as
    # they are stored (the last section of this file)
    assert _qkv_products(hlo) == ["io"] * 3


@pytest.mark.parametrize("donated", [True, False])
def test_prefill_moves_its_cache_nowhere(texts, one_chip, as_on_tpu, donated):
    rows = 2
    hlo = _prefill(texts, one_chip, "internlm2-bf16", CFG, rows, 512, _abstract_params(), donated)
    assert "tpu_custom_call" in hlo
    movers = _cache_movers(hlo, rows)
    if donated:
        assert movers == {}
    else:
        # as the server calls it (a shared zero cache, or the cache a slice
        # carries on from, which the caller keeps): K and V are copied once
        # where they enter, in the order they have, and never again
        assert set(movers) == {"ENTRY"} and len(movers["ENTRY"]) == 2, movers


# -- a cache that is a state (attention kind "retention") ------------------------------------
# Brumby-14B-Base's widths (benchmark/configs) with two layers and a small
# vocabulary; 4 slots

RCFG = T.TransformerConfig(
    vocab_size=4096, dim=5120, n_layers=2, n_heads=40, n_kv_heads=8,
    hidden_dim=17408, max_seq=4096, rope_theta=1e6, norm_eps=1e-6, attn_kind="retention",
)


def _state_writers(hlo: str, batch: int) -> dict[str, list[str]]:
    """computation name -> instructions whose result has the whole state's
    or one layer's shape, other than the kernels that own it."""
    row = f"{batch},{RCFG.n_kv_heads},{RCFG.head_dim},8320"
    shapes = (f"f32[{RCFG.n_layers},{row}]", f"f32[1,{row}]", f"f32[{row}]")
    found: dict[str, list[str]] = {}
    for computation, name, shape, _, opcode in _instructions(hlo):
        if shape in shapes and opcode in _MOVERS:
            found.setdefault(computation, []).append(name)
    return found


def _retention_params():
    return lambda: T.init_transformer(jax.random.key(0), RCFG)


def test_pooled_chunk_of_a_retention_model_leaves_its_state_to_the_kernel(texts, one_chip, as_on_tpu):
    hlo = _pooled_chunk(texts, one_chip, "brumby", RCFG, SLOTS, _retention_params())
    assert "retention_step" in hlo and "tpu_custom_call" in hlo
    # the state is donated through the chunk: the kernel reads and writes
    # its layer of it in place, and nothing copies or slices it anywhere
    assert _state_writers(hlo, SLOTS) == {}


def test_prefill_of_a_retention_model_copies_its_state_once_at_entry(one_chip, as_on_tpu):
    rows = 2
    hlo = _compiled(
        lambda p, t, c, l: T.prefill(p, t, c, RCFG, l), (), one_chip,
        _retention_params(), jnp.zeros((rows, 512), jnp.int32),
        lambda: T.init_cache(RCFG, rows), jnp.zeros((rows,), jnp.int32),
    )
    assert "retention_chunk" in hlo and "tpu_custom_call" in hlo
    # the caller keeps the cache it passed (a shared zero cache, or the
    # state a slice carries on from), so the program copies it once
    writers = _state_writers(hlo, rows)
    assert set(writers) <= {"ENTRY"} and len(writers.get("ENTRY", [])) <= 1, writers


# -- routed experts and a cache with a tail (attention kind "cca", feed-forward "moe") ---------
# ZAYA1-8B's widths and the cell's 20 layers (benchmark/configs) with a
# small vocabulary; 32 slots

ZCFG = T.TransformerConfig(
    vocab_size=4096, dim=2048, n_layers=20, n_heads=8, n_kv_heads=2, head_dim=128,
    hidden_dim=2048, max_seq=2048, rope_theta=5e6, rope_fraction=0.5, attn_kind="cca",
    ffn_kind="moe", n_experts=16, router_dim=256, tie_embeddings=True,
)
ZSLOTS = 32


def _expert_movers(hlo: str) -> list[str]:
    """Instructions whose result has the shape of the expert stacks, of one
    layer's experts or of one expert: none may exist, the kernels read
    [layer, expert] of the stacks where they lie."""
    shape = f"{ZCFG.dim},{ZCFG.hidden_dim}"
    shapes = {f"bf16[{ZCFG.n_layers},{ZCFG.n_experts},{shape}]",
              f"bf16[{ZCFG.n_experts},{shape}]", f"bf16[1,{ZCFG.n_experts},{shape}]",
              f"bf16[{shape}]"}
    return [name for _, name, shape, _, opcode in _instructions(hlo)
            if shape in shapes and opcode in _MOVERS]


def _zaya_params():
    return T.init_transformer(jax.random.key(0), ZCFG)


def test_pooled_chunk_of_an_expert_model_leaves_its_experts_where_they_lie(one_chip, as_on_tpu):
    hlo = _compiled(
        lambda p, t, c, key, temp, tk, tp, mp: T.decode_chunk_pool(
            p, t, c, ZCFG, 8, key, temp, tk, tp, mp),
        (2, 3), one_chip,
        _zaya_params, jnp.zeros((ZSLOTS, 1), jnp.int32),
        lambda: T.init_cache(ZCFG, ZSLOTS), lambda: jax.random.key(0),
        jnp.zeros((ZSLOTS,), jnp.float32), jnp.zeros((ZSLOTS,), jnp.int32),
        jnp.zeros((ZSLOTS,), jnp.float32), jnp.zeros((ZSLOTS,), jnp.float32),
    )
    # the decode form of flash attention and the two expert products
    assert hlo.count("tpu_custom_call") >= 3
    assert "moe_experts_gated" in hlo and "moe_experts_down" in hlo
    assert _expert_movers(hlo) == []
    assert _cache_movers(hlo, ZSLOTS, ZCFG) == {}


@pytest.mark.parametrize("rows,bucket", [(2, 256), (1, 256), (2, 128)])
def test_prefill_of_an_expert_model_compiles_at_the_cells_buckets(one_chip, as_on_tpu, rows, bucket):
    """512, 256 and 256 tokens: four, two and two row tiles of 128."""
    hlo = _compiled(
        lambda p, t, c, l: T.prefill(p, t, c, ZCFG, l, with_aux=True), (), one_chip,
        _zaya_params, jnp.zeros((rows, bucket), jnp.int32),
        lambda: T.init_cache(ZCFG, rows), jnp.zeros((rows,), jnp.int32),
    )
    assert "moe_experts_gated" in hlo and "moe_experts_down" in hlo
    assert _expert_movers(hlo) == []
    # called as the server calls it, the caller keeping its cache: K and V
    # are copied once where they enter (42 MB a stack: one of them by way
    # of the fast memory, where the loop then keeps it) and in no loop
    movers = _cache_movers(hlo, rows, ZCFG)
    assert set(movers) <= {"ENTRY"} and len(movers.get("ENTRY", [])) <= 2, movers


# -- layers of two kinds in one stack: state-space mixers among attention layers ----------------
# AI21-Jamba2-3B's widths and depth (benchmark/configs: 28 layers, attention
# at 7 and 21, 20 query heads on ONE kv head, no rotary) with a small
# vocabulary; the cell's 64 slots

JCFG = T.TransformerConfig(
    vocab_size=4096, dim=2560, n_layers=28, n_heads=20, n_kv_heads=1, hidden_dim=8192,
    max_seq=2048, rope_fraction=0.0, norm_eps=1e-6, ssm_dt_rank=160, tie_embeddings=True,
    layer_kinds=tuple("softmax" if i % 14 == 7 else "ssm" for i in range(28)),
)
JSLOTS = 64
# a weight stack of either kind's SwiGLU or state-space projections, and one
# layer of it (the two attention layers' own projections: the last section)
_JWEIGHTS = {f"bf16[{n},{shape}]" for n in (26, 2, 1) for shape in (
    "2560,10240", "5120,2560", "2560,8192", "8192,2560")}


def _hybrid_movers(hlo: str, batch: int) -> dict[str, list[str]]:
    """computation name -> the instructions in it that write, by moving
    data, a result of the shape of a whole cache leaf (the state, the
    convolution tail, K or V), of a layer of the state, or of a weight
    stack. A stack's in-place update comes as a fusion of the stack's shape
    named ``*dynamic-update-slice*`` (``bitcast_dynamic-update-slice_fusion``)
    and is no move; the compiler's own prefetches of a small stack into the
    fast memory (``copy-start`` / ``copy-done``) are counted apart."""
    state = f"{batch},{JCFG.ssm_state},{JCFG.d_inner}"
    leaves = {f"f32[26,{state}]", f"f32[1,{state}]", f"f32[{state}]",
              f"bf16[26,{batch},{3 * JCFG.d_inner}]",
              f"bf16[2,{batch},1,{JCFG.max_seq},128]"} | _JWEIGHTS
    found: dict[str, list[str]] = {}
    for computation, name, shape, _, opcode in _instructions(hlo):
        # inside a fused computation nothing is written out: a layer's weights
        # sliced there feed the product they are fused with
        if shape not in leaves or computation.startswith("fused_computation"):
            continue
        if opcode in ("copy", "transpose", "reshape", "slice", "dynamic-slice") or (
                opcode == "fusion" and not _updates_in_place(hlo, name)):
            found.setdefault(computation, []).append(name)
    return found


def _updates_in_place(hlo: str, fusion: str) -> bool:
    """Whether the fusion ``fusion`` is rooted in a dynamic-update-slice of
    its own operand: the in-place write of one layer's rows into a stack."""
    called = re.search(rf"%{re.escape(fusion)} = .*calls=%([\w.\-]+)", hlo)
    if not called:
        return False
    body = hlo.split(f"%{called.group(1)} (", 1)[1].split("\n}\n", 1)[0]
    root = [line for line in body.splitlines() if "ROOT" in line]
    return bool(root) and "dynamic-update-slice(" in root[0]


def _jamba_params():
    return T.init_transformer(jax.random.key(0), JCFG)


def test_pooled_chunk_of_a_hybrid_model_leaves_state_tail_and_weights_where_they_lie(
        texts, one_chip, as_on_tpu):
    hlo = _pooled_chunk(texts, one_chip, "jamba2", JCFG, JSLOTS, _jamba_params)
    # one state-space body a run of the PERIOD (7 and 6 layers: two step
    # kernels) and the decode form of flash attention at a group of 20
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", hlo)) == 3
    assert "ssm_step" in hlo and "ssm_scan" not in hlo
    # the cache is donated through the chunk: the step kernel reads and
    # writes its layer of the state in place, the tail's rows are written
    # into their stack, and no weight stack is sliced into a copy
    assert _hybrid_movers(hlo, JSLOTS) == {}


@pytest.mark.parametrize("rows,bucket", [(2, 256), (1, 256), (2, 128)])
def test_prefill_of_a_hybrid_model_compiles_at_the_cells_buckets(one_chip, as_on_tpu, rows, bucket):
    hlo = _compiled(
        lambda p, t, c, l: T.prefill(p, t, c, JCFG, l), (), one_chip,
        _jamba_params, jnp.zeros((rows, bucket), jnp.int32),
        lambda: T.init_cache(JCFG, rows), jnp.zeros((rows,), jnp.int32),
    )
    # two chunked-scan kernels (the period's runs) and the prefill form of
    # flash attention, the one kv head's block revisited by 20 query heads
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", hlo)) == 3
    assert "ssm_scan" in hlo and "ssm_step" not in hlo
    # called as the server calls it, the caller keeping its cache: every
    # leaf is copied where it enters (a row or two: 11 MB a row; the one-row
    # tail, 0.8 MB, twice) and in no loop
    movers = _hybrid_movers(hlo, rows)
    assert set(movers) <= {"ENTRY"} and len(movers.get("ENTRY", [])) <= 6, movers


# -- a layer's weights are read where they lie, inside their matmul, once -----------------------
# The chip's compiler may fold the reshape by head that follows a product
# into the product (a convolution ``bf0_0oi->b0f``, the result [B, heads,
# head_dim]); that form wants its weight as [heads, head_dim, dim], the stored
# one transposed, so the program relays the whole stack once a run
# (``copy(%p__layers____wq…)``) or a layer's slab once a layer, copies each
# layer's slab out as an operation of its own (``constant_dynamic-slice_fusion``:
# the weight's whole HBM read) and runs the product from the copy.
# ``_attention_mixer`` holds the three products 2-D until they exist.

# Mistral-7B-v0.3's widths and depth (benchmark/configs), int8 as the cell
# serves it, 6 slots; a vocabulary whose head and table share no shape
# with a layer's weights
MCFG = T.TransformerConfig(
    vocab_size=8192, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    hidden_dim=14336, max_seq=2048, rope_theta=1e6,
)
MSLOTS = 6
_HLO_TYPES = {"bfloat16": "bf16", "int8": "s8"}


def _mistral_params():
    from gofr_tpu.models import quant

    return quant.quantize_params(T.init_transformer(jax.random.key(0), MCFG), "int8")


def _weight_stacks(layers) -> set[tuple[str, int, int, int]]:
    """(type, layers, in, out) of every stacked matmul leaf under ``layers``
    (a narrow leaf, the retention gate's 40 columns or a convolution's 4
    taps, is no weight stream)."""
    return {
        (_HLO_TYPES[leaf.dtype.name], *leaf.shape)
        for leaf in jax.tree.leaves(jax.eval_shape(layers))
        if leaf.ndim == 3 and leaf.dtype.name in _HLO_TYPES and min(leaf.shape[1:]) >= 128
    }


def _weight_reads(hlo: str, stacks) -> dict[str, list]:
    """What the compiled text does with the weight ``stacks``:

    ``moved``: (computation, instruction) outside the fused computations
    whose result has the shape of a stack, of one layer's slab or of that
    slab as a matrix, in the stored type or dequantised, written by moving
    data (a fusion with such a result is rooted in a slice or a copy:
    ``constant_dynamic-slice_fusion``); ``prefetched``: the compiler's own
    asynchronous fetches of a small stack into the fast memory, in the
    layout it has (``copy-done``, ``slice-done``: no relayout, and beside
    the weight stream, not in it); ``relaid``: the shapes that appear
    anywhere in a layout other than the one the program was handed."""
    shapes = set()
    for kind, n, i, o in stacks:
        for t in {kind, "bf16"}:
            shapes |= {f"{t}[{n},{i},{o}]", f"{t}[1,{i},{o}]", f"{t}[{i},{o}]"}
    reads: dict[str, list] = {"moved": [], "prefetched": [], "relaid": []}
    for computation, name, shape, layout, opcode in _instructions(hlo):
        if shape not in shapes:
            continue
        if layout != ",".join(str(d) for d in reversed(range(shape.count(",") + 1))):
            reads["relaid"].append((shape, layout))
        if computation.startswith("fused_computation"):
            continue  # nothing is written out there: the slice feeds its product
        if opcode in ("copy-done", "slice-done"):
            reads["prefetched"].append((computation, name))
        elif opcode in _MOVERS:
            reads["moved"].append((computation, name))
    return reads


def _qkv_products(hlo: str) -> list[str]:
    """The kernel side of the ``dim_labels`` of every product of the
    ``attn.qkv`` scope, less the batch axis a prefill adds (``io0``): ``io``
    is the weight as it is stored, [dim, width]; ``0oi`` (``1oi``) is the
    fold, [heads, head_dim, dim]."""
    return [
        labels.split("_")[1].split("->")[0].rstrip("0")
        for labels in re.findall(
            r" convolution\(.*dim_labels=([\w>\-]+).*op_name=\"[^\"]*attn\.qkv/dot_general", hlo)
    ]


def _reads_weights_in_place(hlo: str, stacks, prefetches: int = 0) -> None:
    reads = _weight_reads(hlo, stacks)
    assert reads["moved"] == [] and reads["relaid"] == [], reads
    assert len(reads["prefetched"]) <= prefetches, reads
    products = _qkv_products(hlo)
    assert len(products) >= 3 and set(products) == {"io"}, products


@pytest.mark.parametrize("program", ["chunk", "prefill"])
@pytest.mark.parametrize("model", ["internlm2-bf16", "mistral-int8"])
def test_dense_programs_read_every_weight_stack_where_it_lies(texts, one_chip, as_on_tpu, model, program):
    """On the tree before this test, three of the four fail: both pooled
    chunks copy the ``wq`` and ``wk`` stacks in ENTRY and slice a layer of
    each in the layer loop (``constant_dynamic-slice_fusion.19 =
    s8[1,4096,4096]``, ``.4 = bf16[1,2048,2048]``), the bf16 prefill slices
    and copies all three slabs in its loop; the int8 prefill held no fold
    (the scale's multiply stands between product and reshape)."""
    cfg, slots, params = ((CFG, SLOTS, _abstract_params()) if model == "internlm2-bf16"
                          else (MCFG, MSLOTS, _mistral_params))
    hlo = (_pooled_chunk(texts, one_chip, model, cfg, slots, params) if program == "chunk"
           else _prefill(texts, one_chip, model, cfg, 2, 512, params))
    assert "tpu_custom_call" in hlo
    _reads_weights_in_place(hlo, _weight_stacks(lambda: params()["layers"]))


def test_pooled_chunk_of_a_retention_model_reads_every_weight_stack_where_it_lies(
        texts, one_chip, as_on_tpu):
    """Brumby's q and k norms stand between product and rotary and its
    kernel takes v by head: the tree before this test folded all THREE
    products here (``copy`` of the ``wq``, ``wk`` and ``wv`` stacks in
    ENTRY). What is left is the compiler's prefetch of the two small stacks
    (``wk``, ``wv``: 21 MB each at this test's two layers) as they lie."""
    hlo = _pooled_chunk(texts, one_chip, "brumby", RCFG, SLOTS, _retention_params())
    _reads_weights_in_place(
        hlo, _weight_stacks(lambda: _retention_params()()["layers"]), prefetches=2)


def test_pooled_chunk_of_a_hybrid_model_reads_its_attention_weights_where_they_lie(
        texts, one_chip, as_on_tpu):
    """Jamba's two attention layers have no rotary, and the tree before this
    test folded their ``wq`` all the same (a ``copy`` of the 26 MB stack in
    ENTRY, a slice of a layer of it in the loop): the fold is the reshape's
    whoever follows it. Left: the compiler's prefetches, as they lie, of
    the ``wk`` and ``wv`` stacks (0.65 MB a layer) and of each attention
    layer's ``w_down`` slab while the layers before it run."""
    hlo = _pooled_chunk(texts, one_chip, "jamba2", JCFG, JSLOTS, _jamba_params)
    _reads_weights_in_place(
        hlo, _weight_stacks(lambda: _jamba_params()["layers"]["softmax"]), prefetches=4)


# -- a latent cache, a top-k gate over a share of the experts, the double layer -------------------
# LongCat-Flash-Chat's widths as the benchmark's cell cuts it (4 layers, 16
# of 512 routed experts held, MODEL_MAX_SEQ 7168, the cell's 40 slots) with a
# small vocabulary

LCFG = T.TransformerConfig(
    vocab_size=4096, dim=6144, n_layers=4, n_heads=64, n_kv_heads=1, hidden_dim=12288,
    max_seq=7168, rope_theta=1e7, attn_kind="mla", q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, ffn_kind="scmoe", router_kind="linear",
    n_experts=16, n_routed_experts=512, n_identity_experts=256, top_k=12, routed_scale=6.0,
    expert_dim=2048,
)
LSLOTS = 40
# a sublayer stack's big leaves, one sublayer of one, or the pair a scan would
# hand in; the expert stacks, one layer's experts, one expert
_LWEIGHTS = {f"bf16[{lead}{shape}]" for lead in ("8,", "2,", "1,", "") for shape in (
    "6144,12288", "12288,6144", "8192,6144", "1536,12288", "6144,1536")} | {
    f"bf16[{lead}{shape}]" for lead in ("4,16,", "1,16,", "16,", "")
    for shape in ("6144,2048", "2048,6144")}


def _movers_of(hlo: str, leaves: set) -> dict[str, list[str]]:
    """computation name -> the instructions in it that write, by moving data,
    a result of one of the shapes ``leaves``. In-place row writes and what
    happens inside a fused computation are no moves (``_hybrid_movers``)."""
    found: dict[str, list[str]] = {}
    for computation, name, shape, _, opcode in _instructions(hlo):
        if shape not in leaves or computation.startswith("fused_computation"):
            continue
        if opcode in ("copy", "transpose", "reshape", "slice", "dynamic-slice") or (
                opcode == "fusion" and not _updates_in_place(hlo, name)):
            found.setdefault(computation, []).append(name)
    return found


def _latent_movers(hlo: str, batch: int) -> dict[str, list[str]]:
    """What moves a latent cache leaf (or one place of it), a sublayer weight
    stack or the expert stacks."""
    return _movers_of(hlo, {f"bf16[{lead}{batch},7168,512]" for lead in ("8,", "1,", "")} | {
        f"bf16[{lead}{batch},64,7168]" for lead in ("8,", "1,", "")} | _LWEIGHTS)


def _longcat_params():
    return T.init_transformer(jax.random.key(0), LCFG)


def test_pooled_chunk_of_a_latent_model_leaves_latent_weights_and_experts_where_they_lie(
        texts, one_chip, as_on_tpu):
    """The absorbed form reads a place's latent out of the stack inside its
    products; the two sublayers of a layer are read out of the [2 L, ...]
    stacks the loop closes over (handed in by the scan as a [2, ...] slice
    they were copied whole: 1.3 GB a layer and step); the pair form's two
    Pallas calls index [layer, expert] of the stacks themselves."""
    hlo = _pooled_chunk(texts, one_chip, "longcat", LCFG, LSLOTS, _longcat_params)
    assert "moe_experts_gated" in hlo and "moe_experts_down" in hlo
    assert _latent_movers(hlo, LSLOTS) == {}


def test_prefill_of_a_latent_model_compiles_at_the_cells_widest_bucket(one_chip, as_on_tpu):
    """Two rows of 1024: the expanded form through the flash kernel's prefill
    form at a key of 192 and a value of 128, and the pair form of the routed
    product at 2048 tokens (1024 rows of sorted pairs a pass)."""
    hlo = _compiled(
        lambda p, t, c, l: T.prefill(p, t, c, LCFG, l, with_aux=True), (), one_chip,
        _longcat_params, jnp.zeros((2, 1024), jnp.int32),
        lambda: T.init_cache(LCFG, 2), jnp.zeros((2,), jnp.int32),
    )
    # the flash forward in both sublayers and the two expert products
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", hlo)) == 4
    assert "moe_experts_gated" in hlo and "moe_experts_down" in hlo
    # each head's keys and values exist expanded, in the order the kernel reads
    assert "bf16[1,2,64,7168,192]" in hlo and "bf16[1,2,64,7168,128]" in hlo
    # called as the server calls it, the caller keeping its cache: the two
    # leaves are copied where they enter and in no loop; no weight is moved
    movers = _latent_movers(hlo, 2)
    assert set(movers) <= {"ENTRY"} and len(movers.get("ENTRY", [])) <= 4, movers


# -- a dense layer before expert layers, every expert held, shared experts, no q bottleneck ------
# Moonlight-16B-A3B's widths as the benchmark's cell holds it (1 dense + 8
# expert layers, all 64 routed experts, MODEL_MAX_SEQ 2048, the cell's 48
# slots) with a small vocabulary

MOONCFG = T.TransformerConfig(
    vocab_size=4096, dim=2048, n_layers=9, n_heads=16, n_kv_heads=1, hidden_dim=11264,
    max_seq=2048, rope_theta=50000.0, attn_kind="mla", kv_lora_rank=512, qk_nope_dim=128,
    qk_rope_dim=64, v_head_dim=128, mla_scale=False, ffn_kinds=("dense",) + ("moe",) * 8,
    router_kind="linear", gate_scoring="sigmoid", norm_topk=True, n_experts=64,
    n_routed_experts=64, n_shared_experts=2, top_k=6, routed_scale=2.446, expert_dim=1408,
)
MOONSLOTS = 48
# the expert stacks, one layer's experts, one expert; the shared experts' and
# the attention's stacks over the expert layers, and the dense layer's
_MOONWEIGHTS = {f"bf16[{lead}{shape}]" for lead in ("8,64,", "1,64,", "64,", "")
             for shape in ("2048,1408", "1408,2048")} | {
    f"bf16[{lead}{shape}]" for lead in ("8,", "1,") for shape in (
        "2048,2816", "2816,2048", "2048,3072", "2048,2048", "2048,11264", "11264,2048")}


def _moonlight_movers(hlo: str, batch: int) -> dict[str, list[str]]:
    """What moves the latent cache over nine places, the expert stacks, the
    shared experts' stacks or the attention's."""
    return _movers_of(hlo, {f"bf16[{lead}{batch},2048,512]" for lead in ("9,", "1,", "")} | {
        f"bf16[{lead}{batch},64,2048]" for lead in ("9,", "1,", "")} | _MOONWEIGHTS)


def _moonlight_params():
    return T.init_transformer(jax.random.key(0), MOONCFG)


def test_pooled_chunk_of_a_model_with_a_leading_dense_layer_takes_one_pass_of_pairs_a_layer(
        texts, one_chip, as_on_tpu):
    """The dense layer inline and one scan over the eight expert layers, each
    reading its kind's stack where it lies; all 48 x 6 pairs of a step in ONE
    pass of the pair form (288 sorted rows, three row tiles of 128), whose
    two Pallas calls index [layer, expert] of the stacks themselves; the
    absorbed attention's kernel in both bodies."""
    hlo = _pooled_chunk(texts, one_chip, "moonlight", MOONCFG, MOONSLOTS, _moonlight_params)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", hlo)) == 4
    assert "moe_experts_gated" in hlo and "moe_experts_down" in hlo
    assert hlo.count("mla_absorbed_decode") >= 2
    assert "bf16[384,2048]" in hlo and "bf16[384,1408]" in hlo  # a step's every pair, padded
    assert _moonlight_movers(hlo, MOONSLOTS) == {}


def test_prefill_of_a_model_with_a_leading_dense_layer_compiles_at_the_cells_bucket(
        one_chip, as_on_tpu):
    """Two rows of 256: the expanded form through the flash kernel at a key
    of 192 and a value of 128, and all 512 x 6 pairs in one pass of the pair
    form (3072 sorted rows)."""
    hlo = _compiled(
        lambda p, t, c, l: T.prefill(p, t, c, MOONCFG, l, with_aux=True), (), one_chip,
        _moonlight_params, jnp.zeros((2, 256), jnp.int32),
        lambda: T.init_cache(MOONCFG, 2), jnp.zeros((2,), jnp.int32),
    )
    # the flash forward in the dense layer and in the scanned one, the two expert products
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", hlo)) == 4
    assert "moe_experts_gated" in hlo and "moe_experts_down" in hlo
    assert "bf16[3072,2048]" in hlo and "bf16[3072,1408]" in hlo
    # called as the server calls it, the caller keeping its cache: the two
    # leaves are copied where they enter and in no loop; no weight is moved
    movers = _moonlight_movers(hlo, 2)
    assert set(movers) <= {"ENTRY"} and len(movers.get("ENTRY", [])) <= 4, movers
