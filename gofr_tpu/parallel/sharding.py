"""PartitionSpec rules for the model families.

Name-based rules over the plain-dict param trees (the reason models keep
params as dicts, models/__init__.py): given a param tree, produce a
matching tree of jax.sharding.PartitionSpec.

Transformer layout (stacked layer weights have a leading n_layers axis that
is never sharded):

| weight              | shape              | spec                      |
|---------------------|--------------------|---------------------------|
| embed               | [V, D]             | P(None, 'tp')             |
| lm_head             | [D, V]             | P('fsdp', 'tp')           |
| wq / wk / wv        | [L, D, H*hd]       | P(None, 'fsdp', 'tp')     |
| wo                  | [L, D, D]          | P(None, 'tp', 'fsdp')     |
| w_gate / w_up       | [L, D, F]          | P(None, 'fsdp', 'tp')     |
| w_down              | [L, F, D]          | P(None, 'tp', 'fsdp')     |
| norms / biases      | [...]              | replicated                |

This is the Megatron pattern: column-parallel in-projections, row-parallel
out-projections — XLA inserts the psum on the row-parallel output. ``fsdp``
shards the other matmul dimension (ZeRO-3); gradients reduce-scatter over
``fsdp`` and all-reduce over ``dp`` automatically under jit.

Quantized weights shard like the underlying weight: int8 {"q", "scale"}
(and w8a8 {"q8", "scale"}) scales follow the output axis; int4
{"q4", "scale"} scales take the full
weight spec (their group axis follows the input axis).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# name -> (spec for plain 2-D [in, out], stacked 3-D gets None prepended)
_COL_PARALLEL = {"wq", "wk", "wv", "w_gate", "w_up", "w_in", "wqkv"}  # out dim -> tp
_ROW_PARALLEL = {"wo", "w_down", "w_out"}  # in dim -> tp


def _spec_for(name: str, ndim: int) -> P:
    if name == "embed" or name == "tok_embed":
        # vocab axis replicated: token gather over a vocab-sharded table
        # is ambiguous for GSPMD (would need collective gather); hidden
        # axis over tp keeps activations sharded from the start
        return P(None, "tp")
    if name == "lm_head":
        return P("fsdp", "tp")
    if name == "pos_embed":
        return P(None, "fsdp")
    if name in _COL_PARALLEL:
        base = ("fsdp", "tp")
    elif name in _ROW_PARALLEL:
        base = ("tp", "fsdp")
    else:  # norms, biases, scalars: replicate
        return P()
    pad = (None,) * (ndim - 2)
    return P(*pad, *base)


def param_specs(params: Any, _name: str = "") -> Any:
    """Mirror a param tree with PartitionSpecs (name-based rules)."""

    def walk(tree: Any, name: str) -> Any:
        if isinstance(tree, dict):
            keys = set(tree)
            if keys == {"w", "lora_a", "lora_b", "lora_scale"}:
                # LoRA leaf: base shards by its own rule under the same
                # name; A shards its in axis, B its out axis, like the
                # weight (the rank axis replicates)
                w_spec = walk(tree["w"], name)
                q_spec = _spec_for(name, tree["lora_a"].ndim)
                pad = (None,) * (tree["lora_a"].ndim - 2)
                a_in = q_spec[-2] if len(q_spec) >= 2 else None
                b_out = q_spec[-1] if len(q_spec) >= 1 else None
                return {
                    "w": w_spec,
                    "lora_a": P(*pad, a_in, None),
                    "lora_b": P(*pad, None, b_out),
                    "lora_scale": P(),
                }
            if keys in ({"q", "scale"}, {"q4", "scale"}, {"q8", "scale"}):
                # packed leaf pair; w8a8 ({"q8"}) shards exactly like int8
                q_key = next(k for k in ("q", "q4", "q8") if k in tree)
                q_spec = _spec_for(name, tree[q_key].ndim)
                if q_key == "q4" and tree["scale"].shape[-2] > 1:
                    # int4 scale [..., groups, out]: the group axis follows
                    # the weight's in axis, so it takes the SAME spec (a
                    # row-parallel weight shards its groups over tp). A
                    # single-group scale (group clamped to a small dim)
                    # degenerates to the int8 rule below — a size-1 axis
                    # cannot split
                    return {q_key: q_spec, "scale": q_spec}
                # int8 scale is [..., 1, out]: only the out axis is
                # shardable (the size-1 axis cannot split)
                tail = q_spec[-1] if len(q_spec) > 0 else None
                scale_pad = (None,) * (tree["scale"].ndim - 1)
                return {q_key: q_spec, "scale": P(*scale_pad, tail)}
            return {k: walk(v, k) for k, v in tree.items()}
        return _spec_for(name, getattr(tree, "ndim", 0))

    return walk(params, _name)


def batch_spec(sp: bool = False) -> P:
    """Token batches [B, S]: batch over dp(+fsdp), optionally sequence over
    sp (ring attention path)."""
    return P(("dp", "fsdp"), "sp" if sp else None)


def cache_specs(cache: Any) -> Any:
    """KV cache [L, B, Hkv, S, hd]: batch over dp(+fsdp), kv heads over tp;
    the per-row vectors ``lengths`` and ``live`` [B] go with the batch."""
    return {
        "k": P(None, ("dp", "fsdp"), "tp", None, None),
        "v": P(None, ("dp", "fsdp"), "tp", None, None),
        "lengths": P(("dp", "fsdp")),
        "live": P(("dp", "fsdp")),
    }


def kv_arena_spec() -> P:
    """Paged-KV block arena [L, n_blocks, Hkv, bt, hd]: kv heads over tp
    (the same head split :func:`cache_specs` gives the compute caches,
    so scatter/gather between blocks and rows moves no bytes across the
    tp axis); block and token axes stay unsharded — block ids are
    mesh-agnostic bookkeeping."""
    return P(None, None, "tp", None, None)


def shard_params(params: Any, mesh: Mesh, specs: Optional[Any] = None) -> Any:
    """Place a param tree onto the mesh with NamedShardings."""
    if specs is None:
        specs = param_specs(params)

    def walk(p: Any, s: Any) -> Any:
        # explicit recursion: PartitionSpec is itself a tuple, so a generic
        # tree_map over the spec tree would descend INTO the specs
        if isinstance(p, dict):
            return {k: walk(p[k], s[k]) for k in p}
        return jax.device_put(p, NamedSharding(mesh, s))

    return walk(params, specs)
