"""Seeded weights, made on the device in the type they are served in.

The benchmark makes the weights (a deployment loads a checkpoint; it does
not initialise), hands the served tree to the program, and gives the plain
reference the same values layer by layer in float32. Both come from
``leaf_values`` below, so neither takes anything the other has made.

Every value is integer noise times a constant: the sum of the four bytes of
one random word (Irwin-Hall, close to normal, sd 147.8) recentred. Integer
arithmetic is exact, so the whole-model program (one jitted call, layers
under ``lax.map``) and the reference's per-layer program give the same
bits whatever the compiler fuses.

- ``int8``: ``q = round(v / 4)`` as int8 (sd 37, clipped to +-127) with one
  float32 scale per output channel, ``3 / (127 sqrt(fan_in)) * (1 + k/1024)``,
  ``k`` a seeded byte. The model IS these int8 values times their scales.
- ``bf16``: ``v * (1 / (147.8 sqrt(fan_in)))`` rounded once to bfloat16.
- norm weights: ``1 + (byte - 128) / 512`` in bfloat16.

The tree handed to the program has the layout ``models/transformer.py``
serves (``embed``, ``norm_f``, ``lm_head``, ``layers`` stacked on a leading
axis; a quantised leaf is ``{"q", "scale"}``). That layout is the seam
between the benchmark and the program: see PERF.md, Open questions.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

SD = 147.8  # sd of the recentred sum of four uniform bytes

# the matmul weights of one decoder layer, in the program's names
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_LEAF_IDS = {name: i for i, name in enumerate(
    LAYER_LEAVES + ("attn_norm", "mlp_norm", "embed", "lm_head", "norm_f")
)}


def sizes_of(cfg: dict) -> dict:
    """The sizes this module needs, from a configuration file's keys."""
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return {
        "dim": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": head_dim, "ffn": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"], "quant": cfg["serving"]["quant"],
        "dtype": cfg["serving"].get("dtype", "bfloat16"),
    }


def leaf_shape(sz: dict, name: str) -> tuple[int, int]:
    d, kv = sz["dim"], sz["kv_heads"] * sz["head_dim"]
    return {
        "wq": (d, sz["heads"] * sz["head_dim"]), "wk": (d, kv), "wv": (d, kv),
        "wo": (sz["heads"] * sz["head_dim"], d), "w_gate": (d, sz["ffn"]),
        "w_up": (d, sz["ffn"]), "w_down": (sz["ffn"], d),
        "embed": (sz["vocab"], d), "lm_head": (d, sz["vocab"]),
    }[name]


def _key(seed: jax.Array, layer: jax.Array, name: str) -> jax.Array:
    key = jax.random.fold_in(jax.random.key(0), seed)
    key = jax.random.fold_in(key, layer)
    return jax.random.fold_in(key, _LEAF_IDS[name])


def _noise(key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """int32 in [-510, 510], sd 147.8: four bytes of one word, summed."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    total = (bits & 0xFF) + ((bits >> 8) & 0xFF) + ((bits >> 16) & 0xFF) + (bits >> 24)
    return total.astype(jnp.int32) - 510


def leaf_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict,
                quant: str) -> Any:
    """One matmul weight as served: ``{"q", "scale"}`` for int8, else an
    array of the serving dtype. ``layer`` is -1 for the model-level leaves.
    ``embed`` is never quantised (the program's scheme keeps it dense)."""
    rows, cols = leaf_shape(sz, name)
    fan_in = sz["dim"] if name == "embed" else rows
    key = _key(seed, layer, name)
    v = _noise(key, (rows, cols))
    if quant == "int8" and name != "embed":
        q = jnp.clip((v + 2) >> 2, -127, 127).astype(jnp.int8)
        k = jax.random.bits(jax.random.fold_in(key, 1), (1, cols), jnp.uint8)
        scale = jnp.float32(3.0 / (127.0 * fan_in ** 0.5)) * (
            (k.astype(jnp.float32) + 1024.0) / 1024.0
        )
        return {"q": q, "scale": scale}
    if quant not in ("", "int8"):
        raise ValueError(f"weights for quant {quant!r} are not defined")
    return (v.astype(jnp.float32) * jnp.float32(1.0 / (SD * fan_in ** 0.5))).astype(
        jnp.dtype(sz["dtype"])
    )


def norm_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    k = jax.random.bits(_key(seed, layer, name), (sz["dim"],), jnp.uint8)
    return (1.0 + (k.astype(jnp.float32) - 128.0) / 512.0).astype(jnp.dtype(sz["dtype"]))


def layer_values(seed: jax.Array, layer: jax.Array, sz: dict) -> dict:
    """One decoder layer as served."""
    out = {n: leaf_values(seed, layer, n, sz, sz["quant"]) for n in LAYER_LEAVES}
    out["attn_norm"] = norm_values(seed, layer, "attn_norm", sz)
    out["mlp_norm"] = norm_values(seed, layer, "mlp_norm", sz)
    return out


def head_values(seed: jax.Array, sz: dict) -> dict:
    top = jnp.int32(-1)
    return {
        "embed": leaf_values(seed, top, "embed", sz, sz["quant"]),
        "norm_f": norm_values(seed, top, "norm_f", sz),
        "lm_head": leaf_values(seed, top, "lm_head", sz, sz["quant"]),
    }


def make_params(seed: int, sz: dict) -> dict:
    """The whole served tree in ONE jitted call from the seed."""

    def build(s: jax.Array) -> dict:
        tree = head_values(s, sz)
        tree["layers"] = jax.lax.map(
            lambda i: layer_values(s, i, sz), jnp.arange(sz["layers"], dtype=jnp.int32)
        )
        return tree

    # any whole-number seed: folded in as 32 bits
    return jax.jit(build)(jnp.uint32(int(seed) & 0xFFFFFFFF))


def dequantise(leaf: Any) -> jax.Array:
    """A served leaf's exact values in float32."""
    if isinstance(leaf, dict):
        return leaf["q"].astype(jnp.float32) * leaf["scale"]
    return leaf.astype(jnp.float32)
