"""Seeded values, made on the device in the type they are served in: the
part every architecture uses. It knows no model: a caller names a leaf by
the triple (seed, layer, leaf id) and gives its shape, fan-in, quantisation
and dtype. Which leaves a model has, their ids and shapes, and the tree the
program is handed live in ``benchmark/architectures/<name>.py``.

The benchmark makes the weights (a deployment loads a checkpoint; it does
not initialise). An architecture hands the served tree to the program and
gives its plain reference the same values layer by layer in float32, both
from ``matmul_values`` / ``norm_values`` below, so neither takes anything
the other has made.

Every value is integer noise times a constant: the sum of the four bytes of
one random word (Irwin-Hall, close to normal, sd 147.8) recentred. Integer
arithmetic is exact, so a whole-model program (one jitted call, layers
under ``lax.map``) and a reference's per-layer program give the same bits
whatever the compiler fuses.

- ``int8``: ``q = round(v / 4)`` as int8 (sd 37, clipped to +-127) with one
  float32 scale per output channel, ``3 / (127 sqrt(fan_in)) * (1 + k/1024)``,
  ``k`` a seeded byte. The model IS these int8 values times their scales.
- ``""``: ``v * (1 / (147.8 sqrt(fan_in)))`` rounded once to the dtype.
- norm weights: ``1 + (byte - 128) / 512`` in the dtype.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

SD = 147.8  # sd of the recentred sum of four uniform bytes


def seed_word(seed: int) -> jax.Array:
    """Any whole-number seed, folded in as 32 bits."""
    return jnp.uint32(int(seed) & 0xFFFFFFFF)


def _key(seed: jax.Array, layer: jax.Array, leaf_id: int) -> jax.Array:
    key = jax.random.fold_in(jax.random.key(0), seed)
    key = jax.random.fold_in(key, layer)
    return jax.random.fold_in(key, leaf_id)


def _noise(key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """int32 in [-510, 510], sd 147.8: four bytes of one word, summed."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    total = (bits & 0xFF) + ((bits >> 8) & 0xFF) + ((bits >> 16) & 0xFF) + (bits >> 24)
    return total.astype(jnp.int32) - 510


def matmul_values(seed: jax.Array, layer: jax.Array, leaf_id: int, shape: tuple[int, int],
                  fan_in: int, quant: str, dtype: Any) -> Any:
    """One ``[rows, cols]`` weight as served: ``{"q", "scale"}`` for int8,
    else an array of ``dtype``. ``layer`` is -1 for model-level leaves."""
    rows, cols = shape
    key = _key(seed, layer, leaf_id)
    v = _noise(key, (rows, cols))
    if quant == "int8":
        q = jnp.clip((v + 2) >> 2, -127, 127).astype(jnp.int8)
        k = jax.random.bits(jax.random.fold_in(key, 1), (1, cols), jnp.uint8)
        scale = jnp.float32(3.0 / (127.0 * fan_in ** 0.5)) * (
            (k.astype(jnp.float32) + 1024.0) / 1024.0
        )
        return {"q": q, "scale": scale}
    if quant:
        raise ValueError(f"weights for quant {quant!r} are not defined")
    return (v.astype(jnp.float32) * jnp.float32(1.0 / (SD * fan_in ** 0.5))).astype(
        jnp.dtype(dtype)
    )


def norm_values(seed: jax.Array, layer: jax.Array, leaf_id: int, width: int,
                dtype: Any) -> jax.Array:
    k = jax.random.bits(_key(seed, layer, leaf_id), (width,), jnp.uint8)
    return (1.0 + (k.astype(jnp.float32) - 128.0) / 512.0).astype(jnp.dtype(dtype))


def dequantise(leaf: Any) -> jax.Array:
    """A served leaf's exact values in float32."""
    if isinstance(leaf, dict):
        return leaf["q"].astype(jnp.float32) * leaf["scale"]
    return leaf.astype(jnp.float32)
