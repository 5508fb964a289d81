"""The plain reference: the published decoder block (RMSNorm, rotary in the
split-half convention, grouped-query causal attention, SwiGLU, untied head)
in ``jax.numpy``, float32, matmul precision ``highest``; no cache, no
kernels, no batching tricks. It imports nothing of the program and takes
nothing the program has made: its weights come from ``benchmark/weights.py``
and the seed, one layer at a time so it fits beside the serving state.

What it is used for (``served_gaps``): over a request's prompt followed by
the tokens the server sent, one full forward pass gives at every served
position the reference's logits; the number compared is how far the served
token's logit lies below the reference's best there. 0 means the server
chose what the reference would have chosen.

The control (``degrade``) is this same reference computed in the nearest
precision below the one the configuration states: ``int4`` (groups of 128
input rows, absmax / 7) below int8 weights, ``int8`` (per output channel,
absmax / 127) below bfloat16, ``bf16`` below float32.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W

_MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")


def degrade_weight(w: jax.Array, mode: Optional[str]) -> jax.Array:
    """A float32 ``[in, out]`` weight as the lower precision would hold it."""
    if not mode:
        return w
    if mode == "bf16":
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0, 1e-8)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if mode == "int4":
        rows, cols = w.shape
        group = 128 if rows % 128 == 0 else rows
        g = w.reshape(rows // group, group, cols)
        scale = jnp.maximum(jnp.max(jnp.abs(g), axis=1, keepdims=True) / 7.0, 1e-8)
        return (jnp.clip(jnp.round(g / scale), -8, 7) * scale).reshape(rows, cols)
    raise ValueError(f"unknown control precision {mode!r}")


def _weight(seed: jax.Array, layer: jax.Array, name: str, sz: dict,
            mode: Optional[str]) -> jax.Array:
    w = W.dequantise(W.leaf_values(seed, layer, name, sz, sz["quant"]))
    return degrade_weight(w, mode) if name in _MATMULS else w


def _rms(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """``x`` [T, heads, head_dim] at positions 0..T-1, split-half pairs."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer_one(x: jax.Array, w: dict, sz: dict, eps: float, theta: float) -> jax.Array:
    """One decoder layer over one sequence ``x`` [T, D]."""
    t = x.shape[0]
    h, kvh, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    a = _rms(x, w["attn_norm"], eps)
    q = _rope((a @ w["wq"]).reshape(t, h, hd), theta)
    k = _rope((a @ w["wk"]).reshape(t, kvh, hd), theta)
    v = (a @ w["wv"]).reshape(t, kvh, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def group(qkv):  # one KV head with the query heads that share it
        qg, kg, vg = qkv  # [T, rep, hd], [T, hd], [T, hd]
        scores = jnp.einsum("qrd,kd->rqk", qg, kg) * (hd ** -0.5)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", probs, vg)

    grouped = q.reshape(t, kvh, h // kvh, hd).transpose(1, 0, 2, 3)
    attn = jax.lax.map(group, (grouped, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = attn.transpose(1, 0, 2, 3).reshape(t, h * hd)
    x = x + attn @ w["wo"]
    m = _rms(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _layer_weights(seed, layer, sz_items, mode):
    """One layer's weights in float32 (``mode``: as the control holds them)."""
    sz = dict(sz_items)
    w = {n: _weight(seed, layer, n, sz, mode) for n in W.LAYER_LEAVES}
    w["attn_norm"] = W.norm_values(seed, layer, "attn_norm", sz).astype(jnp.float32)
    w["mlp_norm"] = W.norm_values(seed, layer, "mlp_norm", sz).astype(jnp.float32)
    return w


@functools.partial(jax.jit, static_argnames=("sz_items", "eps", "theta"))
def _layer(w, x, sz_items, eps, theta):
    sz = dict(sz_items)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: _layer_one(row, w, sz, eps, theta), x)


@functools.partial(jax.jit, static_argnames=("sz_items",))
def _embed(seed, tokens, sz_items):
    sz = dict(sz_items)
    table = W.leaf_values(seed, jnp.int32(-1), "embed", sz, sz["quant"])
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sz_items", "eps", "mode"))
def _head(seed, x, rows, cols, sz_items, eps, mode):
    sz = dict(sz_items)
    with jax.default_matmul_precision("highest"):
        top = jnp.int32(-1)
        norm = W.norm_values(seed, top, "norm_f", sz).astype(jnp.float32)
        picked = _rms(x[rows, cols], norm, eps)
        return picked @ _weight(seed, top, "lm_head", sz, mode)


def logits_at(seed: int, cfg: dict, blocks: list[tuple], mode: Optional[str] = None):
    """Full forward over every block ``(tokens [S, T], rows, cols)`` (tokens
    right-padded; causal attention keeps padding out of earlier positions);
    yields per block the float32 logits [N, V] at the ``(rows[i], cols[i])``
    positions, each predicting the NEXT token. A layer's weights are made
    once and applied to every block, so only one layer is ever resident."""
    sz = W.sizes_of(cfg)
    items = tuple(sorted(sz.items()))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = jnp.uint32(int(seed) & 0xFFFFFFFF)
    xs = [_embed(s, jnp.asarray(tokens, jnp.int32), items) for tokens, _, _ in blocks]
    for i in range(sz["layers"]):
        w = _layer_weights(s, jnp.int32(i), items, mode)
        xs = [_layer(w, x, items, eps, theta) for x in xs]
    for x, (_, rows, cols) in zip(xs, blocks):
        yield _head(s, x, jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
                    items, eps, mode)


def pack(samples: list[tuple[list[int], list[int]]], widths: list[int], rows: int,
         scored: int) -> list[dict]:
    """``[(prompt, served)]`` -> blocks of fixed shapes (one compile per
    width): each sample goes to the narrowest of ``widths`` that holds its
    prompt and served tokens, ``rows`` samples to a block. A block is
    ``tokens`` [rows, width] right-padded, and for every served token the
    position that predicts it (``rows``, ``cols``) and the token itself
    (``served``), padded to ``scored`` entries of which ``n`` are real;
    ``sample`` says which sample each entry came from."""
    by_width: dict[int, list[int]] = {}
    for i, (prompt, out) in enumerate(samples):
        need = len(prompt) + len(out)
        fits = [w for w in sorted(widths) if w >= need]
        if not fits:
            raise ValueError(f"a sample of {need} tokens exceeds the check's widths {widths}")
        by_width.setdefault(fits[0], []).append(i)
    blocks = []
    for width in sorted(by_width):
        members = by_width[width]
        for at in range(0, len(members), rows):
            tokens = np.zeros((rows, width), np.int32)
            r_idx, c_idx, served, origin = [], [], [], []
            for r, i in enumerate(members[at: at + rows]):
                prompt, out = samples[i]
                seq = list(prompt) + list(out)
                tokens[r, : len(seq)] = seq
                for j, tok in enumerate(out):
                    r_idx.append(r)
                    c_idx.append(len(prompt) + j - 1)
                    served.append(tok)
                    origin.append(i)
            n = len(served)
            if n > scored:
                raise ValueError(f"{n} served tokens exceed a block's {scored} scored positions")
            pad = [0] * (scored - n)
            blocks.append({"tokens": tokens, "rows": np.asarray(r_idx + pad),
                           "cols": np.asarray(c_idx + pad), "served": np.asarray(served + pad),
                           "sample": np.asarray(origin), "n": n})
    return blocks


def served_gaps(seed: int, cfg: dict, samples: list[tuple[list[int], list[int]]],
                widths: list[int], rows: int, scored: int,
                control: Optional[str] = None) -> dict[str, Any]:
    """The comparison that decides ``correct``. -> ``gaps`` (per served
    token: reference's best logit minus the served token's logit, >= 0),
    ``sample`` (which sample each came from), ``agree`` (share of served
    tokens that ARE the reference's best) and, with ``control``,
    ``control_gaps``: the same reading for the token the lower precision
    puts first at each position."""
    blocks = pack(samples, widths, rows, scored)
    feed = [(b["tokens"], b["rows"], b["cols"]) for b in blocks]

    def at(logits, tokens, n):  # the logit of one token per scored position
        return np.asarray(jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], axis=-1)[:, 0])[:n]

    low_first = [None] * len(blocks)
    if control:
        low_first = [np.asarray(jnp.argmax(logits, axis=-1))
                     for logits in logits_at(seed, cfg, feed, control)]
    best, picked, first, at_low = [], [], [], []
    for b, low, logits in zip(blocks, low_first, logits_at(seed, cfg, feed)):
        n = b["n"]
        best.append(np.asarray(jnp.max(logits, axis=-1))[:n])
        picked.append(at(logits, b["served"], n))
        first.append(np.asarray(jnp.argmax(logits, axis=-1))[:n])
        if control:
            at_low.append(at(logits, low, n))
    best = np.concatenate(best)
    served = np.concatenate([b["served"][: b["n"]] for b in blocks])
    out: dict[str, Any] = {
        "gaps": best - np.concatenate(picked),
        "agree": float(np.mean(np.concatenate(first) == served)),
        "sample": np.concatenate([b["sample"] for b in blocks]),
    }
    if control:
        out["control_gaps"] = best - np.concatenate(at_low)
    return out
