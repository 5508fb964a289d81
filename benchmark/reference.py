"""The plain reference's shared parts, and the comparison that decides
``correct``. They know no model. The forward pass itself (float32, matmul
precision ``highest``; no cache, no kernels, no batching tricks; its own
weights from the seed, one layer resident at a time) is the ``logits_at``
of the configuration's architecture, ``benchmark/architectures/<name>.py``.
It imports nothing of the program and takes nothing the program has made.

Here: the pieces such a forward pass is built from (``rms``; ``rope``,
rotary in the split-half convention; ``degrade_weight``), the packing of
served requests into blocks of fixed shapes (``pack``), and
``served_gaps``, which takes the architecture's ``logits_at``: over a
request's prompt followed by the tokens the server sent, one full forward
pass gives at every served position the reference's logits; the number
compared is how far the served token's logit lies below the reference's
best there. 0 means the server chose what the reference would have chosen.

The control (``degrade_weight``) is the same reference computed in the
nearest precision below the one the configuration states: ``int4`` (groups
of 128 input rows, absmax / 7) below int8 weights, ``int8`` (per output
channel, absmax / 127) below bfloat16, ``bf16`` below float32.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


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


def rms(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x: jax.Array, theta: float) -> jax.Array:
    """``x`` [T, heads, head_dim] at positions 0..T-1, split-half pairs."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


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


def served_gaps(logits_at: Callable, seed: int, cfg: dict,
                samples: list[tuple[list[int], list[int]]], widths: list[int], rows: int,
                scored: int, control: Optional[str] = None) -> dict[str, Any]:
    """The comparison that decides ``correct``, against the architecture's
    ``logits_at(seed, cfg, blocks, mode=None)``. -> ``gaps`` (per served
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
