"""Architecture ``dense_gqa``: the dense decoder of one layer type (RMSNorm,
rotary in the split-half convention, grouped-query causal softmax attention,
SwiGLU, untied head). Its leaves and their ids, the served tree, the seam
into the program and the plain reference. The contract of an architecture
module is in ``benchmark/spec.py``.

The tree handed to the program has the layout ``models/transformer.py``
serves (``embed``, ``norm_f``, ``lm_head``, ``layers`` stacked on a leading
axis; a quantised leaf is ``{"q", "scale"}``). That layout is the seam
between the benchmark and the program: see PERF.md, Open questions.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from benchmark import reference as R
from benchmark import weights as W
from benchmark.spec import SpecError

# the matmul weights of one decoder layer, in the program's names
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LEAF_IDS = {name: i for i, name in enumerate(
    LAYER_LEAVES + ("attn_norm", "mlp_norm", "embed", "lm_head", "norm_f")
)}
_MATMULS = LAYER_LEAVES + ("lm_head",)  # what the control holds in lower precision


def sizes_of(cfg: dict) -> dict:
    """The sizes this module and the dense decoder's work sheets
    (``model_work.py``, ``kernels/*.py``) need, from a configuration's keys."""
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


def leaf_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> Any:
    """One matmul weight as served. ``layer`` is -1 for the model-level
    leaves. ``embed`` is never quantised (the program's scheme keeps it
    dense) and its fan-in is the width it is read out at."""
    shape = leaf_shape(sz, name)
    embed = name == "embed"
    return W.matmul_values(seed, layer, LEAF_IDS[name], shape,
                           sz["dim"] if embed else shape[0],
                           "" if embed else sz["quant"], sz["dtype"])


def norm_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    return W.norm_values(seed, layer, LEAF_IDS[name], sz["dim"], sz["dtype"])


def layer_values(seed: jax.Array, layer: jax.Array, sz: dict) -> dict:
    """One decoder layer as served."""
    out = {n: leaf_values(seed, layer, n, sz) for n in LAYER_LEAVES}
    out["attn_norm"] = norm_values(seed, layer, "attn_norm", sz)
    out["mlp_norm"] = norm_values(seed, layer, "mlp_norm", sz)
    return out


def head_values(seed: jax.Array, sz: dict) -> dict:
    top = jnp.int32(-1)
    return {
        "embed": leaf_values(seed, top, "embed", sz),
        "norm_f": norm_values(seed, top, "norm_f", sz),
        "lm_head": leaf_values(seed, top, "lm_head", sz),
    }


def make_params(seed: int, sz: dict) -> dict:
    """The whole served tree in ONE jitted call from the seed."""

    def build(s: jax.Array) -> dict:
        tree = head_values(s, sz)
        tree["layers"] = jax.lax.map(
            lambda i: layer_values(s, i, sz), jnp.arange(sz["layers"], dtype=jnp.int32)
        )
        return tree

    return jax.jit(build)(W.seed_word(seed))


# -- the seam into the program ------------------------------------------------------

def register(run: Any) -> str:
    """The configuration's published sizes as a ``TransformerConfig`` in the
    program's table, and the seeded weights in place of the program's own
    seeded init: the two seams the benchmark has into the program.
    ``run`` gives ``cfg``, ``sizes``, ``seed`` and ``log``."""
    import gofr_tpu.models.transformer as T
    from gofr_tpu.models.llama import CONFIGS

    cfg, sz = run.cfg, run.sizes
    name = cfg["_name"]
    CONFIGS[name] = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"],
        n_heads=sz["heads"], n_kv_heads=sz["kv_heads"], hidden_dim=sz["ffn"],
        max_seq=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=jnp.dtype(sz["dtype"]),
    )
    if CONFIGS[name].head_dim != sz["head_dim"]:
        raise SpecError("the program derives head_dim as hidden/heads; "
                        f"{name} states {sz['head_dim']}")

    def seeded(key, model_cfg, quantize=False, mesh=None):
        if (quantize or "") != sz["quant"]:
            raise SpecError(f"MODEL_QUANT {quantize!r} but the configuration "
                            f"serves {sz['quant']!r}")
        start = time.monotonic()
        params = make_params(run.seed, sz)
        if mesh is not None:
            from gofr_tpu.parallel.sharding import shard_params

            params = shard_params(params, mesh)
        jax.block_until_ready(params)
        run.log(f"weights from seed {run.seed}: {time.monotonic() - start:.2f}s")
        return params

    T.init_transformer = seeded
    return name


# -- the plain reference --------------------------------------------------------------

def _weight(seed: jax.Array, layer: jax.Array, name: str, sz: dict,
            mode: Optional[str]) -> jax.Array:
    w = W.dequantise(leaf_values(seed, layer, name, sz))
    return R.degrade_weight(w, mode) if name in _MATMULS else w


def _layer_one(x: jax.Array, w: dict, sz: dict, eps: float, theta: float) -> jax.Array:
    """One decoder layer over one sequence ``x`` [T, D]."""
    t = x.shape[0]
    h, kvh, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    a = R.rms(x, w["attn_norm"], eps)
    q = R.rope((a @ w["wq"]).reshape(t, h, hd), theta)
    k = R.rope((a @ w["wk"]).reshape(t, kvh, hd), theta)
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
    m = R.rms(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _layer_weights(seed, layer, sz_items, mode):
    """One layer's weights in float32 (``mode``: as the control holds them)."""
    sz = dict(sz_items)
    w = {n: _weight(seed, layer, n, sz, mode) for n in LAYER_LEAVES}
    w["attn_norm"] = norm_values(seed, layer, "attn_norm", sz).astype(jnp.float32)
    w["mlp_norm"] = norm_values(seed, layer, "mlp_norm", sz).astype(jnp.float32)
    return w


@functools.partial(jax.jit, static_argnames=("sz_items", "eps", "theta"))
def _layer(w, x, sz_items, eps, theta):
    sz = dict(sz_items)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: _layer_one(row, w, sz, eps, theta), x)


@functools.partial(jax.jit, static_argnames=("sz_items",))
def _embed(seed, tokens, sz_items):
    table = leaf_values(seed, jnp.int32(-1), "embed", dict(sz_items))
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sz_items", "eps", "mode"))
def _head(seed, x, rows, cols, sz_items, eps, mode):
    sz = dict(sz_items)
    with jax.default_matmul_precision("highest"):
        top = jnp.int32(-1)
        norm = norm_values(seed, top, "norm_f", sz).astype(jnp.float32)
        picked = R.rms(x[rows, cols], norm, eps)
        return picked @ _weight(seed, top, "lm_head", sz, mode)


def logits_at(seed: int, cfg: dict, blocks: list[tuple], mode: Optional[str] = None):
    """Full forward over every block ``(tokens [S, T], rows, cols)`` (tokens
    right-padded; causal attention keeps padding out of earlier positions);
    yields per block the float32 logits [N, V] at the ``(rows[i], cols[i])``
    positions, each predicting the NEXT token. A layer's weights are made
    once and applied to every block, so only one layer is ever resident."""
    sz = sizes_of(cfg)
    items = tuple(sorted(sz.items()))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = W.seed_word(seed)
    xs = [_embed(s, jnp.asarray(tokens, jnp.int32), items) for tokens, _, _ in blocks]
    for i in range(sz["layers"]):
        w = _layer_weights(s, jnp.int32(i), items, mode)
        xs = [_layer(w, x, items, eps, theta) for x in xs]
    for x, (_, rows, cols) in zip(xs, blocks):
        yield _head(s, x, jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
                    items, eps, mode)
