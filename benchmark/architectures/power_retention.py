"""Architecture ``power_retention``: a decoder whose attention is power
retention of degree 2 (Brumby-14B-Base on Qwen3-14B's skeleton). RMSNorm,
q/k/v projections, RMSNorm over each head of q and k, rotary in the
split-half convention, a gate per kv head, retention, SwiGLU, untied head.
The contract of an architecture module is in ``benchmark/spec.py``.

One layer, for token t of a sequence, kv head h with the query heads that
share it, head size d::

    a = rms(x);  q, k, v = a Wq, a Wk, a Wv;  q, k = rope(rms_head(q)), rope(rms_head(k))
    log g_t = logsigmoid(a W_g)                      (float32, one per kv head)
    w_tj = exp(sum_{s=j+1..t} log g_s) (q_t . k_j / sqrt d)^2        (j <= t)
    y_t = sum_j w_tj v_j / (sum_j w_tj + 1e-6)
    x = x + y Wo;  x = x + (silu(m Wgate) * m Wup) Wdown,  m = rms(x)

``logits_at`` is that attention form and nothing else: float32, matmul
precision ``highest``, one layer resident, no state and no cache, blocked
over queries so that 13k positions fit. It shares no code with the
program's ``ops/retention.py`` (which serves the recurrent and chunked
forms over a state). The decay between two positions is summed from the
query's block backwards, block by block, so that it is exact where it is
not negligible: a plain difference of two cumulative sums loses three
digits at position 10,000.

The tree handed to the program has ``models/transformer.py``'s layout with
the three leaves this attention kind adds (``q_norm``, ``k_norm``, ``w_g``).
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

EPS = 1e-6  # the configuration's ``assumed.normaliser``
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_g")
_NORMS = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "norm_f")
# ids of its own: no leaf of this model is a leaf of ``dense_gqa``
LEAF_IDS = {name: 64 + i for i, name in enumerate(LAYER_LEAVES + _NORMS + ("embed", "lm_head"))}
_MATMULS = LAYER_LEAVES + ("lm_head",)  # what the control holds in lower precision
QUERY_BLOCK = 256  # queries whose weights over all keys exist at once


def sizes_of(cfg: dict) -> dict:
    heads, head_dim = cfg["num_attention_heads"], cfg["head_dim"]
    if heads * head_dim != cfg["hidden_size"]:
        raise SpecError("the program derives head_dim as hidden/heads; "
                        f"{cfg.get('_name')} states {head_dim} x {heads} != {cfg['hidden_size']}")
    return {
        "dim": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": head_dim, "ffn": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"], "quant": cfg["serving"]["quant"],
        "dtype": cfg["serving"].get("dtype", "bfloat16"),
        # phi's entries (program: ops/retention.py::phi_dim); the work sheets' state row
        "phi": (head_dim // 2 + 1) * head_dim,
    }


def leaf_shape(sz: dict, name: str) -> tuple[int, int]:
    d, kv = sz["dim"], sz["kv_heads"] * sz["head_dim"]
    return {
        "wq": (d, sz["heads"] * sz["head_dim"]), "wk": (d, kv), "wv": (d, kv),
        "wo": (sz["heads"] * sz["head_dim"], d), "w_gate": (d, sz["ffn"]),
        "w_up": (d, sz["ffn"]), "w_down": (sz["ffn"], d), "w_g": (d, sz["kv_heads"]),
        "embed": (sz["vocab"], d), "lm_head": (d, sz["vocab"]),
    }[name]


def leaf_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    """One matmul weight as served (``layer`` -1 for model-level leaves).
    The embedding's fan-in is the width it is read out at."""
    shape = leaf_shape(sz, name)
    return W.matmul_values(seed, layer, LEAF_IDS[name], shape,
                           sz["dim"] if name == "embed" else shape[0], "", sz["dtype"])


def norm_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    width = sz["head_dim"] if name in ("q_norm", "k_norm") else sz["dim"]
    return W.norm_values(seed, layer, LEAF_IDS[name], width, sz["dtype"])


def layer_values(seed: jax.Array, layer: jax.Array, sz: dict) -> dict:
    out = {n: leaf_values(seed, layer, n, sz) for n in LAYER_LEAVES}
    out.update({n: norm_values(seed, layer, n, sz) for n in _NORMS if n != "norm_f"})
    return out


def make_params(seed: int, sz: dict) -> dict:
    """The whole served tree in ONE jitted call from the seed."""

    def build(s: jax.Array) -> dict:
        top = jnp.int32(-1)
        return {
            "embed": leaf_values(s, top, "embed", sz),
            "norm_f": norm_values(s, top, "norm_f", sz),
            "lm_head": leaf_values(s, top, "lm_head", sz),
            "layers": jax.lax.map(lambda i: layer_values(s, i, sz),
                                  jnp.arange(sz["layers"], dtype=jnp.int32)),
        }

    return jax.jit(build)(W.seed_word(seed))


# -- the seam into the program ------------------------------------------------------

def register(run: Any) -> str:
    """The published sizes as a ``TransformerConfig`` of attention kind
    ``retention`` in the program's table, and the seeded weights in place
    of the program's own seeded init."""
    import gofr_tpu.models.transformer as T
    from gofr_tpu.models.llama import CONFIGS

    cfg, sz = run.cfg, run.sizes
    if sz["quant"]:
        raise SpecError("power_retention is served unquantised; the configuration "
                        f"states quant {sz['quant']!r}")
    if "attn_kind" not in T.TransformerConfig.__dataclass_fields__:
        raise SpecError("this program has no attention kind: it cannot serve a model "
                        "whose cache is a retention state")
    name = cfg["_name"]
    CONFIGS[name] = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"],
        n_heads=sz["heads"], n_kv_heads=sz["kv_heads"], hidden_dim=sz["ffn"],
        max_seq=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=jnp.dtype(sz["dtype"]),
        attn_kind="retention",
    )

    def seeded(key, model_cfg, quantize=False, mesh=None):
        if quantize or mesh is not None:
            raise SpecError("power_retention is served unquantised on one chip")
        start = time.monotonic()
        params = make_params(run.seed, sz)
        jax.block_until_ready(params)
        run.log(f"weights from seed {run.seed}: {time.monotonic() - start:.2f}s")
        return params

    T.init_transformer = seeded
    return name


# -- the plain reference --------------------------------------------------------------

def _retention(q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array) -> jax.Array:
    """The attention form for one kv head: q [T, R, d], k, v [T, d],
    log_g [T] -> [T, R, d]."""
    t, _, d = q.shape
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    nb = t // blk
    local = jnp.cumsum(log_g.reshape(nb, blk), axis=1)  # within each block, inclusive
    totals = local[:, -1]
    pos = jnp.arange(t)

    def block(qb):
        # decay from key j to the END of the block before qb, then to query i
        before = jnp.where(jnp.arange(nb) < qb, totals, 0.0)
        to_block = jnp.cumsum(before[::-1])[::-1]  # sum of totals of blocks kb..qb-1
        from_key = (jnp.repeat(to_block, blk) - local.reshape(t))  # [T]
        qpos = qb * blk + jnp.arange(blk)
        log_decay = local[qb][:, None] + from_key[None, :]  # [blk, T]
        seen = pos[None, :] <= qpos[:, None]
        decay = jnp.exp(jnp.where(seen, log_decay, -jnp.inf))
        qs = jax.lax.dynamic_slice_in_dim(q, qb * blk, blk, 0)
        scores = jnp.einsum("qrd,kd->rqk", qs, k) * (d ** -0.5)
        w = decay[None] * scores * scores
        num = jnp.einsum("rqk,kd->qrd", w, v)
        return num / (jnp.sum(w, axis=-1).T[..., None] + EPS)

    return jax.lax.map(block, jnp.arange(nb)).reshape(q.shape)


def _layer_one(x: jax.Array, w: dict, sz: dict, eps: float, theta: float) -> jax.Array:
    """One decoder layer over one sequence ``x`` [T, D]."""
    t = x.shape[0]
    h, kvh, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    a = R.rms(x, w["attn_norm"], eps)
    q = R.rope(R.rms((a @ w["wq"]).reshape(t, h, hd), w["q_norm"], eps), theta)
    k = R.rope(R.rms((a @ w["wk"]).reshape(t, kvh, hd), w["k_norm"], eps), theta)
    v = (a @ w["wv"]).reshape(t, kvh, hd)
    log_g = jax.nn.log_sigmoid(a @ w["w_g"])  # [T, kvh]
    grouped = q.reshape(t, kvh, h // kvh, hd).transpose(1, 0, 2, 3)
    y = jax.lax.map(lambda args: _retention(*args),
                    (grouped, k.transpose(1, 0, 2), v.transpose(1, 0, 2), log_g.T))
    x = x + y.transpose(1, 0, 2, 3).reshape(t, h * hd) @ w["wo"]
    m = R.rms(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _layer_weights(seed, layer, sz_items, mode):
    """One layer's weights in float32 (``mode``: as the control holds them)."""
    sz = dict(sz_items)
    w = {n: R.degrade_weight(leaf_values(seed, layer, n, sz).astype(jnp.float32), mode)
         for n in LAYER_LEAVES}
    w.update({n: norm_values(seed, layer, n, sz).astype(jnp.float32)
              for n in _NORMS if n != "norm_f"})
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
        head = R.degrade_weight(leaf_values(seed, top, "lm_head", sz).astype(jnp.float32), mode)
        return R.rms(x[rows, cols], norm, eps) @ head


def logits_at(seed: int, cfg: dict, blocks: list[tuple], mode: Optional[str] = None):
    """Full forward over every block ``(tokens [S, T], rows, cols)`` (tokens
    right-padded; a weight is zero for a key after its query, so padding
    stays out of earlier positions); yields per block the float32 logits
    [N, V] at the ``(rows[i], cols[i])`` positions, each predicting the NEXT
    token. One layer's weights are resident at a time."""
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
