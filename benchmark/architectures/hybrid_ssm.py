"""Architecture ``hybrid_ssm``: a decoder whose layers are of two kinds in one
stack: selective state-space (Mamba-1) mixers with the Jamba family's three
inner norms, and causal softmax attention WITHOUT any positional embedding
(the state-space layers carry position) every ``attn_layer_period``-th layer;
every layer ends in a dense SwiGLU; RMSNorm, tied head. AI21-Jamba2-3B. The
contract of an architecture module is in ``benchmark/spec.py``.

Layer i attends where ``i % attn_layer_period == attn_layer_offset``. Every
layer, for token t of a sequence (anything before its first token is zero)::

    x = x + mixer(rms(x));  x = x + (silu(m Wgate) * m Wup) Wdown,  m = rms(x)
    logits = rms(x) embed^T

state-space mixer (Di = mamba_expand * hidden channels, N = mamba_d_state,
R = mamba_dt_rank, K = mamba_d_conv), h = rms(x)::

    [u, z] = h W_in                                        (no bias)
    u_t = silu(b_c + sum_{j<K} w_c[j] u_{t-(K-1)+j})       (depthwise, causal)
    [dt, B, C] = u W_x  (R | N | N);  dt, B, C = rms(dt), rms(B), rms(C)
    delta = softplus(dt W_dt + b_dt) [Di];  A = -exp(A_log) [N, Di]
    s_t = exp(delta_t A) * s_{t-1} + (delta_t u_t) B_t^T   (s_{-1} = 0)
    y_t = s_t C_t + D u_t;   out = (y * silu(z)) W_out

attention mixer (H query heads on G kv heads of size d = hidden / H)::

    q, k, v = h Wq, h Wk, h Wv  (no bias, no rotary)
    out = causal-softmax(q k^T / sqrt(d)) v Wo

``logits_at`` is that and nothing else: float32, matmul precision
``highest``, one layer resident, no cache, no state carried between calls, no
tail (the convolution runs over the whole sequence with zero left padding),
no kernel: the recurrence token by token under ``lax.scan``, as written. The
state is laid ``[N, Di]`` (the channels in the minor place, where 16 entries
would be padded to 128 on the chip); that is a layout, not a form. It
imports nothing of ``gofr_tpu/ops/ssm.py``.

What the seed does NOT make with 1 / sqrt(fan-in) noise (there it gives
states that forget in a token or overflow), but by the family's
initialisation: ``A_log = log(1..N)`` in every channel, and ``b_dt`` the
inverse softplus of a step size spread log-uniformly over [0.001, 0.1] by a
seeded byte a channel. ``D`` is a norm-like weight (1 +- 1/4).
"""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from benchmark import reference as R
from benchmark import weights as W
from benchmark.spec import SpecError

SSM_MATMULS = ("ssm_in", "ssm_x", "ssm_dt", "ssm_out")
ATTN_MATMULS = ("wq", "wk", "wv", "wo")
FFN = ("w_gate", "w_up", "w_down")
_NORMS = ("attn_norm", "mlp_norm", "ssm_dt_norm", "ssm_b_norm", "ssm_c_norm", "norm_f")
_VECTORS = ("ssm_conv_b", "ssm_dt_b", "ssm_d")
# ids of its own: no leaf of this model is a leaf of another architecture
LEAF_IDS = {name: 320 + i for i, name in enumerate(
    SSM_MATMULS + ATTN_MATMULS + FFN + _NORMS + _VECTORS + ("ssm_conv_w", "embed"))}
STEP_MIN, STEP_MAX = 0.001, 0.1  # softplus(b_dt) lies between them
SCAN_UNROLL = 8  # tokens a loop step of the reference's recurrence: the same sums, fewer steps


def kinds_of(cfg: dict) -> tuple[str, ...]:
    """The mixer of every layer, from the period and the offset (the
    catalog's ``not_given``: the configuration's ``assumed.layer_order``)."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return tuple("softmax" if i % period == offset else "ssm"
                 for i in range(cfg["num_hidden_layers"]))


def sizes_of(cfg: dict) -> dict:
    if cfg["num_experts"] != 1 or cfg["num_experts_per_tok"] != 1:
        raise SpecError("hybrid_ssm is written for the dense feed-forward (num_experts 1); "
                        f"{cfg.get('_name')} states {cfg['num_experts']}")
    if not cfg["tie_word_embeddings"] or not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]:
        raise SpecError("hybrid_ssm is written with a tied head, a biased convolution and "
                        "projections without bias")
    heads = cfg["num_attention_heads"]
    if cfg["hidden_size"] % heads:
        raise SpecError("the head size is hidden / heads")
    kinds = kinds_of(cfg)
    return {
        "dim": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads, "ffn": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"], "quant": cfg["serving"]["quant"],
        "dtype": cfg["serving"].get("dtype", "bfloat16"),
        "d_inner": cfg["mamba_expand"] * cfg["hidden_size"], "d_state": cfg["mamba_d_state"],
        "d_conv": cfg["mamba_d_conv"], "dt_rank": cfg["mamba_dt_rank"],
        "attn_layers": kinds.count("softmax"), "ssm_layers": kinds.count("ssm"),
        "attn_period": cfg["attn_layer_period"], "attn_offset": cfg["attn_layer_offset"],
    }


def leaf_shape(sz: dict, name: str) -> tuple[int, int]:
    d, di, n, r = sz["dim"], sz["d_inner"], sz["d_state"], sz["dt_rank"]
    kv = sz["kv_heads"] * sz["head_dim"]
    return {
        "ssm_in": (d, 2 * di), "ssm_x": (di, r + 2 * n), "ssm_dt": (r, di), "ssm_out": (di, d),
        "ssm_conv_w": (sz["d_conv"], di),
        "wq": (d, d), "wk": (d, kv), "wv": (d, kv), "wo": (d, d),
        "w_gate": (d, sz["ffn"]), "w_up": (d, sz["ffn"]), "w_down": (sz["ffn"], d),
        "embed": (sz["vocab"], d),
    }[name]


def leaf_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    """One matmul weight (or the convolution's taps [K, Di], fan-in K) as
    served; ``layer`` is the layer's place in the whole stack, -1 for the
    embedding, whose fan-in is the width it is read out at."""
    shape = leaf_shape(sz, name)
    return W.matmul_values(seed, layer, LEAF_IDS[name], shape,
                           sz["dim"] if name == "embed" else shape[0], "", sz["dtype"])


def vector_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    """Norm weights (1 +- 1/4) in the model's type; the convolution's bias
    (+- 1/4) in it too; float32, as the scan takes them: ``D`` (1 +- 1/4)
    and ``b_dt``, the inverse softplus of a step size log-uniform over
    [STEP_MIN, STEP_MAX] (a seeded byte a channel)."""
    di = sz["d_inner"]
    width = {"ssm_dt_norm": sz["dt_rank"], "ssm_b_norm": sz["d_state"],
             "ssm_c_norm": sz["d_state"], "ssm_conv_b": di, "ssm_dt_b": di, "ssm_d": di,
             }.get(name, sz["dim"])
    v = W.norm_values(seed, layer, LEAF_IDS[name], width, "float32")
    if name == "ssm_d":
        return v
    if name == "ssm_dt_b":
        byte = (v - 1.0) * 512.0 + 128.0  # 0..255
        step = jnp.exp(math.log(STEP_MIN) + byte / 255.0 * math.log(STEP_MAX / STEP_MIN))
        return step + jnp.log(-jnp.expm1(-step))
    if name == "ssm_conv_b":
        v = v - 1.0
    return v.astype(jnp.dtype(sz["dtype"]))


def a_log(sz: dict) -> jax.Array:
    """log(1..N) down the state's entries, the same in every channel and
    layer: [N, Di] float32."""
    n = sz["d_state"]
    return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                            (n, sz["d_inner"]))


def layer_values(seed: jax.Array, layer: jax.Array, kind: str, sz: dict) -> dict:
    """One layer's served leaves, under the names ``models/transformer.py``
    gives a layer of that kind."""
    out = {n: leaf_values(seed, layer, n, sz) for n in FFN}
    out.update({n: vector_values(seed, layer, n, sz) for n in ("attn_norm", "mlp_norm")})
    if kind == "softmax":
        out.update({n: leaf_values(seed, layer, n, sz) for n in ATTN_MATMULS})
        return out
    out.update({n: leaf_values(seed, layer, n, sz) for n in SSM_MATMULS + ("ssm_conv_w",)})
    out.update({n: vector_values(seed, layer, n, sz)
                for n in ("ssm_dt_norm", "ssm_b_norm", "ssm_c_norm") + _VECTORS})
    out["ssm_a_log"] = a_log(sz)
    return out


def _layer_ids(sz: dict, kind: str) -> list[int]:
    return [i for i in range(sz["layers"])
            if (i % sz["attn_period"] == sz["attn_offset"]) == (kind == "softmax")]


def make_params(seed: int, sz: dict) -> dict:
    """The whole served tree in ONE jitted call from the seed: the layers
    stacked per kind, a layer at its place among its kind (tied: no
    ``lm_head``)."""

    def build(s: jax.Array) -> dict:
        top = jnp.int32(-1)
        return {
            "embed": leaf_values(s, top, "embed", sz),
            "norm_f": vector_values(s, top, "norm_f", sz),
            "layers": {
                kind: jax.lax.map(lambda i, kind=kind: layer_values(s, i, kind, sz),
                                  jnp.asarray(_layer_ids(sz, kind), jnp.int32))
                for kind in ("ssm", "softmax")
            },
        }

    return jax.jit(build)(W.seed_word(seed))


# -- the seam into the program ------------------------------------------------------

def register(run: Any) -> str:
    """The published sizes as a ``TransformerConfig`` with a mixer a layer
    (``layer_kinds``) in the program's table, and the seeded weights in
    place of the program's own seeded init."""
    import gofr_tpu.models.transformer as T
    from gofr_tpu.models.llama import CONFIGS

    cfg, sz = run.cfg, run.sizes
    if sz["quant"]:
        raise SpecError(f"hybrid_ssm is served unquantised; the configuration states "
                        f"quant {sz['quant']!r}")
    if "layer_kinds" not in T.TransformerConfig.__dataclass_fields__:
        raise SpecError("this program's layers are all of one kind: it cannot serve a model "
                        "with state-space and attention layers in one stack")
    name = cfg["_name"]
    CONFIGS[name] = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"],
        n_heads=sz["heads"], n_kv_heads=sz["kv_heads"], hidden_dim=sz["ffn"],
        max_seq=cfg["max_position_embeddings"], rope_fraction=0.0,
        norm_eps=float(cfg["rms_norm_eps"]), dtype=jnp.dtype(sz["dtype"]),
        layer_kinds=kinds_of(cfg), ssm_state=sz["d_state"], ssm_conv=sz["d_conv"],
        ssm_dt_rank=sz["dt_rank"], ssm_expand=cfg["mamba_expand"], tie_embeddings=True,
    )

    def seeded(key, model_cfg, quantize=False, mesh=None):
        if quantize or mesh is not None:
            raise SpecError("hybrid_ssm is served unquantised on one chip")
        start = time.monotonic()
        params = make_params(run.seed, sz)
        jax.block_until_ready(params)
        run.log(f"weights from seed {run.seed}: {time.monotonic() - start:.2f}s")
        return params

    T.init_transformer = seeded
    return name


# -- the plain reference --------------------------------------------------------------

def selective_scan(u: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array) -> jax.Array:
    """The recurrence as written, token by token from a zero state: u,
    delta [T, Di]; a [N, Di]; b, c [T, N] -> y [T, Di] (without ``D u``)."""
    def step(s, xs):
        u_t, d_t, b_t, c_t = xs
        s = jnp.exp(d_t[None, :] * a) * s + (d_t * u_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32), (u, delta, b, c),
                        unroll=SCAN_UNROLL)
    return y


def _ssm_one(x: jax.Array, w: dict, sz: dict, eps: float) -> jax.Array:
    """The state-space mixer over one sequence ``x`` [T, D] (residual added)."""
    t = x.shape[0]
    di, n, r, taps = sz["d_inner"], sz["d_state"], sz["dt_rank"], sz["d_conv"]
    uz = R.rms(x, w["attn_norm"], eps) @ w["ssm_in"]
    u, z = uz[:, :di], uz[:, di:]
    seen = jnp.concatenate([jnp.zeros((taps - 1, di), u.dtype), u], axis=0)
    u = jax.nn.silu(w["ssm_conv_b"] + sum(w["ssm_conv_w"][j] * seen[j:j + t]
                                          for j in range(taps)))
    dbc = u @ w["ssm_x"]
    dt = R.rms(dbc[:, :r], w["ssm_dt_norm"], eps)
    b = R.rms(dbc[:, r:r + n], w["ssm_b_norm"], eps)
    c = R.rms(dbc[:, r + n:], w["ssm_c_norm"], eps)
    delta = jax.nn.softplus(dt @ w["ssm_dt"] + w["ssm_dt_b"])
    y = selective_scan(u, delta, -jnp.exp(w["ssm_a_log"]), b, c) + w["ssm_d"] * u
    return x + (y * jax.nn.silu(z)) @ w["ssm_out"]


def _attention_one(x: jax.Array, w: dict, sz: dict, eps: float) -> jax.Array:
    """Causal softmax attention over one sequence ``x`` [T, D], no rotary
    (residual added)."""
    t = x.shape[0]
    h, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    a = R.rms(x, w["attn_norm"], eps)
    q = (a @ w["wq"]).reshape(t, g, h // g, d)
    k, v = (a @ w["wk"]).reshape(t, g, d), (a @ w["wv"]).reshape(t, g, d)
    scores = jnp.einsum("tgrd,sgd->grts", q, k) * (d ** -0.5)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("grts,sgd->tgrd", weights, v).reshape(t, h * d)
    return x + out @ w["wo"]


def _layer_one(x: jax.Array, w: dict, kind: str, sz: dict, eps: float) -> jax.Array:
    x = (_attention_one if kind == "softmax" else _ssm_one)(x, w, sz, eps)
    m = R.rms(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("kind", "sz_items", "mode"))
def _layer_weights(seed, layer, kind, sz_items, mode):
    """One layer's weights in float32 (``mode``: every matmul leaf as the
    control holds it; the convolution's taps, the vectors and ``A_log`` are
    not matmul leaves)."""
    sz = dict(sz_items)
    w = {n: v.astype(jnp.float32) for n, v in layer_values(seed, layer, kind, sz).items()}
    for n in FFN + (ATTN_MATMULS if kind == "softmax" else SSM_MATMULS):
        w[n] = R.degrade_weight(w[n], mode)
    return w


@functools.partial(jax.jit, static_argnames=("kind", "sz_items", "eps"))
def _layer(w, x, kind, sz_items, eps):
    sz = dict(sz_items)
    one = lambda row: _layer_one(row, w, kind, sz, eps)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        # a block's sequences side by side through the recurrence (its steps
        # are the time; a sequence is independent of its neighbours), one
        # after another through attention (its scores are the memory)
        return jax.vmap(one)(x) if kind == "ssm" else jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _table_and_head(seed, sz_items, mode):
    """The tied table as served, and as a float32 head [D, V] (the control
    degrades it by output channel) with the final norm's weight."""
    sz = dict(sz_items)
    top = jnp.int32(-1)
    table = leaf_values(seed, top, "embed", sz)
    head = R.degrade_weight(table.astype(jnp.float32).T, mode)
    return table, head, vector_values(seed, top, "norm_f", sz).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(head, norm, x, rows, cols, eps):
    with jax.default_matmul_precision("highest"):
        return R.rms(x[rows, cols], norm, eps) @ head


def logits_at(seed: int, cfg: dict, blocks: list[tuple], mode: Optional[str] = None):
    """Full forward over every block ``(tokens [S, T], rows, cols)`` (tokens
    right-padded: every part of a layer is causal or by the token, so
    padding stays out of earlier positions); yields per block the float32
    logits [N, V] at the ``(rows[i], cols[i])`` positions, each predicting
    the NEXT token. One layer's weights are resident at a time."""
    sz = sizes_of(cfg)
    items = tuple(sorted(sz.items()))
    eps = float(cfg["rms_norm_eps"])
    s = W.seed_word(seed)
    table, head, norm = _table_and_head(s, items, mode)
    xs = [table[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32) for tokens, _, _ in blocks]
    del table
    for i, kind in enumerate(kinds_of(cfg)):
        w = _layer_weights(s, jnp.int32(i), kind, items, mode)
        xs = [_layer(w, x, kind, items, eps) for x in xs]
    for x, (_, rows, cols) in zip(xs, blocks):
        yield _head(head, norm, x, jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32), eps)
