"""Architecture ``cca_moe``: a decoder whose every layer is one CCA attention
sublayer (softmax attention in a latent narrower than the hidden size, q and
k mixed by two causal convolutions over the sequence, v shifted by a token)
and one mixture-of-experts sublayer (top-1 of E SwiGLU experts behind an MLP
router whose state passes down the stack); RMSNorm, half-rotary, tied head.
ZAYA1-8B. The contract of an architecture module is in ``benchmark/spec.py``.

One layer, for token t of a sequence (anything before its first token is
zero); H query heads, G kv heads, r = H / G, head size d::

    a = rms(x);  q~ = a Wq [H, d];  k~ = a Wk [G, d];  c = concat(q~, k~)
    c1_t = w0[0] c_{t-1} + w0[1] c_t + b0                        (by channel)
    c2_t[g] = c1_{t-1}[g] W1[0, g] + c1_t[g] W1[1, g] + b1[g]    (by head, d -> d)
    mq[h] = (q~[h] + k~[h // r]) / 2;   mk[g] = mean of mq over g's r heads
    q = c2[:H] + mq;  k = c2[H:] + mk
    q[h] = sqrt(d) q[h] / |q[h]|;   k[g] = sqrt(d) temp[g] k[g] / |k[g]|
    q, k = rope over dims 0..d/2-1 of a head (split-half within them)
    u = a Wv;  v_t = concat(u_t[:Gd/2], u_{t-1}[Gd/2:]) viewed as [G, d]
    x = x + softmax-attention(q, k, v, scale 1/sqrt(d)) Wo
    m = rms(x);  rt^l = m Wd + bd + gamma^l rt^{l-1}             (rt^{-1} = 0)
    z = W3 gelu(W2 gelu(W1 rms(rt^l) + b1) + b2);  p = softmax(z);  e = argmax p
    x = x + p[e] (silu(m Wgate[e]) * m Wup[e]) Wdown[e]
    logits = rms(x) embed^T

|.| is sqrt(sum of squares + 1e-6); gelu is the exact (erf) form.
``logits_at`` is that and nothing else: float32, matmul precision
``highest``, one layer resident, no cache and no tail (the convolutions and
the shift run over the whole sequence with zero left padding), no sort and
no grouped kernel: each expert takes its tokens by index (``nonzero`` padded
to a count fixed from the fullest expert) and multiplies those. Every
expert over every token under a mask, the plainest form at 16 times the
FLOPs, is ``experts_masked`` below: the CPU tests hold the indexed form to
it. Nothing here is shared with ``gofr_tpu/``.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as R
from benchmark import weights as W
from benchmark.spec import SpecError

L2_EPS = 1e-6  # the configuration's ``assumed.l2_norm``
MATMULS = ("wq", "wk", "wv", "wo", "router_down", "router_w1", "router_w2", "router_w3")
EXPERTS = ("w_gate", "w_up", "w_down")
_NORMS = ("attn_norm", "mlp_norm", "router_norm", "norm_f")
_BIASES = ("cca_b0", "cca_b1", "router_down_b", "router_b1", "router_b2")
# ids of its own: no leaf of this model is a leaf of another architecture.
# A leaf stacked over experts (or taps and heads) takes one id a slice.
LEAF_IDS = {name: 128 + i for i, name in enumerate(
    MATMULS + _NORMS + _BIASES + ("router_gamma", "embed", "cca_w0"))}
LEAF_IDS.update(cca_w1=160, w_gate=192, w_up=224, w_down=256)
# The router is seeded in the linear range of its gelus, so that no expert
# is favoured whatever the token, as a trained router is balanced: a hidden
# layer's weights an eighth of 1 / sqrt(fan-in) and the last layer's 128
# times it (the logits spread as they would at 1: p[e] about 0.14 of 16),
# each bias an eighth of the others' size at its layer's scale. At 1
# throughout, a gelu's mean makes a constant of each layer's logits that
# outweighs the token's part, and the experts a step reads differ by seed.
_ROUTER_GAIN = {"router_w1": 1 / 8, "router_w2": 1 / 8, "router_w3": 128.0,
                "router_down_b": 1 / 8, "router_b1": 1 / 64, "router_b2": 1 / 1024}
ROW_COUNT = 128  # an expert's tokens are padded to this times a power of two: a handful of
# programs whatever the seed (each count is a compile; a warm run has 340 s in all)


def sizes_of(cfg: dict) -> dict:
    if (cfg["cca_time0"], cfg["cca_time1"], cfg["num_experts_per_tok"]) != (2, 2, 1):
        raise SpecError("cca_moe is written for convolutions of kernel 2 and top-1 routing; "
                        f"{cfg.get('_name')} states {cfg['cca_time0']}, {cfg['cca_time1']} and "
                        f"{cfg['num_experts_per_tok']}")
    if not cfg["tie_word_embeddings"]:
        raise SpecError("cca_moe is written with a tied head")
    return {
        "dim": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"], "ffn": cfg["moe_intermediate_size"],
        "experts": cfg["num_experts"], "router": cfg["router_hidden_size"],
        "vocab": cfg["vocab_size"], "quant": cfg["serving"]["quant"],
        "dtype": cfg["serving"].get("dtype", "bfloat16"),
        "rope_fraction": float(cfg["partial_rotary_factor"]),
    }


def leaf_shape(sz: dict, name: str) -> tuple[int, int]:
    """[in, out] of a matmul leaf (of one expert's slice of a stacked one)."""
    d, r = sz["dim"], sz["router"]
    qd, kvd = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    return {
        "wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
        "router_down": (d, r), "router_w1": (r, r), "router_w2": (r, r),
        "router_w3": (r, sz["experts"]), "w_gate": (d, sz["ffn"]), "w_up": (d, sz["ffn"]),
        "w_down": (sz["ffn"], d), "embed": (sz["vocab"], d),
    }[name]


def leaf_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    """One matmul weight as served (``layer`` -1 for the embedding, whose
    fan-in is the width it is read out at); a leaf of ``EXPERTS`` comes
    stacked [E, in, out]."""
    shape = leaf_shape(sz, name)
    fan_in = sz["dim"] if name == "embed" else shape[0] / _ROUTER_GAIN.get(name, 1) ** 2
    one = lambda leaf_id: W.matmul_values(  # noqa: E731
        seed, layer, leaf_id, shape, fan_in, "", sz["dtype"])
    if name in EXPERTS:
        return jax.vmap(one)(LEAF_IDS[name] + jnp.arange(sz["experts"]))
    return one(LEAF_IDS[name])


def conv_values(seed: jax.Array, layer: jax.Array, sz: dict) -> dict:
    """The two convolutions' weights: ``cca_w0`` [tap, channel] (fan-in 2)
    and ``cca_w1`` [tap, head, in, out] (fan-in 2 d)."""
    heads, d = sz["heads"] + sz["kv_heads"], sz["head_dim"]
    w0 = W.matmul_values(seed, layer, LEAF_IDS["cca_w0"], (2, heads * d), 2, "", sz["dtype"])
    w1 = jax.vmap(lambda leaf_id: W.matmul_values(
        seed, layer, leaf_id, (d, d), 2 * d, "", sz["dtype"]))(
            LEAF_IDS["cca_w1"] + jnp.arange(2 * heads))
    return {"cca_w0": w0, "cca_w1": w1.reshape(2, heads, d, d)}


def vector_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    """Norm weights (1 +- 1/4), biases (the same less 1, the router's
    times their ``_ROUTER_GAIN``), the router's depth-wise carry
    ``router_gamma`` (half a norm weight) and the temperature of k (seeded
    at 1)."""
    heads, d, r = sz["heads"] + sz["kv_heads"], sz["head_dim"], sz["router"]
    if name == "cca_temp":
        return jnp.ones((sz["kv_heads"],), jnp.dtype(sz["dtype"]))
    width = {"cca_b0": heads * d, "cca_b1": heads * d, "router_down_b": r, "router_b1": r,
             "router_b2": r, "router_gamma": r, "router_norm": r}.get(name, sz["dim"])
    v = W.norm_values(seed, layer, LEAF_IDS[name], width, "float32")
    if name in _BIASES:
        v = (v - 1.0) * _ROUTER_GAIN.get(name, 1)
    if name == "router_gamma":
        v = v * 0.5
    v = v.astype(jnp.dtype(sz["dtype"]))
    return v.reshape(heads, d) if name == "cca_b1" else v


def layer_values(seed: jax.Array, layer: jax.Array, sz: dict) -> dict:
    out = {n: leaf_values(seed, layer, n, sz) for n in MATMULS + EXPERTS}
    out.update(conv_values(seed, layer, sz))
    out.update({n: vector_values(seed, layer, n, sz)
                for n in _NORMS[:-1] + _BIASES + ("router_gamma", "cca_temp")})
    return out


def make_params(seed: int, sz: dict) -> dict:
    """The whole served tree in ONE jitted call from the seed (tied: no
    ``lm_head``)."""

    def build(s: jax.Array) -> dict:
        top = jnp.int32(-1)
        return {
            "embed": leaf_values(s, top, "embed", sz),
            "norm_f": vector_values(s, top, "norm_f", sz),
            "layers": jax.lax.map(lambda i: layer_values(s, i, sz),
                                  jnp.arange(sz["layers"], dtype=jnp.int32)),
        }

    return jax.jit(build)(W.seed_word(seed))


# -- the seam into the program ------------------------------------------------------

def register(run: Any) -> str:
    """The published sizes as a ``TransformerConfig`` of attention kind
    ``cca`` and feed-forward kind ``moe`` in the program's table, and the
    seeded weights in place of the program's own seeded init."""
    import gofr_tpu.models.transformer as T
    from gofr_tpu.models.llama import CONFIGS

    cfg, sz = run.cfg, run.sizes
    if sz["quant"]:
        raise SpecError(f"cca_moe is served unquantised; the configuration states "
                        f"quant {sz['quant']!r}")
    fields = T.TransformerConfig.__dataclass_fields__
    if "ffn_kind" not in fields or "head_dim" not in fields:
        raise SpecError("this program serves no routed experts and no head size other than "
                        "hidden / heads: it cannot serve cca_moe")
    name = cfg["_name"]
    CONFIGS[name] = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"],
        n_heads=sz["heads"], n_kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        hidden_dim=sz["ffn"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_parameters"]["hybrid"]["rope_theta"]),
        rope_fraction=sz["rope_fraction"], norm_eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype(sz["dtype"]), attn_kind="cca", ffn_kind="moe",
        n_experts=sz["experts"], router_dim=sz["router"], tie_embeddings=True,
    )

    def seeded(key, model_cfg, quantize=False, mesh=None):
        if quantize or mesh is not None:
            raise SpecError("cca_moe is served unquantised on one chip")
        start = time.monotonic()
        params = make_params(run.seed, sz)
        jax.block_until_ready(params)
        run.log(f"weights from seed {run.seed}: {time.monotonic() - start:.2f}s")
        return params

    T.init_transformer = seeded
    return name


# -- the plain reference --------------------------------------------------------------

def _before(x: jax.Array) -> jax.Array:
    """``x`` [T, ...] a token later, zeros at the first."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _unit(x: jax.Array) -> jax.Array:
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _half_rope(x: jax.Array, theta: float, fraction: float) -> jax.Array:
    rot = int(x.shape[-1] * fraction)
    return jnp.concatenate([R.rope(x[..., :rot], theta), x[..., rot:]], axis=-1)


def _attention_one(x: jax.Array, w: dict, sz: dict, eps: float, theta: float) -> jax.Array:
    """The CCA sublayer over one sequence ``x`` [T, D] (residual added)."""
    t = x.shape[0]
    h, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    rep, half = h // g, g * d // 2
    a = R.rms(x, w["attn_norm"], eps)
    qt, kt = (a @ w["wq"]).reshape(t, h, d), (a @ w["wk"]).reshape(t, g, d)
    c = jnp.concatenate([qt, kt], axis=1)  # [T, H + G, d]
    w0 = w["cca_w0"].reshape(2, h + g, d)
    c1 = w0[0] * _before(c) + w0[1] * c + w["cca_b0"].reshape(h + g, d)
    c2 = (jnp.einsum("tgi,gio->tgo", _before(c1), w["cca_w1"][0])
          + jnp.einsum("tgi,gio->tgo", c1, w["cca_w1"][1]) + w["cca_b1"])
    mq = (qt + jnp.repeat(kt, rep, axis=1)) / 2.0
    mk = jnp.mean(mq.reshape(t, g, rep, d), axis=2)
    q = (d ** 0.5) * _unit(c2[:, :h] + mq)
    k = (d ** 0.5) * w["cca_temp"][:, None] * _unit(c2[:, h:] + mk)
    q, k = (_half_rope(y, theta, sz["rope_fraction"]) for y in (q, k))
    u = a @ w["wv"]
    v = jnp.concatenate([u[:, :half], _before(u[:, half:])], axis=1).reshape(t, g, d)
    scores = jnp.einsum("tgrd,sgd->grts", q.reshape(t, g, rep, d), k) * (d ** -0.5)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("grts,sgd->tgrd", weights, v).reshape(t, h * d)
    return x + out @ w["wo"]


def _router_one(x: jax.Array, rt_before: jax.Array, w: dict, eps: float) -> tuple:
    """-> (m [T, D], rt [T, R], e [T], p[e] [T]) of the expert sublayer."""
    m = R.rms(x, w["mlp_norm"], eps)
    rt = m @ w["router_down"] + w["router_down_b"] + w["router_gamma"] * rt_before
    z = R.rms(rt, w["router_norm"], eps)
    z = jax.nn.gelu(z @ w["router_w1"] + w["router_b1"], approximate=False)
    z = jax.nn.gelu(z @ w["router_w2"] + w["router_b2"], approximate=False)
    p = jax.nn.softmax(z @ w["router_w3"], axis=-1)
    return m, rt, jnp.argmax(p, axis=-1), jnp.max(p, axis=-1)


def _swiglu(m: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array) -> jax.Array:
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def experts_indexed(m: jax.Array, e: jax.Array, w: dict, count: int) -> jax.Array:
    """Each expert over ITS tokens ``m`` [T, D], taken by index: ``count``
    indices an expert (those of its tokens, then T, which points at a row
    of zeros and is dropped on the way back). ``count`` is at least the
    fullest expert's tokens."""
    t, d = m.shape
    rows = jnp.concatenate([m, jnp.zeros((1, d), m.dtype)])

    def one(args):
        i, gate, up, down = args
        (idx,) = jnp.nonzero(e == i, size=count, fill_value=t)
        return idx, _swiglu(rows[idx], gate, up, down)

    idx, ys = jax.lax.map(one, (jnp.arange(w["w_gate"].shape[0]), w["w_gate"], w["w_up"],
                                w["w_down"]))
    return jnp.zeros((t + 1, d), m.dtype).at[idx.reshape(-1)].add(ys.reshape(-1, d))[:t]


def experts_masked(m: jax.Array, e: jax.Array, w: dict) -> jax.Array:
    """Every expert over every token, each token keeping its own expert's
    row: the plainest form, the indexed form's check."""
    ys = jax.vmap(lambda gate, up, down: _swiglu(m, gate, up, down))(
        w["w_gate"], w["w_up"], w["w_down"])  # [E, T, D]
    return jnp.einsum("et,etd->td", (e[None, :] == jnp.arange(ys.shape[0])[:, None])
                      .astype(m.dtype), ys)


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _layer_weights(seed, layer, sz_items, mode):
    """One layer's weights in float32 (``mode``: every matmul leaf, the
    experts among them, as the control holds them)."""
    sz = dict(sz_items)
    w = {n: v.astype(jnp.float32) for n, v in layer_values(seed, layer, sz).items()}
    for n in MATMULS:
        w[n] = R.degrade_weight(w[n], mode)
    for n in EXPERTS:
        w[n] = jax.vmap(lambda x: R.degrade_weight(x, mode))(w[n])
    return w


@functools.partial(jax.jit, static_argnames=("sz_items", "eps", "theta"))
def _attend_and_route(w, x, rt_before, sz_items, eps, theta):
    sz = dict(sz_items)
    with jax.default_matmul_precision("highest"):
        x = jax.lax.map(lambda row: _attention_one(row, w, sz, eps, theta), x)
        return (x,) + jax.vmap(lambda a, b: _router_one(a, b, w, eps))(x, rt_before)


@functools.partial(jax.jit, static_argnames=("count",))
def _expert_sublayer(w, x, m, e, p, count):
    with jax.default_matmul_precision("highest"):
        ys = jax.lax.map(lambda args: experts_indexed(args[0], args[1], w, count), (m, e))
        return x + p[..., None] * ys


def layer_forward(w: dict, x: jax.Array, rt_before: jax.Array, sz_items: tuple, eps: float,
                  theta: float) -> tuple[jax.Array, jax.Array]:
    """One layer over a block ``x`` [S, T, D] -> (x, the router's state)."""
    x, m, rt, e, p = _attend_and_route(w, x, rt_before, sz_items, eps, theta)
    fullest = int(max(np.bincount(row, minlength=1).max() for row in np.asarray(e)))
    count = ROW_COUNT << max(-(-fullest // ROW_COUNT) - 1, 0).bit_length()
    return _expert_sublayer(w, x, m, e, p, count), rt


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _table_and_head(seed, sz_items, mode):
    """The tied table as served, and as a float32 head [D, V] (the control
    degrades it by output channel) with the final norm's weight. Made once a
    pass: the table's 537 M seeded values take longer than a block's forward."""
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
    theta = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    s = W.seed_word(seed)
    table, head, norm = _table_and_head(s, items, mode)
    xs = [table[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32) for tokens, _, _ in blocks]
    del table
    rts = [jnp.zeros(x.shape[:2] + (sz["router"],), jnp.float32) for x in xs]
    for i in range(sz["layers"]):
        w = _layer_weights(s, jnp.int32(i), items, mode)
        done = [layer_forward(w, x, rt, items, eps, theta) for x, rt in zip(xs, rts)]
        xs, rts = [x for x, _ in done], [rt for _, rt in done]
    for x, (_, rows, cols) in zip(xs, blocks):
        yield _head(head, norm, x, jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32), eps)
