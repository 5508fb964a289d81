"""Architecture ``mla_scmoe``: a decoder of shortcut-connected double layers.
Each layer is two (latent attention, dense SwiGLU) sublayers and one
mixture-of-experts product that takes the first sublayer's normed input and
is added two sublayers later; the experts are chosen by a linear gate over
the deployment's routed experts and its identity ("zero-computation")
experts, top-k, and THIS chip holds a share of the routed ones; RMSNorm,
interleaved rotary on a 64-wide part of a head, untied head.
LongCat-Flash-Chat. The contract of an architecture module is in
``benchmark/spec.py``.

``rms`` is RMSNorm with a learned weight. One attention sublayer (MLA), H
heads, for a sequence's tokens (causal)::

    h      = rms(x)
    q      = rms(h Wqa) Wqb * sqrt(D / q_rank)        -> [H, nope | rope]
    [c|kr] = h Wkva                                   -> kv_rank | rope
    c      = rms(c) * sqrt(D / kv_rank);  kr = rope(kr)   (one head, shared, not scaled)
    [k_nope_i | v_i] = c Wkvb                         -> H x (nope | v)
    s_ij   = (q_nope_i . k_nope_i,j + rope(q_rope_i) . kr_j) / sqrt(nope + rope)
    out    = concat_i(softmax_j(s_i) v_i) Wo

One layer::

    x1 = x  + MLA_0(x);    a = rms(x1);   m = MoE(a)
    x2 = x1 + SwiGLU_0(a)
    x3 = x2 + MLA_1(x2);   b = rms(x3)
    y  = x3 + SwiGLU_1(b) + m

The expert product, a token at a time (float32 router)::

    p  = softmax(a Wr)               R = routed + identity outputs
    E  = top-k of (p + bias)
    m  = scale * sum_{e in E} p_e f_e(a)     f_e = SwiGLU_e for a routed e, f_e(a) = a
                                             for an identity e; p is not renormalised

**The share.** This chip is rank ``ep_rank`` of ``ep`` and holds the routed
experts ``ep_rank * held .. + held``. The router keeps all its outputs and
its k; a pair whose expert another chip holds adds nothing, here and in the
program alike; the identity experts are computed here (a token's home chip
needs no exchange for them). ``logits_at`` is that and nothing else:
float32, matmul precision ``highest``, no cache, no kernels, the EXPANDED
form of attention only (each head's keys and values made from the latent),
one sublayer's or one layer's experts' weights resident at a time, each
held expert over ITS tokens taken by index (``experts_indexed``; every held
expert over every token under a mask, the plainest form, is
``experts_dense``: the CPU tests hold the one to the other). It imports
nothing of ``gofr_tpu/``.
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

SUB_MATMULS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down")
SUB_NORMS = ("attn_norm", "q_norm", "kv_norm", "mlp_norm")
EXPERTS = ("w_gate", "w_up", "w_down")
# ids of its own: no leaf of this model is a leaf of another architecture.
# A sublayer's leaves are seeded at place 2 * layer + j; an expert leaf takes
# one id an expert.
LEAF_IDS = {name: 400 + i for i, name in enumerate(
    SUB_MATMULS + SUB_NORMS + ("router", "norm_f", "embed", "lm_head"))}
EXPERT_IDS = {"w_gate": 1024, "w_up": 2048, "w_down": 3072}  # + the expert's place, under 1024
# The gate's seeded scale, times 1 / sqrt(fan-in): the configuration's
# ``assumed.router`` says what was read over six seeds and why it stands.
ROUTER_GAIN = 1.0
ROW_COUNT = 512  # an expert's tokens are padded to this times a power of two: a slab of 8,192
# positions gives a held expert 128 on average, so one program whatever the seed
SLAB = 8192  # positions a call of a program that goes by the token takes
GROUP_TOKENS = 98304  # positions whose float32 activations are resident at once (x and the expert
# product's m: 2 x 2.4 GB) beside a layer's experts (2.4 GB) and a sequence's attention


def sizes_of(cfg: dict) -> dict:
    if cfg.get("attention_method", "MLA") != "MLA" or cfg.get("zero_expert_type", "identity") != "identity":
        raise SpecError("mla_scmoe is written for MLA attention and identity zero-computation "
                        f"experts; {cfg.get('_name')} states {cfg.get('attention_method')} and "
                        f"{cfg.get('zero_expert_type')}")
    if cfg.get("attention_bias"):
        raise SpecError("mla_scmoe is written without biases")
    if cfg.get("tie_word_embeddings", False):
        raise SpecError("mla_scmoe is written with an untied head")
    deployment = cfg.get("deployment", {})
    held = cfg["n_routed_experts"]
    routed = cfg.get("published", {}).get("n_routed_experts", held)
    ep, rank = int(deployment.get("ep", 1)), int(deployment.get("ep_rank", 0))
    if held * ep != routed or not 0 <= rank < ep:
        raise SpecError(f"{held} experts held by each of ep={ep} chips are not the "
                        f"deployment's {routed} routed experts")
    if cfg["moe_topk"] > routed + cfg["zero_expert_num"]:
        raise SpecError("top-k exceeds the router's outputs")
    return {
        "dim": cfg["hidden_size"], "layers": cfg["num_layers"],
        "heads": cfg["num_attention_heads"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "dense_ffn": cfg["ffn_hidden_size"], "ffn": cfg["expert_ffn_hidden_size"],
        "experts": held, "routed": routed, "identity": cfg["zero_expert_num"],
        "top_k": cfg["moe_topk"], "scale": float(cfg["routed_scaling_factor"]),
        "ep": ep, "ep_rank": rank,
        "scale_q": bool(cfg["mla_scale_q_lora"]), "scale_kv": bool(cfg["mla_scale_kv_lora"]),
        "vocab": cfg["vocab_size"], "quant": cfg["serving"]["quant"],
        "dtype": cfg["serving"].get("dtype", "bfloat16"),
    }


def leaf_shape(sz: dict, name: str) -> tuple[int, int]:
    """[in, out] of a matmul leaf (of one expert's slice of an expert leaf:
    ``expert_shape``)."""
    d, h = sz["dim"], sz["heads"]
    return {
        "wq_a": (d, sz["q_rank"]), "wq_b": (sz["q_rank"], h * (sz["nope"] + sz["rope"])),
        "wkv_a": (d, sz["kv_rank"] + sz["rope"]),
        "wkv_b": (sz["kv_rank"], h * (sz["nope"] + sz["v"])), "wo": (h * sz["v"], d),
        "w_gate": (d, sz["dense_ffn"]), "w_up": (d, sz["dense_ffn"]),
        "w_down": (sz["dense_ffn"], d), "router": (d, sz["routed"] + sz["identity"]),
        "embed": (sz["vocab"], d), "lm_head": (d, sz["vocab"]),
    }[name]


def expert_shape(sz: dict, name: str) -> tuple[int, int]:
    return (sz["ffn"], sz["dim"]) if name == "w_down" else (sz["dim"], sz["ffn"])


def leaf_values(seed: jax.Array, place: jax.Array, name: str, sz: dict) -> jax.Array:
    """One matmul weight as served; ``place`` is 2 * layer + j for a
    sublayer's leaf, the layer for the router, -1 for the embedding (whose
    fan-in is the width it is read at) and the head."""
    shape = leaf_shape(sz, name)
    # the two projections out of a bottleneck are seeded at 1 / sqrt(hidden), as
    # the family initialises every matrix: the ``mla_scale`` factors, sqrt(hidden
    # / rank), are there to give q, k and v unit variance from such weights. At
    # 1 / sqrt(rank) the scores' spread is 5.7 and attention is a hard argmax
    # that bf16 and float32 place differently (the configuration's ``assumed``)
    fan_in = sz["dim"] if name in ("embed", "wq_b", "wkv_b") else shape[0]
    if name == "router":
        fan_in = fan_in / ROUTER_GAIN ** 2
    return W.matmul_values(seed, place, LEAF_IDS[name], shape, fan_in, "", sz["dtype"])


def expert_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    """An expert leaf of one layer, stacked [held, in, out]. The ids go by
    the expert's place in the DEPLOYMENT, so every rank makes its own."""
    shape = expert_shape(sz, name)
    first = EXPERT_IDS[name] + sz["ep_rank"] * sz["experts"]
    return jax.vmap(lambda leaf_id: W.matmul_values(
        seed, layer, leaf_id, shape, shape[0], "", sz["dtype"]))(first + jnp.arange(sz["experts"]))


def norm_values(seed: jax.Array, place: jax.Array, name: str, sz: dict) -> jax.Array:
    width = {"q_norm": sz["q_rank"], "kv_norm": sz["kv_rank"]}.get(name, sz["dim"])
    return W.norm_values(seed, place, LEAF_IDS[name], width, sz["dtype"])


def sub_values(seed: jax.Array, place: jax.Array, sz: dict) -> dict:
    """The sublayer at ``place`` (2 * layer + j): its attention and its
    dense SwiGLU."""
    out = {n: leaf_values(seed, place, n, sz) for n in SUB_MATMULS}
    out.update({n: norm_values(seed, place, n, sz) for n in SUB_NORMS})
    return out


def moe_values(seed: jax.Array, layer: jax.Array, sz: dict) -> dict:
    """A layer's gate (the bias seeded 0, float32) and the experts held."""
    out = {n: expert_values(seed, layer, n, sz) for n in EXPERTS}
    out["router"] = leaf_values(seed, layer, "router", sz)
    out["router_bias"] = jnp.zeros((sz["routed"] + sz["identity"],), jnp.float32)
    return out


def make_params(seed: int, sz: dict) -> dict:
    """The whole served tree in ONE jitted call from the seed."""

    def build(s: jax.Array) -> dict:
        top = jnp.int32(-1)
        return {
            "embed": leaf_values(s, top, "embed", sz),
            "norm_f": norm_values(s, top, "norm_f", sz),
            "lm_head": leaf_values(s, top, "lm_head", sz),
            # as ``models/transformer.py`` names them: the sublayers stacked
            # [2 L, ...] under ``sub``, the gates and the experts [L, ...]
            "layers": {
                "sub": jax.lax.map(lambda at: sub_values(s, at, sz),
                                   jnp.arange(2 * sz["layers"], dtype=jnp.int32)),
                **jax.lax.map(lambda i: moe_values(s, i, sz),
                              jnp.arange(sz["layers"], dtype=jnp.int32)),
            },
        }

    return jax.jit(build)(W.seed_word(seed))


# -- the seam into the program ------------------------------------------------------

def register(run: Any) -> str:
    """The published widths as a ``TransformerConfig`` of attention kind
    ``mla`` and feed-forward kind ``scmoe`` under the linear router in the
    program's table, and the seeded weights in place of the program's init."""
    import gofr_tpu.models.transformer as T
    from gofr_tpu.models.llama import CONFIGS

    cfg, sz = run.cfg, run.sizes
    if sz["quant"]:
        raise SpecError(f"mla_scmoe is served unquantised; the configuration states "
                        f"quant {sz['quant']!r}")
    fields = T.TransformerConfig.__dataclass_fields__
    if "kv_lora_rank" not in fields or "router_kind" not in fields:
        raise SpecError("this program has no latent attention, no top-k gate over a share of "
                        "the experts and no shortcut-connected layer: it cannot serve mla_scmoe")
    if not (sz["scale_q"] and sz["scale_kv"]):
        raise SpecError("the program's MLA applies both mla_scale factors")
    name = cfg["_name"]
    CONFIGS[name] = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=1, hidden_dim=sz["dense_ffn"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype(sz["dtype"]), attn_kind="mla", q_lora_rank=sz["q_rank"],
        kv_lora_rank=sz["kv_rank"], qk_nope_dim=sz["nope"], qk_rope_dim=sz["rope"],
        v_head_dim=sz["v"], ffn_kind="scmoe", router_kind="linear", n_experts=sz["experts"],
        n_routed_experts=sz["routed"], n_identity_experts=sz["identity"], top_k=sz["top_k"],
        routed_scale=sz["scale"], ep_rank=sz["ep_rank"], expert_dim=sz["ffn"],
    )

    def seeded(key, model_cfg, quantize=False, mesh=None):
        if quantize or mesh is not None:
            raise SpecError("mla_scmoe is served unquantised on one chip")
        start = time.monotonic()
        params = make_params(run.seed, sz)
        jax.block_until_ready(params)
        run.log(f"weights from seed {run.seed}: {time.monotonic() - start:.2f}s")
        return params

    T.init_transformer = seeded
    return name


# -- the plain reference --------------------------------------------------------------

def rope_pairs(x: jax.Array, theta: float) -> jax.Array:
    """``x`` [T, heads, d] at positions 0..T-1, interleaved pairs: dims
    (2i, 2i + 1) turn together by ``t * theta^(-2i / d)``, in place."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _attention_one(x: jax.Array, w: dict, sz: dict, eps: float, theta: float) -> jax.Array:
    """One MLA sublayer over one sequence ``x`` [T, D] (residual added), in
    the expanded form: every head's keys and values made from the latent,
    a head at a time (its scores are the memory)."""
    t = x.shape[0]
    h, nope, rope, dv = sz["heads"], sz["nope"], sz["rope"], sz["v"]
    hid = R.rms(x, w["attn_norm"], eps)
    q = R.rms(hid @ w["wq_a"], w["q_norm"], eps) @ w["wq_b"]
    q = (q * (sz["dim"] / sz["q_rank"]) ** 0.5).reshape(t, h, nope + rope)
    q_rope = rope_pairs(q[..., nope:], theta)
    ckr = hid @ w["wkv_a"]
    c = R.rms(ckr[:, :sz["kv_rank"]], w["kv_norm"], eps) * (sz["dim"] / sz["kv_rank"]) ** 0.5
    kr = rope_pairs(ckr[:, None, sz["kv_rank"]:], theta)[:, 0]  # [T, rope]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w_kv = w["wkv_b"].reshape(sz["kv_rank"], h, nope + dv)

    def head(args):
        qn, qr, wi = args  # [T, nope], [T, rope], [rank, nope + v]
        kv = c @ wi
        scores = (qn @ kv[:, :nope].T + qr @ kr.T) * (nope + rope) ** -0.5
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ kv[:, nope:]

    out = jax.lax.map(head, (jnp.swapaxes(q[..., :nope], 0, 1), jnp.swapaxes(q_rope, 0, 1),
                             jnp.swapaxes(w_kv, 0, 1)))  # [H, T, v]
    return x + jnp.swapaxes(out, 0, 1).reshape(t, h * dv) @ w["wo"]


def _swiglu(m: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array) -> jax.Array:
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def route(a: jax.Array, w: dict, sz: dict) -> tuple[jax.Array, jax.Array]:
    """-> (the k chosen outputs [T, k], their weights scale * p_e [T, k])."""
    p = jax.nn.softmax(a @ w["router"], axis=-1)
    choice = jnp.argsort(-(p + w["router_bias"]), axis=-1)[:, :sz["top_k"]]
    return choice, sz["scale"] * jnp.take_along_axis(p, choice, axis=-1)


def held_weights(choice: jax.Array, weight: jax.Array, sz: dict) -> tuple[jax.Array, jax.Array]:
    """-> (per held expert the weight each token gives it [held, T], 0 where
    the token did not choose it; the sum of a token's identity weights [T])."""
    first = sz["ep_rank"] * sz["experts"]
    ids = first + jnp.arange(sz["experts"])
    per = jnp.sum(jnp.where(choice[None] == ids[:, None, None], weight[None], 0.0), axis=-1)
    return per, jnp.sum(jnp.where(choice >= sz["routed"], weight, 0.0), axis=-1)


def experts_dense(a: jax.Array, per: jax.Array, w: dict) -> jax.Array:
    """Every held expert over every token, weighted (0 for a token that did
    not choose it): the plainest form, the indexed form's check."""
    ys = jax.vmap(lambda gate, up, down: _swiglu(a, gate, up, down))(
        w["w_gate"], w["w_up"], w["w_down"])  # [held, T, D]
    return jnp.einsum("et,etd->td", per, ys)


def experts_indexed(a: jax.Array, per: jax.Array, w: dict, count: int) -> jax.Array:
    """Each held expert over ITS tokens ``a`` [T, D], taken by index:
    ``count`` indices an expert (those of its tokens, then T, which points
    at a row of zeros and is dropped on the way back). ``count`` is at
    least the fullest expert's tokens."""
    t, d = a.shape
    rows = jnp.concatenate([a, jnp.zeros((1, d), a.dtype)])
    weights = jnp.concatenate([per, jnp.zeros((per.shape[0], 1), per.dtype)], axis=1)

    def one(args):
        mine, wt, gate, up, down = args
        (idx,) = jnp.nonzero(mine, size=count, fill_value=t)
        return idx, wt[idx][:, None] * _swiglu(rows[idx], gate, up, down)

    idx, ys = jax.lax.map(one, (per > 0, weights, w["w_gate"], w["w_up"], w["w_down"]))
    return jnp.zeros((t + 1, d), a.dtype).at[idx.reshape(-1)].add(ys.reshape(-1, d))[:t]


def _f32(tree: dict, names: tuple, mode: Optional[str]) -> dict:
    """Float32, each matmul leaf of ``names`` as the control holds it."""
    out = {n: v.astype(jnp.float32) for n, v in tree.items()}
    for n in names:
        out[n] = (jax.vmap(lambda x: R.degrade_weight(x, mode))(out[n]) if out[n].ndim == 3
                  else R.degrade_weight(out[n], mode))
    return out


# The reference's programs. A group's sequences lie end to end in ONE array of
# positions ``x`` [GROUP_TOKENS + a width, D] (float32), whatever the group holds:
# attention takes a sequence out of it and puts it back (a program a width),
# and what goes by the token (the norms, the dense SwiGLUs, the gate, the
# experts, the head) takes a slab of ``SLAB`` positions out and puts it back
# through ONE program each. Every program has one shape whatever the window
# served: a program of these sizes takes the chip's compiler five to ten
# seconds, and a run that compiles everything has 340 s in all.

@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _sub_weights(seed, place, sz_items, mode):
    return _f32(sub_values(seed, place, dict(sz_items)), SUB_MATMULS, mode)


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _moe_weights(seed, layer, sz_items, mode):
    return _f32(moe_values(seed, layer, dict(sz_items)), EXPERTS + ("router",), mode)


def _slab(x: jax.Array, at: jax.Array, n: int = 0) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(x, at, n or SLAB)


def _put(x: jax.Array, part: jax.Array, at: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(x, part, at, 0)


@functools.partial(jax.jit, static_argnames=("t", "sz_items", "eps", "theta"), donate_argnums=(1,))
def _attend_at(w, x, at, need, t, sz_items, eps, theta):
    """``x`` with the attention sublayer added to the sequence whose first
    ``need`` positions lie at ``at``: ``t`` positions are taken (the program
    of the sequence's width; what lies past ``need`` is the next sequence's,
    which a causal layer keeps out of this one's) and ``need`` put back."""
    with jax.default_matmul_precision("highest"):
        seen = _slab(x, at, t)
        new = _attention_one(seen, w, dict(sz_items), eps, theta)
        return _put(x, jnp.where(jnp.arange(t)[:, None] < need, new, seen), at)


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(1,))
def _dense_at(w, x, at, eps):
    """``x`` with the dense SwiGLU added to the slab at ``at``."""
    with jax.default_matmul_precision("highest"):
        xs = _slab(x, at)
        return _put(x, xs + _swiglu(R.rms(xs, w["mlp_norm"], eps), w["w_gate"], w["w_up"],
                                    w["w_down"]), at)


@functools.partial(jax.jit, static_argnames=("sz_items", "eps"))
def _route_at(norm, w, x, at, sz_items, eps):
    """Of the slab at ``at`` -> (the expert product's input a = rms(x), each
    held expert's weight a token, a token's identity weight, the fullest
    held expert's tokens)."""
    sz = dict(sz_items)
    with jax.default_matmul_precision("highest"):
        a = R.rms(_slab(x, at), norm, eps)
        per, own = held_weights(*route(a, w, sz), sz)
        return a, per, own, jnp.max(jnp.sum(per > 0, axis=-1))


@functools.partial(jax.jit, static_argnames=("count",), donate_argnums=(1,))
def _experts_at(w, m, at, a, per, own, count):
    """``m`` with the slab at ``at`` set to the expert product of ``a``."""
    with jax.default_matmul_precision("highest"):
        return _put(m, experts_indexed(a, per, w, count) + own[:, None] * a, at)


def moe_forward(norm: jax.Array, w: dict, x: jax.Array, m: jax.Array, used: int,
                sz_items: tuple, eps: float) -> jax.Array:
    """``m`` with the expert product of the first ``used`` positions of ``x``."""
    for at in range(0, used, SLAB):
        a, per, own, fullest = _route_at(norm, w, x, jnp.int32(at), sz_items, eps)
        count = ROW_COUNT << max(-(-int(fullest) // ROW_COUNT) - 1, 0).bit_length()
        m = _experts_at(w, m, jnp.int32(at), a, per, own, min(count, SLAB))
    return m


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(x, m):
    return x + m


@functools.partial(jax.jit, static_argnames=("sz_items", "mode"))
def _table(seed, sz_items, mode):
    sz = dict(sz_items)
    top = jnp.int32(-1)
    head = R.degrade_weight(leaf_values(seed, top, "lm_head", sz).astype(jnp.float32), mode)
    return (leaf_values(seed, top, "embed", sz), head,
            norm_values(seed, top, "norm_f", sz).astype(jnp.float32))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(head, norm, x, at, eps):
    with jax.default_matmul_precision("highest"):
        return R.rms(x[at], norm, eps) @ head


def _groups(blocks: list[tuple]) -> list[list[tuple]]:
    """The blocks in their order, cut into runs of at most ``GROUP_TOKENS``
    positions (a block longer than that stands alone)."""
    groups: list[list[tuple]] = [[]]
    room = GROUP_TOKENS
    for block in blocks:
        size = int(np.prod(np.shape(block[0])))
        if groups[-1] and size > room:
            groups.append([])
            room = GROUP_TOKENS
        groups[-1].append(block)
        room -= size
    return groups


def logits_at(seed: int, cfg: dict, blocks: list[tuple], mode: Optional[str] = None):
    """Full forward over every block ``(tokens [S, T], rows, cols)`` (tokens
    right-padded: every part of a layer is causal or by the token, so
    padding stays out of earlier positions); yields per block the float32
    logits [N, V] at the ``(rows[i], cols[i])`` positions, each predicting
    the NEXT token. One sublayer's weights, or one layer's gate and experts,
    are resident at a time, and the activations of one group of blocks
    (``_groups``: a window's sequences are 25 KB a position in float32, more
    than the chip holds at once): a group goes through all the layers, its
    weights made from the seed again, before the next begins."""
    sz = sizes_of(cfg)
    items = tuple(sorted(sz.items()))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = W.seed_word(seed)
    table, head, norm = _table(s, items, mode)
    for group in _groups(blocks):
        # every sequence of the group end to end, each cut after its last scored
        # position (what follows it moves no scored logit: every part of a layer
        # is causal or by the token), then zeros up to the array's fixed size
        starts, pieces, at = [], [], 0
        for tokens, rows, cols in group:
            tokens = np.asarray(tokens, np.int32)
            for row in range(tokens.shape[0]):
                scored = np.asarray(cols)[np.asarray(rows) == row]
                need = min(int(scored.max()) + 1, tokens.shape[1]) if scored.size else 0
                starts.append((at, need, tokens.shape[1]))
                pieces.append(tokens[row, :need])
                at += need
        used = at
        size = GROUP_TOKENS + -(-max(t for _, _, t in starts) // SLAB) * SLAB  # a width of slack
        x = _embed(table, jnp.asarray(np.pad(np.concatenate(pieces), (0, size - used))))
        for i in range(sz["layers"]):
            w = _sub_weights(s, jnp.int32(2 * i), items, mode)
            for at, need, t in starts:
                x = _attend_at(w, x, jnp.int32(at), jnp.int32(need), t, items, eps, theta)
            moe = _moe_weights(s, jnp.int32(i), items, mode)
            m = moe_forward(w["mlp_norm"], moe, x, jnp.zeros_like(x), used, items, eps)
            del moe
            for at in range(0, used, SLAB):
                x = _dense_at(w, x, jnp.int32(at), eps)
            w = _sub_weights(s, jnp.int32(2 * i + 1), items, mode)
            for at, need, t in starts:
                x = _attend_at(w, x, jnp.int32(at), jnp.int32(need), t, items, eps, theta)
            for at in range(0, used, SLAB):
                x = _dense_at(w, x, jnp.int32(at), eps)
            x = _add(x, m)
            del w, m
        place = iter(starts)
        for tokens, rows, cols in group:
            firsts = np.asarray([next(place)[0] for _ in range(np.shape(tokens)[0])])
            yield _head(head, norm, x, jnp.asarray(firsts[np.asarray(rows)] + np.asarray(cols),
                                                   jnp.int32), eps)
        del x
