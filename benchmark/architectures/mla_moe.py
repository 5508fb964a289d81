"""Architecture ``mla_moe``: a decoder of (latent attention, feed-forward)
layers in which the first ``first_k_dense_replace`` layers take a dense
SwiGLU and every later one a mixture of experts: routed experts chosen by a
sigmoid gate, their scores normalised over the chosen, beside shared experts
that every token takes; RMSNorm, interleaved rotary on a 64-wide part of a
head, no query bottleneck, untied head. The DeepSeek-V3 family as
Moonlight-16B-A3B configures it. The contract of an architecture module is
in ``benchmark/spec.py``.

``rms`` is RMSNorm with a learned weight, eps from the configuration; no
bias anywhere. One layer, for a sequence's tokens (causal)::

    x = x + MLA(rms(x));   a = rms(x);   x = x + FFN_l(a)

    MLA:   q      = h Wq                              -> [H, nope | rope]   (q_lora_rank null)
           [c|kr] = h Wkva                            -> kv_rank | rope
           c      = rms(c);  kr = rope(kr)            (one rotated key, shared by all heads)
           [k_nope_i | v_i] = c Wkvb                  -> H x (nope | v)
           s_ij   = (q_nope_i . k_nope_i,j + rope(q_rope_i) . kr_j) / sqrt(nope + rope)
           out    = concat_i(softmax_j(s_i) v_i) Wo

    FFN_l = SwiGLU of width ``intermediate_size``                 l < first_k_dense_replace
    FFN_l = shared(a) + scale * sum_{e in E(a)} w_e expert_e(a)   otherwise
           s   = sigmoid(a Wg)            float32, one score a routed expert
           E   = top-k of (s + bias)      (n_group 1, topk_group 1: no group limit)
           w_e = s_e / (sum_{e in E} s_e + 1e-20)     (norm_topk_prob; the bias chooses only)
           shared = one SwiGLU of width n_shared_experts x moe_intermediate_size

``logits_at`` is that and nothing else: float32, matmul precision
``highest``, no cache, no kernels, the EXPANDED form of attention only (each
head's keys and values made from the latent, a head at a time), one layer's
weights resident at a time, each expert over ITS tokens taken by index
(``experts_indexed``; every expert over every token under the pairs'
weights, the plainest form, is ``experts_dense``: the CPU tests hold the one
to the other). This chip holds every routed expert: a configuration that
states a share of them is refused (``mla_scmoe`` is the architecture with a
share). It imports nothing of ``gofr_tpu/``.
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

ATTN_MATMULS = ("wq", "wkv_a", "wkv_b", "wo")
NORMS = ("attn_norm", "kv_norm", "mlp_norm")
DENSE = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
EXPERTS = ("w_gate", "w_up", "w_down")
# ids of its own: no leaf of this model is a leaf of another architecture.
# A leaf is seeded at its layer's place in the MODEL (the dense layers
# first); an expert leaf takes one id an expert.
LEAF_IDS = {name: 480 + i for i, name in enumerate(
    ATTN_MATMULS + NORMS + DENSE + SHARED + ("router", "norm_f", "embed", "lm_head"))}
EXPERT_IDS = {"w_gate": 4096, "w_up": 5120, "w_down": 6144}  # + the expert, under 1024
# The routed experts' way back, times 1 / sqrt(fan-in): the configuration's
# ``assumed.weights`` says what was read at 1 and why it stands where it does.
ROUTED_GAIN = 0.125
SLAB = 8192  # positions a call of a program that goes by the token takes
# an expert's tokens are padded to this times a power of two: a slab gives each of 64
# experts 768 pairs on average (top-6), so one program whatever the seed
ROW_COUNT = 1024
# positions whose float32 activations are resident at once (8 KB a position at a hidden size
# of 2048: 2.1 GB) beside a layer's experts in float32 (2.2 GB) and a sequence's attention
GROUP_TOKENS = 262144


def sizes_of(cfg: dict) -> dict:
    if cfg.get("q_lora_rank"):
        raise SpecError("mla_moe is written for a query projected directly (q_lora_rank null); "
                        f"{cfg.get('_name')} states {cfg['q_lora_rank']}")
    if cfg.get("scoring_func") != "sigmoid" or not cfg.get("norm_topk_prob"):
        raise SpecError("mla_moe is written for a sigmoid gate whose chosen scores are normalised")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise SpecError("mla_moe is written without a group limit on the gate (n_group 1)")
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings"):
        raise SpecError("mla_moe is written without biases and with an untied head")
    if cfg.get("moe_layer_freq", 1) != 1 or cfg.get("hidden_act", "silu") != "silu":
        raise SpecError("mla_moe is written for SwiGLU experts in every layer after the dense ones")
    held = cfg["n_routed_experts"]
    if cfg.get("published", {}).get("n_routed_experts", held) != held or cfg.get("ep_size", 1) != 1:
        raise SpecError("mla_moe holds every routed expert on this chip (ep_size 1)")
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    if not 0 <= dense < layers or cfg["num_experts_per_tok"] > held:
        raise SpecError(f"{dense} dense layers of {layers}, top-{cfg['num_experts_per_tok']} "
                        f"of {held} experts: not a stack this module can build")
    return {
        "dim": cfg["hidden_size"], "layers": layers, "dense_layers": dense,
        "heads": cfg["num_attention_heads"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "dense_ffn": cfg["intermediate_size"], "ffn": cfg["moe_intermediate_size"],
        "experts": held, "shared": cfg["n_shared_experts"], "top_k": cfg["num_experts_per_tok"],
        "scale": float(cfg["routed_scaling_factor"]), "vocab": cfg["vocab_size"],
        "quant": cfg["serving"]["quant"], "dtype": cfg["serving"].get("dtype", "bfloat16"),
    }


def leaf_shape(sz: dict, name: str) -> tuple[int, int]:
    """[in, out] of a matmul leaf (of ONE expert's slice of an expert leaf:
    ``expert_shape``)."""
    d, h, wide = sz["dim"], sz["heads"], sz["shared"] * sz["ffn"]
    return {
        "wq": (d, h * (sz["nope"] + sz["rope"])), "wkv_a": (d, sz["kv_rank"] + sz["rope"]),
        "wkv_b": (sz["kv_rank"], h * (sz["nope"] + sz["v"])), "wo": (h * sz["v"], d),
        "w_gate": (d, sz["dense_ffn"]), "w_up": (d, sz["dense_ffn"]),
        "w_down": (sz["dense_ffn"], d), "shared_gate": (d, wide), "shared_up": (d, wide),
        "shared_down": (wide, d), "router": (d, sz["experts"]),
        "embed": (sz["vocab"], d), "lm_head": (d, sz["vocab"]),
    }[name]


def expert_shape(sz: dict, name: str) -> tuple[int, int]:
    return (sz["ffn"], sz["dim"]) if name == "w_down" else (sz["dim"], sz["ffn"])


def leaf_values(seed: jax.Array, place: jax.Array, name: str, sz: dict) -> jax.Array:
    """One matmul weight as served, every one at 1 / sqrt(fan-in): nothing in
    this model rescales q or the latent, so that seeding gives the scores a
    spread near 1 (the configuration's ``assumed.weights``). ``place`` is
    the layer, -1 for the embedding (whose fan-in is the width it is read
    at) and the head."""
    shape = leaf_shape(sz, name)
    fan_in = sz["dim"] if name == "embed" else shape[0]
    return W.matmul_values(seed, place, LEAF_IDS[name], shape, fan_in, "", sz["dtype"])


def expert_values(seed: jax.Array, layer: jax.Array, name: str, sz: dict) -> jax.Array:
    """An expert leaf of one layer, stacked [experts, in, out]; the way back
    (``w_down``) at ``ROUTED_GAIN`` times 1 / sqrt(fan-in)."""
    shape = expert_shape(sz, name)
    fan_in = shape[0] / ROUTED_GAIN ** 2 if name == "w_down" else shape[0]
    return jax.vmap(lambda leaf_id: W.matmul_values(
        seed, layer, leaf_id, shape, fan_in, "", sz["dtype"]))(
            EXPERT_IDS[name] + jnp.arange(sz["experts"]))


def norm_values(seed: jax.Array, place: jax.Array, name: str, sz: dict) -> jax.Array:
    width = sz["kv_rank"] if name == "kv_norm" else sz["dim"]
    return W.norm_values(seed, place, LEAF_IDS[name], width, sz["dtype"])


def layer_values(seed: jax.Array, layer: jax.Array, sz: dict, routed: bool) -> dict:
    """The layer at ``layer`` of the model: its attention, its norms and its
    feed-forward (a dense SwiGLU; or the gate, its bias seeded 0 in float32,
    the shared experts' one SwiGLU and the routed experts)."""
    out = {n: leaf_values(seed, layer, n, sz) for n in ATTN_MATMULS}
    out.update({n: norm_values(seed, layer, n, sz) for n in NORMS})
    if not routed:
        out.update({n: leaf_values(seed, layer, n, sz) for n in DENSE})
        return out
    out.update({n: expert_values(seed, layer, n, sz) for n in EXPERTS})
    out.update({n: leaf_values(seed, layer, n, sz) for n in SHARED + ("router",)})
    out["router_bias"] = jnp.zeros((sz["experts"],), jnp.float32)
    return out


def make_params(seed: int, sz: dict) -> dict:
    """The whole served tree in ONE jitted call from the seed."""

    def build(s: jax.Array) -> dict:
        top, dense = jnp.int32(-1), sz["dense_layers"]
        return {
            "embed": leaf_values(s, top, "embed", sz),
            "norm_f": norm_values(s, top, "norm_f", sz),
            "lm_head": leaf_values(s, top, "lm_head", sz),
            # as ``models/transformer.py`` names them: one stack a
            # feed-forward kind, a layer at its place among its kind
            "layers": {
                "dense": jax.lax.map(lambda i: layer_values(s, i, sz, False),
                                     jnp.arange(dense, dtype=jnp.int32)),
                "moe": jax.lax.map(lambda i: layer_values(s, i, sz, True),
                                   jnp.arange(dense, sz["layers"], dtype=jnp.int32)),
            },
        }

    return jax.jit(build)(W.seed_word(seed))


# -- the seam into the program ------------------------------------------------------

def register(run: Any) -> str:
    """The published widths as a ``TransformerConfig`` of attention kind
    ``mla`` with a feed-forward kind a layer in the program's table, and the
    seeded weights in place of the program's init."""
    import gofr_tpu.models.transformer as T
    from gofr_tpu.models.llama import CONFIGS

    cfg, sz = run.cfg, run.sizes
    if sz["quant"]:
        raise SpecError(f"mla_moe is served unquantised; the configuration states "
                        f"quant {sz['quant']!r}")
    fields = T.TransformerConfig.__dataclass_fields__
    if not {"ffn_kinds", "gate_scoring", "n_shared_experts", "mla_scale"} <= set(fields):
        raise SpecError("this program has no feed-forward kind a layer, no sigmoid gate, no "
                        "shared expert and no latent attention without a query bottleneck: "
                        "it cannot serve mla_moe")
    name = cfg["_name"]
    CONFIGS[name] = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=1, hidden_dim=sz["dense_ffn"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype(sz["dtype"]), attn_kind="mla", q_lora_rank=0,
        kv_lora_rank=sz["kv_rank"], qk_nope_dim=sz["nope"], qk_rope_dim=sz["rope"],
        v_head_dim=sz["v"], mla_scale=False,
        ffn_kinds=("dense",) * sz["dense_layers"] + ("moe",) * (sz["layers"] - sz["dense_layers"]),
        router_kind="linear", gate_scoring="sigmoid", norm_topk=True, n_experts=sz["experts"],
        n_routed_experts=sz["experts"], n_shared_experts=sz["shared"], top_k=sz["top_k"],
        routed_scale=sz["scale"], expert_dim=sz["ffn"],
    )

    def seeded(key, model_cfg, quantize=False, mesh=None):
        if quantize or mesh is not None:
            raise SpecError("mla_moe is served unquantised on one chip")
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


def attention_one(x: jax.Array, w: dict, sz: dict, eps: float, theta: float) -> jax.Array:
    """One MLA layer over one sequence ``x`` [T, D] (residual added), in the
    expanded form: every head's keys and values made from the latent, a head
    at a time (its scores are the memory)."""
    t = x.shape[0]
    h, nope, rope, dv = sz["heads"], sz["nope"], sz["rope"], sz["v"]
    hid = R.rms(x, w["attn_norm"], eps)
    q = (hid @ w["wq"]).reshape(t, h, nope + rope)
    q_rope = rope_pairs(q[..., nope:], theta)
    ckr = hid @ w["wkv_a"]
    c = R.rms(ckr[:, :sz["kv_rank"]], w["kv_norm"], eps)
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


def swiglu(m: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array) -> jax.Array:
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def route(a: jax.Array, w: dict, sz: dict) -> tuple[jax.Array, jax.Array]:
    """-> (the k chosen experts [T, k], their weights [T, k]: the sigmoid
    scores of the chosen, normalised over them, times the scale; the bias
    chooses and weighs nothing)."""
    s = jax.nn.sigmoid(a @ w["router"])
    choice = jnp.argsort(-(s + w["router_bias"]), axis=-1)[:, :sz["top_k"]]
    chosen = jnp.take_along_axis(s, choice, axis=-1)
    return choice, sz["scale"] * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def expert_weights(choice: jax.Array, weight: jax.Array, sz: dict) -> jax.Array:
    """-> per expert the weight each token gives it [experts, T], 0 where the
    token did not choose it."""
    ids = jnp.arange(sz["experts"])
    return jnp.sum(jnp.where(choice[None] == ids[:, None, None], weight[None], 0.0), axis=-1)


def experts_dense(a: jax.Array, per: jax.Array, w: dict) -> jax.Array:
    """Every expert over every token, weighted (0 for a token that did not
    choose it): the plainest form, the indexed form's check."""
    ys = jax.vmap(lambda gate, up, down: swiglu(a, gate, up, down))(
        w["w_gate"], w["w_up"], w["w_down"])  # [experts, T, D]
    return jnp.einsum("et,etd->td", per, ys)


def experts_indexed(a: jax.Array, per: jax.Array, w: dict, count: int) -> jax.Array:
    """Each expert over ITS tokens ``a`` [T, D], taken by index: ``count``
    indices an expert (those of its tokens, then T, which points at a row of
    zeros and is dropped on the way back). ``count`` is at least the fullest
    expert's tokens."""
    t, d = a.shape
    rows = jnp.concatenate([a, jnp.zeros((1, d), a.dtype)])
    weights = jnp.concatenate([per, jnp.zeros((per.shape[0], 1), per.dtype)], axis=1)

    def one(args):
        mine, wt, gate, up, down = args
        (idx,) = jnp.nonzero(mine, size=count, fill_value=t)
        return idx, wt[idx][:, None] * swiglu(rows[idx], gate, up, down)

    idx, ys = jax.lax.map(one, (per > 0, weights, w["w_gate"], w["w_up"], w["w_down"]))
    return jnp.zeros((t + 1, d), a.dtype).at[idx.reshape(-1)].add(ys.reshape(-1, d))[:t]


def shared_expert(a: jax.Array, w: dict) -> jax.Array:
    return swiglu(a, w["shared_gate"], w["shared_up"], w["shared_down"])


# The reference's programs. A group's sequences lie end to end in ONE array of
# positions ``x`` [GROUP_TOKENS + a width, D] (float32), whatever the group holds:
# attention takes a sequence out of it and puts it back (one program, at the widest
# block's width), and
# what goes by the token (the feed-forward's norm, the dense SwiGLU, the gate, the
# experts and the shared expert, the head) takes a slab of ``SLAB`` positions out
# and puts it back through ONE program each. Every program has one shape whatever
# the window served, and a layer's weights are made by one of two programs (a dense
# layer's, an expert layer's): a run that compiles everything has 340 s in all.

@functools.partial(jax.jit, static_argnames=("sz_items", "routed", "mode"))
def _layer_weights(seed, layer, sz_items, routed, mode):
    """Float32, each matmul leaf as the control holds it (the gate too)."""
    tree = layer_values(seed, layer, dict(sz_items), routed)
    out = {n: v.astype(jnp.float32) for n, v in tree.items()}
    for n in out:
        if n in NORMS or n == "router_bias":
            continue
        out[n] = (jax.vmap(lambda x: R.degrade_weight(x, mode))(out[n]) if out[n].ndim == 3
                  else R.degrade_weight(out[n], mode))
    return out


def _slab(x: jax.Array, at: jax.Array, n: int = 0) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(x, at, n or SLAB)


def _put(x: jax.Array, part: jax.Array, at: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(x, part, at, 0)


@functools.partial(jax.jit, static_argnames=("t", "sz_items", "eps", "theta"), donate_argnums=(1,))
def _attend_at(w, x, at, need, t, sz_items, eps, theta):
    """``x`` with the attention layer added to the sequence whose first
    ``need`` positions lie at ``at``: ``t`` positions are taken (what lies
    past ``need`` is the next sequence's, which a causal layer keeps out of
    this one's) and ``need`` put back."""
    with jax.default_matmul_precision("highest"):
        seen = _slab(x, at, t)
        new = attention_one(seen, w, dict(sz_items), eps, theta)
        return _put(x, jnp.where(jnp.arange(t)[:, None] < need, new, seen), at)


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(1,))
def _dense_at(w, x, at, eps):
    """``x`` with the dense SwiGLU added to the slab at ``at``."""
    with jax.default_matmul_precision("highest"):
        xs = _slab(x, at)
        return _put(x, xs + swiglu(R.rms(xs, w["mlp_norm"], eps), w["w_gate"], w["w_up"],
                                   w["w_down"]), at)


@functools.partial(jax.jit, static_argnames=("sz_items", "eps"))
def _route_at(w, x, at, sz_items, eps):
    """Of the slab at ``at`` -> (each expert's weight a token, the fullest
    expert's tokens)."""
    sz = dict(sz_items)
    with jax.default_matmul_precision("highest"):
        per = expert_weights(*route(R.rms(_slab(x, at), w["mlp_norm"], eps), w, sz), sz)
        return per, jnp.max(jnp.sum(per > 0, axis=-1))


@functools.partial(jax.jit, static_argnames=("count", "eps"), donate_argnums=(1,))
def _experts_at(w, x, at, per, count, eps):
    """``x`` with the shared expert and the routed product added to the
    slab at ``at``."""
    with jax.default_matmul_precision("highest"):
        xs = _slab(x, at)
        a = R.rms(xs, w["mlp_norm"], eps)
        return _put(x, xs + shared_expert(a, w) + experts_indexed(a, per, w, count), at)


def moe_forward(w: dict, x: jax.Array, used: int, sz_items: tuple, eps: float) -> jax.Array:
    """``x`` with the expert layer's feed-forward added to its first
    ``used`` positions."""
    for at in range(0, used, SLAB):
        per, fullest = _route_at(w, x, jnp.int32(at), sz_items, eps)
        count = ROW_COUNT << max(-(-int(fullest) // ROW_COUNT) - 1, 0).bit_length()
        x = _experts_at(w, x, jnp.int32(at), per, min(count, SLAB), eps)
    return x


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
    the NEXT token. One layer's weights are resident at a time, and the
    activations of one group of blocks (``_groups``): a group goes through
    all the layers, its weights made from the seed again, before the next
    begins."""
    sz = sizes_of(cfg)
    items = tuple(sorted(sz.items()))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = W.seed_word(seed)
    table, head, norm = _table(s, items, mode)
    # attention takes every sequence at the widest block's width: one program
    # (some 15 s of the chip's compiler each), and what a narrower sequence
    # pays for positions it does not need is a second or two a window
    width = max(np.shape(block[0])[1] for block in blocks)
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
                starts.append((at, need))
                pieces.append(tokens[row, :need])
                at += need
        used = at
        size = GROUP_TOKENS + -(-width // SLAB) * SLAB  # a width of slack
        x = _embed(table, jnp.asarray(np.pad(np.concatenate(pieces), (0, size - used))))
        for i in range(sz["layers"]):
            routed = i >= sz["dense_layers"]
            w = _layer_weights(s, jnp.int32(i), items, routed, mode)
            attention = {n: w[n] for n in ATTN_MATMULS + ("attn_norm", "kv_norm")}  # one program
            for at, need in starts:
                x = _attend_at(attention, x, jnp.int32(at), jnp.int32(need), width, items, eps,
                               theta)
            if routed:
                x = moe_forward(w, x, used, items, eps)
            else:
                for at in range(0, used, SLAB):
                    x = _dense_at(w, x, jnp.int32(at), eps)
            del w
        place = iter(starts)
        for tokens, rows, cols in group:
            firsts = np.asarray([next(place)[0] for _ in range(np.shape(tokens)[0])])
            yield _head(head, norm, x, jnp.asarray(firsts[np.asarray(rows)] + np.asarray(cols),
                                                   jnp.int32), eps)
        del x
