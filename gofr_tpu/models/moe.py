"""Mixture-of-Experts decoder: top-k routed SwiGLU experts per layer.

The reference has no ML components at all (SURVEY.md §2 "EP: absent");
expert parallelism is a first-class requirement of the TPU build. This
module holds the model definition and the exact (dense) compute path:

- ``MoEConfig`` extends the dense transformer config with expert counts
  and routing hyperparameters (Mixtral-style: every layer's MLP is a
  top-k mixture of SwiGLU experts; attention is unchanged GQA);
- the router is a linear gate over the hidden state; top-k softmax
  weights are renormalized over the chosen experts;
- ``moe_forward`` computes every expert for every token and mixes by the
  routing weights — exact, no capacity drops, O(E·T·D·F) compute. It is
  the single-device serving path for small models and the numerical
  reference the expert-parallel path (gofr_tpu.parallel.expert, which
  dispatches tokens over the ``ep`` mesh axis with all_to_all) is tested
  against;
- auxiliary losses: Switch-style load-balance loss and router z-loss,
  accumulated across layers and returned beside the logits.

Capacity-based dispatch (static shapes for XLA) lives in ``_routing`` and
is shared by the expert-parallel path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models.quant import mm as _mm
from gofr_tpu.models.transformer import TransformerConfig, _block, _cached_freqs
from gofr_tpu.ops.norms import rms_norm


@dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0  # expert slots = T·k·factor/E (EP path)
    aux_weight: float = 0.01  # load-balance loss weight
    z_weight: float = 1e-3  # router z-loss weight


def init_moe(key: jax.Array, cfg: MoEConfig) -> dict:
    """Param tree: attention weights match the dense transformer; the MLP
    is replaced by a router [D, E] and stacked expert weights [E, D, F]."""
    n_keys = cfg.n_layers * 9 + 3
    keys = iter(jax.random.split(key, n_keys))

    def dense(k: jax.Array, shape: tuple[int, ...], fan_in: int) -> jnp.ndarray:
        return (jax.random.truncated_normal(k, -3, 3, shape) * (fan_in ** -0.5)).astype(cfg.dtype)

    params: dict[str, Any] = {
        "embed": dense(next(keys), (cfg.vocab_size, cfg.dim), cfg.dim),
        "norm_f": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": dense(next(keys), (cfg.dim, cfg.vocab_size), cfg.dim),
    }
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    layers = []
    for _ in range(cfg.n_layers):
        layers.append(
            {
                "attn_norm": jnp.ones((cfg.dim,), cfg.dtype),
                "wq": dense(next(keys), (cfg.dim, cfg.dim), cfg.dim),
                "wk": dense(next(keys), (cfg.dim, kv_dim), cfg.dim),
                "wv": dense(next(keys), (cfg.dim, kv_dim), cfg.dim),
                "wo": dense(next(keys), (cfg.dim, cfg.dim), cfg.dim),
                "mlp_norm": jnp.ones((cfg.dim,), cfg.dtype),
                # router in f32: routing decisions are precision-sensitive
                "router": dense(next(keys), (cfg.dim, cfg.n_experts), cfg.dim).astype(jnp.float32),
                "w_gate": dense(next(keys), (cfg.n_experts, cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_up": dense(next(keys), (cfg.n_experts, cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_down": dense(next(keys),
                                (cfg.n_experts, cfg.hidden_dim, cfg.dim),
                                cfg.hidden_dim),
            }
        )
    params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return params


def _route_top_k(
    logits: jnp.ndarray, top_k: int
) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Top-k expert choice from router logits [T, E]: returns renormalized
    weights [T, k], indices [T, k], and the aux-loss dict."""
    n_experts = logits.shape[-1]
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]
    gate_vals, expert_idx = lax.top_k(gates, top_k)
    gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    # Switch load-balance: E · Σ_e (token fraction to e) · (mean router prob e)
    me = gates.mean(axis=0)
    f = jax.nn.one_hot(expert_idx[:, 0], n_experts).mean(axis=0)
    load_balance = n_experts * jnp.sum(f * me)
    z = jnp.mean(jax.scipy.special.logsumexp(logits.astype(jnp.float32), -1) ** 2)
    return gate_vals, expert_idx, {"load_balance": load_balance, "router_z": z}


def _routing(
    logits: jnp.ndarray, top_k: int, capacity: int
) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Capacity-bounded dispatch/combine tensors (GShard style) — static
    shapes for XLA. dispatch/combine: [T, E, C]; tokens overflowing an
    expert's C slots are dropped (their residual stream passes through)."""
    t, n_experts = logits.shape
    gate_vals, expert_idx, aux = _route_top_k(logits, top_k)
    dispatch = jnp.zeros((t, n_experts, capacity), jnp.float32)
    combine = jnp.zeros((t, n_experts, capacity), jnp.float32)
    counts = jnp.zeros((n_experts,), jnp.float32)
    for j in range(top_k):
        oh = jax.nn.one_hot(expert_idx[:, j], n_experts)  # [T, E]
        pos = counts[None, :] + jnp.cumsum(oh, axis=0) - oh  # slot before me
        pos_t = jnp.sum(pos * oh, axis=-1).astype(jnp.int32)  # [T]
        slot = jax.nn.one_hot(pos_t, capacity) * (pos_t < capacity)[:, None]
        d_j = oh[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + gate_vals[:, j, None, None] * d_j
        counts = counts + oh.sum(axis=0)
    return dispatch, combine, aux


def _expert_ffn(
    w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray, xs: jnp.ndarray
) -> jnp.ndarray:
    """SwiGLU over per-expert token blocks: xs [E, C, D] -> [E, C, D]."""
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xs, w_up)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)


def _moe_mlp_dense(p: dict, x: jnp.ndarray, cfg: MoEConfig) -> tuple[jnp.ndarray, dict]:
    """Exact mixture: every expert computes every token, outputs mixed by
    the renormalized top-k weights. x [B, S, D]."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    gate_vals, expert_idx, aux = _route_top_k(logits, cfg.top_k)
    g = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, p["w_up"])
    y_all = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"])  # [T, E, D]
    oh = jax.nn.one_hot(expert_idx, cfg.n_experts)  # [T, k, E]
    w = jnp.sum(gate_vals[:, :, None] * oh, axis=1)  # [T, E]
    out = jnp.einsum("te,ted->td", w.astype(y_all.dtype), y_all)
    return out.reshape(b, s, d).astype(x.dtype), aux


def routed_mlp(
    cfg: TransformerConfig, p: dict, h: jnp.ndarray, r_before: jnp.ndarray,
    experts: dict, layer: jnp.ndarray, token_mask: Any = None,
) -> tuple[jnp.ndarray, dict]:
    """The serving path's expert layer (``cfg.routed``): a router and the
    routed product (ops/experts.py), which reads only the experts that got
    a token. Under ``cfg.router_kind == "linear"``: ``_linear_routed``. Else
    an MLP router whose state passes down the stack, top-1:

    ``h`` [B, S, D] is the normed input, ``r_before`` [B, S, R] float32 the
    router state of the layer above (zeros at the first), ``experts`` the
    whole stacks [L, E, ...] with ``layer`` this layer's index, ``p`` the
    layer's router leaves. ``token_mask`` [B, S] (None: all) says which
    tokens are real: a pad token and a dead row go to no expert, add
    nothing and are counted nowhere. Returns (y [B, S, D], {"router_state":
    r [B, S, R], "expert_counts": [E] int32}).

        r = h Wd + bd + gamma * r_before
        z = W3 gelu(W2 gelu(W1 rms(r) + b1) + b2);  p = softmax(z);  e = argmax p
        y = p[e] (silu(h Wgate[e]) * h Wup[e]) Wdown[e]
    """
    from gofr_tpu.ops.experts import routed_experts

    b, s, d = h.shape
    f32 = jnp.float32
    if cfg.router_kind == "linear":
        return _linear_routed(cfg, p, h, r_before, experts, layer, token_mask)
    with jax.named_scope("moe.router"):
        # float32 throughout: a choice between two near experts should not
        # turn on the rounding of a 256-wide MLP
        dot = lambda x, w: jnp.einsum(  # noqa: E731
            "...i,io->...o", x, p[w].astype(f32), precision=lax.Precision.HIGHEST)
        r = (dot(h.astype(f32), "router_down") + p["router_down_b"].astype(f32)
             + p["router_gamma"].astype(f32) * r_before)
        a = rms_norm(r, p["router_norm"], cfg.norm_eps)
        a = jax.nn.gelu(dot(a, "router_w1") + p["router_b1"].astype(f32), approximate=False)
        a = jax.nn.gelu(dot(a, "router_w2") + p["router_b2"].astype(f32), approximate=False)
        probs = jax.nn.softmax(dot(a, "router_w3"), axis=-1)  # [B, S, E]
        choice = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        weight = jnp.max(probs, axis=-1)
        if token_mask is not None:
            choice = jnp.where(token_mask, choice, cfg.n_experts)
    y, counts = routed_experts(
        h.reshape(b * s, d), choice.reshape(b * s), experts["w_gate"],
        experts["w_up"], experts["w_down"], layer,
    )
    with jax.named_scope("moe.combine"):
        y = (y.reshape(b, s, d).astype(f32) * weight[..., None]).astype(h.dtype)
    return y, {"router_state": r, "expert_counts": counts}


def _linear_routed(
    cfg: TransformerConfig, p: dict, h: jnp.ndarray, r_before: jnp.ndarray,
    experts: dict, layer: jnp.ndarray, token_mask: Any,
) -> tuple[jnp.ndarray, dict]:
    """``routed_mlp`` under the linear router (``cfg.router_kind``): a gate
    over the deployment's routed experts and the identity experts, top-k by
    score + bias, the scores themselves (times ``routed_scale``) the weights::

        p = softmax(h Wr);  E = top-k of (p + bias)
        y = scale * sum_{e in E} p_e f_e(h)

    ``f_e`` is SwiGLU expert e for a routed e, ``h`` itself for an identity
    one. Two facts of the configuration change the gate, and nothing else
    reads them: ``cfg.gate_scoring`` "sigmoid" gives ``p = sigmoid(h Wr)``,
    and under ``cfg.norm_topk`` the chosen scores are divided by their sum
    (+ 1e-20) before the scale; the bias chooses and never weighs. With
    ``cfg.n_shared_experts`` every token also takes one SwiGLU of the shared
    experts' joint width (the layer's leaves ``shared_gate | up | down``),
    added unweighted and unscaled. This chip holds the routed experts
    ``ep_rank * n_experts`` onwards, ``n_experts`` of them: a pair whose
    expert another chip holds adds nothing here, an identity pair is computed here (a token's home chip
    needs no exchange for it). ``expert_counts`` [n_experts + 2]: the pairs
    each held expert got, then the identity pairs, then the absent ones."""
    from gofr_tpu.ops.experts import routed_experts

    b, s, d = h.shape
    f32 = jnp.float32
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("...i,io->...o", h.astype(f32), p["router"].astype(f32),
                            precision=lax.Precision.HIGHEST)
        probs = (jax.nn.sigmoid(logits) if cfg.gate_scoring == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        _, choice = lax.top_k(probs + p["router_bias"].astype(f32), cfg.top_k)
        weight = jnp.take_along_axis(probs, choice, axis=-1)
        if cfg.norm_topk:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
        weight = weight * cfg.routed_scale
        real = jnp.ones(choice.shape, bool) if token_mask is None else token_mask[..., None]
        local = choice - cfg.ep_rank * cfg.n_experts
        held = real & (local >= 0) & (local < cfg.n_experts)
        identity = real & (choice >= cfg.n_routed_experts)
        absent = real & ~held & ~identity
        expert = jnp.where(held, local, cfg.n_experts).astype(jnp.int32)
    y, counts = routed_experts(
        h.reshape(b * s, d), expert.reshape(b * s, cfg.top_k), experts["w_gate"],
        experts["w_up"], experts["w_down"], layer, weight=weight.reshape(b * s, cfg.top_k),
        gate_outputs=cfg.n_routed_experts + cfg.n_identity_experts,
    )
    y = y.reshape(b, s, d)
    if cfg.n_identity_experts:
        with jax.named_scope("moe.identity"):
            own = jnp.sum(jnp.where(identity, weight, 0.0), axis=-1)
            y = y + own[..., None] * h.astype(f32)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            gated = jax.nn.silu(_mm(h, p["shared_gate"])) * _mm(h, p["shared_up"])
            y = y + _mm(gated, p["shared_down"]).astype(f32)
    with jax.named_scope("moe.combine"):
        y = y.astype(h.dtype)
        counts = jnp.concatenate([counts, jnp.stack([jnp.sum(identity), jnp.sum(absent)])
                                  .astype(jnp.int32)])
    return y, {"router_state": r_before, "expert_counts": counts}


def moe_block(
    cfg: MoEConfig,
    p: dict,
    x: jnp.ndarray,
    freqs: jnp.ndarray,
    positions: jnp.ndarray,
    moe_mlp: Any = _moe_mlp_dense,
) -> tuple[jnp.ndarray, dict]:
    """The canonical decoder block (models/transformer.py ``_block``: GQA
    attention + residual) with the MLP swapped for routed experts."""
    y, _, aux = _block(
        cfg, p, x, freqs, positions, mlp_fn=lambda pp, h: moe_mlp(pp, h, cfg)
    )
    return y, aux


def moe_forward(
    params: dict, tokens: jnp.ndarray, cfg: MoEConfig, moe_mlp: Any = _moe_mlp_dense
) -> tuple[jnp.ndarray, dict]:
    """Full forward -> (logits [B, S, V] f32, aux losses averaged over
    layers)."""
    b, s = tokens.shape
    freqs = jnp.asarray(_cached_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta))
    positions = jnp.arange(s)
    x = params["embed"][tokens]

    def body(carry, layer_params):
        y, aux = moe_block(cfg, layer_params, carry, freqs, positions, moe_mlp)
        return y, aux

    x, aux_stack = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["norm_f"], cfg.norm_eps)
    logits = _mm(x, params["lm_head"]).astype(jnp.float32)
    aux = {k: v.mean() for k, v in aux_stack.items()}
    return logits, aux


def moe_loss(params: dict, tokens: jnp.ndarray, cfg: MoEConfig) -> jnp.ndarray:
    """Next-token loss + weighted aux losses (dense/exact path)."""
    from gofr_tpu.ops.loss import next_token_nll

    logits, aux = moe_forward(params, tokens[:, :-1], cfg)
    nll = next_token_nll(logits, tokens[:, 1:]).mean()
    return nll + cfg.aux_weight * aux["load_balance"] + cfg.z_weight * aux["router_z"]
