"""Llama-family decoder-only transformer: RMSNorm, RoPE, GQA attention,
SwiGLU — pure JAX, static shapes, KV-cached ragged-batch decode.

TPU-first design notes:
- all shapes static under jit: prefill is bucketed by the serving layer
  (per-request true lengths passed separately), decode is a fixed [B, 1]
  step over a preallocated cache;
- attention runs through gofr_tpu.ops.attention (Pallas flash on TPU);
- weights default to bfloat16 with f32 norm/softmax accumulation; int8
  weight-only checkpoints route through gofr_tpu.models.quant.mm;
- params are plain nested dicts so pjit PartitionSpec trees mirror them
  (gofr_tpu.parallel.sharding names the same keys);
- the cache is ragged-batch: per-request lengths [B], per-batch
  dynamic_update_slice via vmap, so one compiled step serves requests at
  different positions (continuous-batching-ready);
- RoPE tables are built once per config (lru_cache) and embedded as jit
  constants — no trig on the decode hot path.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from gofr_tpu.models.quant import mm as _mm
from gofr_tpu.ops.attention import attention
from gofr_tpu.ops.norms import rms_norm
from gofr_tpu.ops.rope import apply_rope, rope_frequencies


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    # KV-cache storage dtype (None -> dtype). float8_e4m3fn halves cache
    # HBM per token — 2x context length or decode slots on a capacity-
    # bound chip. Writes cast on merge; attention upcasts at its boundary.
    kv_dtype: Any = None
    # the serving mesh the jitted forwards are partitioned over (None =
    # single device). Only attention reads it: a Mosaic kernel must be
    # shard_mapped, GSPMD cannot partition it (ops/attention.py).
    mesh: Any = None
    # what a layer attends with. "softmax": causal softmax attention over a
    # K/V cache that grows by the token. "retention": power retention
    # (ops/retention.py): RMSNorm over each head of q and k, a gate per kv
    # head (leaves q_norm, k_norm, w_g), and a cache that is a fixed-size
    # state per row, float32 unless kv_dtype says otherwise.
    # "cca": softmax attention in a latent narrower than ``dim``
    # (``n_heads * head_dim != dim``): q and k pass two causal convolutions
    # over the sequence, a q-k mean and an L2 norm, v is shifted by a token
    # (``_cca_qkv``); beside its K/V rows a cached row keeps a fixed
    # ``tail`` (the last token's convolution inputs and shifted value).
    attn_kind: str = "softmax"
    # the size of a head; 0 means ``dim // n_heads``
    head_dim: int = 0
    # the leading share of a head's dims that rotary turns (the rest pass)
    rope_fraction: float = 1.0
    # the output head is ``embed`` transposed: the tree has no ``lm_head``
    tie_embeddings: bool = False
    # what follows attention. "dense": SwiGLU of width ``hidden_dim``.
    # "moe": ``n_experts`` SwiGLU experts of width ``hidden_dim``, one a
    # token, chosen by an MLP router of width ``router_dim`` whose state
    # passes from layer to layer (models/moe.py::routed_mlp).
    # "scmoe": the shortcut-connected double layer (``_shortcut_block``):
    # two (mixer, dense SwiGLU of width ``hidden_dim``) sublayers and one
    # product of ``n_experts`` SwiGLU experts of width ``expert_dim`` that
    # takes the first sublayer's normed input and is added after the second.
    ffn_kind: str = "dense"
    # the feed-forward of each layer, one name a layer ("dense" | "moe"),
    # where the layers do not all take the same; empty means every layer is
    # ``ffn_kind``. Parameters are then stacked per feed-forward kind
    # (``params["layers"]["dense" | "moe"]``) and the layer loop takes the
    # runs of equal layers in turn (``ffn_runs``): a leading dense layer,
    # then a scan over the expert layers. The cache is one stack over all
    # layers, whatever follows a layer's mixer.
    ffn_kinds: tuple = ()
    n_experts: int = 0
    router_dim: int = 0
    # who chooses the experts. "mlp": the MLP router above, one expert a
    # token. "linear": a gate of ``n_routed_experts + n_identity_experts``
    # outputs in float32, softmax, the ``top_k`` best by score + bias, each
    # weighted by its score times ``routed_scale`` (not renormalised). The
    # routed experts are the deployment's; this chip holds ``n_experts`` of
    # them, those from ``ep_rank * n_experts`` on, and a choice of another
    # chip's adds nothing here. An identity expert gives its input back and
    # holds no weights. ``gate_scoring`` "sigmoid" scores each output on its
    # own in place of the softmax; ``norm_topk`` divides the chosen scores by
    # their sum before ``routed_scale`` (the bias chooses and weighs nothing
    # either way). ``n_shared_experts`` experts of width ``expert_dim`` every
    # token takes beside its routed ones: one SwiGLU of their joint width.
    router_kind: str = "mlp"
    n_routed_experts: int = 0
    n_identity_experts: int = 0
    top_k: int = 1
    routed_scale: float = 1.0
    gate_scoring: str = "softmax"
    norm_topk: bool = False
    n_shared_experts: int = 0
    ep_rank: int = 0
    expert_dim: int = 0  # 0: ``hidden_dim``
    # the mixer of each layer, one name a layer, where the layers are not
    # all alike; empty means every layer is ``attn_kind``. Beside the three
    # attention kinds above a layer may be "ssm": a selective state-space
    # (Mamba-1) mixer (``_ssm_mixer``, ops/ssm.py) with no q, k, v and no
    # ``wo``, whose cache is a diagonal state and a convolution tail per
    # row. Parameters and cache are stacked per kind (``MIXERS``) and the
    # layer loop scans the pattern's period (``layer_period``).
    layer_kinds: tuple = ()
    # "mla" (an ``attn_kind``): latent attention (``_mla_mixer``, ops/mla.py).
    # q through a bottleneck of ``q_lora_rank`` (0: projected directly, one
    # ``wq``); ``mla_scale``: the query is multiplied by sqrt(dim /
    # q_lora_rank) and the normed latent by sqrt(dim / kv_lora_rank)
    # (LongCat-Flash's two ``mla_scale`` factors). What a token leaves in the
    # cache is one latent of ``kv_lora_rank`` and one rotated key of
    # ``qk_rope_dim`` shared by all heads, from which each head's key
    # (``qk_nope_dim`` wide beside the shared part) and value
    # (``v_head_dim``) are made. ``head_dim`` is the query-key width.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mla_scale: bool = True
    # an "ssm" layer: ``ssm_expand * dim`` channels, ``ssm_state`` entries
    # of state a channel, a causal depthwise convolution of ``ssm_conv``
    # taps, a step size projected through ``ssm_dt_rank``. The state is
    # held float32 between steps (MODEL_KV_DTYPE is what K and V take)
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    ssm_expand: int = 2

    def __post_init__(self) -> None:
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        if self.layer_kinds:
            object.__setattr__(self, "layer_kinds", tuple(self.layer_kinds))
            unknown = set(self.layer_kinds) - set(MIXER_KINDS)
            if len(self.layer_kinds) != self.n_layers or unknown:
                raise ValueError(
                    f"layer_kinds names {len(self.layer_kinds)} layers of kinds "
                    f"{sorted(set(self.layer_kinds))}; the model has {self.n_layers} and a "
                    f"kind is one of {MIXER_KINDS}")
            if self.ffn_kind != "dense" or self.ffn_kinds:
                raise ValueError("layers of more than one kind take the dense feed-forward")
        if self.ffn_kinds:
            ffns = tuple(self.ffn_kinds)
            if len(ffns) != self.n_layers or set(ffns) - {"dense", "moe"}:
                raise ValueError(
                    f"ffn_kinds names {len(ffns)} layers of kinds {sorted(set(ffns))}; the "
                    f"model has {self.n_layers} and a kind is \"dense\" or \"moe\"")
            if "moe" in ffns and self.router_kind != "linear":
                raise ValueError("a router whose state passes from layer to layer takes "
                                 "expert layers alone: ffn_kinds takes the linear router")
            # layers that are all alike are one stack, scanned as it always was
            alike = len(set(ffns)) == 1
            object.__setattr__(self, "ffn_kind", ffns[0] if alike else "dense")
            object.__setattr__(self, "ffn_kinds", () if alike else ffns)
        if self.attn_kind == "mla":
            object.__setattr__(self, "head_dim", self.qk_nope_dim + self.qk_rope_dim)
        if not self.expert_dim:
            object.__setattr__(self, "expert_dim", self.hidden_dim)

    @property
    def kinds(self) -> tuple:
        """The mixer of every layer, first to last."""
        return self.layer_kinds or (self.attn_kind,) * self.n_layers

    @property
    def kinds_present(self) -> tuple:
        """The kinds the model has, in the order they first appear."""
        return tuple(dict.fromkeys(self.kinds))

    @property
    def mixed(self) -> bool:
        """Whether parameters and cache are stacked per kind (a model of
        one kind keeps one stack over all its layers, as it always had)."""
        return len(self.kinds_present) > 1

    def n_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    @property
    def ffns(self) -> tuple:
        """The feed-forward of every layer, first to last."""
        return self.ffn_kinds or (self.ffn_kind,) * self.n_layers

    @property
    def ffn_stacked(self) -> bool:
        """Whether parameters are stacked per feed-forward kind."""
        return bool(self.ffn_kinds)

    @property
    def ffn_runs(self) -> tuple:
        """The runs of equal layers down the stack as (feed-forward kind,
        first index among the layers of that kind, first layer, layers)."""
        runs: list = []
        seen: dict = {}
        for i, ffn in enumerate(self.ffns):
            if runs and runs[-1][0] == ffn:
                runs[-1][3] += 1
            else:
                runs.append([ffn, seen.get(ffn, 0), i, 1])
            seen[ffn] = seen.get(ffn, 0) + 1
        return tuple(tuple(r) for r in runs)

    @property
    def routed(self) -> bool:
        """Whether a layer has routed experts (feed-forward "moe", "scmoe")."""
        return any(ffn in ("moe", "scmoe") for ffn in self.ffns)

    @property
    def mixers_per_layer(self) -> int:
        """Mixer sublayers a layer has, each with its place in the cache."""
        return 2 if self.ffn_kind == "scmoe" else 1

    @property
    def routing_width(self) -> int:
        """Columns of a layer's routing counts: the experts held and, under
        the linear router, the pairs that chose an identity expert and those
        that chose an expert of another chip."""
        return self.n_experts + (2 if self.router_kind == "linear" else 0)

    @property
    def layer_period(self) -> tuple:
        """(P, runs): the shortest period P of the layers' kinds that
        divides the depth, and the runs of equal layers within one period
        as (kind, first index among the period's layers of that kind,
        layers). What the layer loop of a model with layers of more than
        one kind scans: the period ``n_layers / P`` times, each run inside."""
        kinds = self.kinds
        period = next(p for p in range(1, self.n_layers + 1)
                      if self.n_layers % p == 0
                      and all(kinds[i] == kinds[i % p] for i in range(self.n_layers)))
        runs: list = []
        seen: dict = {}
        for kind in kinds[:period]:
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, seen.get(kind, 0), 1])
            seen[kind] = seen.get(kind, 0) + 1
        return period, tuple(tuple(r) for r in runs)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.dim

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_dim or int(self.head_dim * self.rope_fraction)

    @property
    def tail_dim(self) -> int:
        """What a row of a "cca" cache keeps a layer beside K and V: the
        last token's q~|k~ and first convolution's output, and the half of
        its value projection that the next token takes."""
        kv_dim = self.n_kv_heads * self.head_dim
        return 2 * (self.q_dim + kv_dim) + kv_dim // 2

    @property
    def cache_dtype(self) -> Any:
        if self.attn_kind == "retention" and not self.layer_kinds:
            return self.kv_dtype or jnp.float32
        return self.kv_dtype or self.dtype



@functools.lru_cache(maxsize=16)
def _cached_freqs(head_dim: int, max_seq: int, theta: float):
    """Concrete per-config RoPE table, embedded as a constant in each jitted
    forward — no trig on the decode hot path.

    Computed AND cached as numpy: any jax array (even jnp.asarray of a
    constant) created during a jit trace is a tracer, and caching a tracer
    leaks it into later traces. A numpy array is concrete everywhere; the
    use sites convert with jnp.asarray inside their own trace."""
    import numpy as np

    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    freqs = np.outer(np.arange(max_seq, dtype=np.float32), inv_freq)
    return np.stack([np.cos(freqs), np.sin(freqs)], axis=-1).astype(np.float32)


def _rope_table(cfg: TransformerConfig) -> Optional[jnp.ndarray]:
    """The model's rotary table, or None for a model without rotary
    (``rope_fraction`` 0: a table of width 0 cannot be built, and no layer
    turns anything)."""
    if cfg.rope_dim == 0:
        return None
    return jnp.asarray(_cached_freqs(cfg.rope_dim, cfg.max_seq, cfg.rope_theta))


# write one layer into a preallocated [n_layers, ...] stack IN PLACE: the
# stack's buffer is donated to the write (module-level so every init call
# shares one executable per weight shape)
_place_layer = jax.jit(
    lambda stack, x, i: jax.lax.dynamic_update_index_in_dim(stack, x, i, 0),
    donate_argnums=0,
)


def init_transformer(
    key: jax.Array, cfg: TransformerConfig, quantize: Any = False,
    mesh: Any = None,
) -> dict:
    """Weight layout mirrors Llama-3 shapes; initialization is scaled
    truncated-normal (serving weights come from checkpoints; init exists for
    tests and training-from-scratch).

    ``quantize`` ("int8"/"int4"; True = int8) quantizes each matmul weight
    IMMEDIATELY after creation, so peak device memory is the packed model
    plus ONE bf16 weight — init-then-quantize of the full tree would peak
    at 3x the packed size and OOM an 8B model on a 16GB chip. Values are
    bit-identical to ``quantize_params(init_transformer(key, cfg), mode)``.

    ``mesh`` places every weight in its serving layout
    (parallel/sharding.py) AS IT IS CREATED, so no device holds more than
    its shard plus one layer in flight. Built whole and sharded afterwards,
    the model first piles up on one device (measured at 8B int8, tp=4:
    12.1 GB peak on device 0 against 3.3 GB on the others)."""
    from gofr_tpu.models.quant import quantizer_for, quantizer_for_key

    def put(tree: dict) -> dict:
        if mesh is None:
            return tree
        from gofr_tpu.parallel.sharding import shard_params

        return shard_params(tree, mesh)

    def stack_like(x: jnp.ndarray, n: int) -> jnp.ndarray:
        """Zeros for ``n`` stacked copies of ``x``, allocated in ``x``'s
        layout (the layer axis is never sharded)."""
        shape = (n,) + x.shape
        if mesh is None:
            return jnp.zeros(shape, x.dtype)
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(None, *x.sharding.spec)
        return jnp.zeros(shape, x.dtype, device=NamedSharding(mesh, spec))

    quantizer_for(quantize)  # validate the mode eagerly
    if quantize and cfg.routed:
        raise ValueError("the quantiser does not take expert-stacked leaves")
    if quantize and (cfg.mixed or cfg.ffn_stacked):
        raise ValueError("the quantiser does not take layers stacked per kind")
    n_keys = cfg.n_layers * 7 + 3
    keys = iter(jax.random.split(key, n_keys))

    def dense(k: jax.Array, shape: tuple[int, ...], fan_in: int,
              name: str = "") -> Any:
        w = (jax.random.truncated_normal(k, -3, 3, shape) * (fan_in ** -0.5)).astype(cfg.dtype)
        # key-aware quantizer: the w8a8 lm_head carve-out lives in
        # quant.quantizer_for_key, not here
        quantize_fn = quantizer_for_key(quantize, name)
        return quantize_fn(w) if quantize_fn else w

    params: dict[str, Any] = put({
        # embeddings stay high precision (the quantization scheme's rule)
        "embed": (
            jax.random.truncated_normal(next(keys), -3, 3, (cfg.vocab_size, cfg.dim))
            * (cfg.dim ** -0.5)
        ).astype(cfg.dtype),
        "norm_f": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": dense(
            next(keys), (cfg.dim, cfg.vocab_size), cfg.dim, name="lm_head"
        ),
    })
    if cfg.tie_embeddings:
        del params["lm_head"]  # the head is ``embed`` transposed
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    q_dim = cfg.q_dim

    def extra(i: int, n: int, shape: tuple[int, ...], fan_in: int) -> jnp.ndarray:
        # keys of their own, so the dense leaves' values stay what they were
        k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, n_keys), i), n)
        return (jax.random.truncated_normal(k, -3, 3, shape) * (fan_in ** -0.5)).astype(cfg.dtype)

    def cca_leaves(i: int) -> dict:
        heads, d = cfg.n_heads + cfg.n_kv_heads, cfg.head_dim
        return {
            # two causal convolutions of kernel 2 over q~|k~: depthwise
            # [tap, channel], then grouped by head [tap, head, in, out];
            # tap 0 multiplies the token before, tap 1 this one
            "cca_w0": extra(i, 1, (2, heads * d), 2),
            "cca_b0": jnp.zeros((heads * d,), cfg.dtype),
            "cca_w1": extra(i, 2, (2, heads, d, d), 2 * d),
            "cca_b1": jnp.zeros((heads, d), cfg.dtype),
            "cca_temp": jnp.ones((cfg.n_kv_heads,), cfg.dtype),
        }

    def expert_leaves(i: int) -> dict:
        e, f = cfg.n_experts, cfg.expert_dim
        return {
            "w_gate": extra(i, 7, (e, cfg.dim, f), cfg.dim),
            "w_up": extra(i, 8, (e, cfg.dim, f), cfg.dim),
            "w_down": extra(i, 9, (e, f, cfg.dim), f),
        }

    def moe_leaves(i: int) -> dict:
        r, e = cfg.router_dim, cfg.n_experts
        if cfg.router_kind == "linear":
            outputs = cfg.n_routed_experts + cfg.n_identity_experts
            shared = cfg.n_shared_experts * cfg.expert_dim
            return {"router": extra(i, 3, (cfg.dim, outputs), cfg.dim),
                    "router_bias": jnp.zeros((outputs,), jnp.float32), **expert_leaves(i),
                    **({"shared_gate": extra(i, 15, (cfg.dim, shared), cfg.dim),
                        "shared_up": extra(i, 16, (cfg.dim, shared), cfg.dim),
                        "shared_down": extra(i, 17, (shared, cfg.dim), shared)}
                       if shared else {})}
        return {
            "router_down": extra(i, 3, (cfg.dim, r), cfg.dim),
            "router_down_b": jnp.zeros((r,), cfg.dtype),
            "router_gamma": jnp.full((r,), 0.5, cfg.dtype),
            "router_norm": jnp.ones((r,), cfg.dtype),
            "router_w1": extra(i, 4, (r, r), r),
            "router_b1": jnp.zeros((r,), cfg.dtype),
            "router_w2": extra(i, 5, (r, r), r),
            "router_b2": jnp.zeros((r,), cfg.dtype),
            "router_w3": extra(i, 6, (r, e), r),
            **expert_leaves(i),
        }

    def mla_leaves(i: int, j: int) -> dict:
        h, rq, rc = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        at = 30 + 8 * j
        query = {"wq_a": extra(i, at, (cfg.dim, rq), cfg.dim),
                 "q_norm": jnp.ones((rq,), cfg.dtype),
                 "wq_b": extra(i, at + 1, (rq, h * cfg.head_dim), rq)} if rq else {
                     "wq": extra(i, at, (cfg.dim, h * cfg.head_dim), cfg.dim)}
        return {
            "attn_norm": jnp.ones((cfg.dim,), cfg.dtype),
            **query,
            "wkv_a": extra(i, at + 2, (cfg.dim, rc + cfg.qk_rope_dim), cfg.dim),
            "kv_norm": jnp.ones((rc,), cfg.dtype),
            "wkv_b": extra(i, at + 3, (rc, h * (cfg.qk_nope_dim + cfg.v_head_dim)), rc),
            "wo": extra(i, at + 4, (h * cfg.v_head_dim, cfg.dim), h * cfg.v_head_dim),
            "mlp_norm": jnp.ones((cfg.dim,), cfg.dtype),
        }

    def dense_leaves(i: int, j: int) -> dict:
        at = 35 + 8 * j
        return {
            "w_gate": extra(i, at, (cfg.dim, cfg.hidden_dim), cfg.dim),
            "w_up": extra(i, at + 1, (cfg.dim, cfg.hidden_dim), cfg.dim),
            "w_down": extra(i, at + 2, (cfg.hidden_dim, cfg.dim), cfg.hidden_dim),
        }

    def retention_leaves(i: int) -> dict:
        # keys of their own, so the dense leaves' values stay what they were
        k = jax.random.fold_in(jax.random.fold_in(key, n_keys), i)
        return {
            "q_norm": jnp.ones((cfg.head_dim,), cfg.dtype),
            "k_norm": jnp.ones((cfg.head_dim,), cfg.dtype),
            # the gate stays dense (dim x n_kv_heads: a few KB a layer)
            "w_g": (jax.random.truncated_normal(k, -3, 3, (cfg.dim, cfg.n_kv_heads))
                    * (cfg.dim ** -0.5)).astype(cfg.dtype),
        }

    def ssm_leaves(i: int) -> dict:
        di, n, r, taps = cfg.d_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
        f32 = jnp.float32
        # the family's initialisation, not 1 / sqrt(fan-in) noise (which
        # gives states that forget in a token or overflow): A = -(1..N) in
        # every channel, and a step-size bias whose softplus is spread
        # log-uniformly over [0.001, 0.1]
        k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, n_keys), i), 20)
        step = jnp.exp(jax.random.uniform(k, (di,), f32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "ssm_in": extra(i, 10, (cfg.dim, 2 * di), cfg.dim),
            # [tap, channel]: tap ``taps - 1`` multiplies this token
            "ssm_conv_w": extra(i, 11, (taps, di), taps),
            "ssm_conv_b": jnp.zeros((di,), cfg.dtype),
            "ssm_x": extra(i, 12, (di, r + 2 * n), di),
            "ssm_dt_norm": jnp.ones((r,), cfg.dtype),
            "ssm_b_norm": jnp.ones((n,), cfg.dtype),
            "ssm_c_norm": jnp.ones((n,), cfg.dtype),
            "ssm_dt": extra(i, 13, (r, di), r),
            # float32, as the scan takes them: the inverse of softplus at
            # ``step``; log(1..N) down the state's entries, [N, Di] as the
            # state is kept (ops/ssm.py)
            "ssm_dt_b": step + jnp.log(-jnp.expm1(-step)),
            "ssm_a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=f32))[:, None], (n, di)),
            "ssm_d": jnp.ones((di,), f32),
            "ssm_out": extra(i, 14, (di, cfg.dim), di),
        }

    def make_layer(kind: str, i: int) -> dict:
        ffn = cfg.ffns[i]
        if ffn == "scmoe":
            # the two (mixer, dense) sublayers under "sub" (stacked over all
            # sublayers in the tree); the router and the experts are the layer's
            subs = [{**mla_leaves(i, j), **dense_leaves(i, j)} for j in range(2)]
            return {"sub": jax.tree.map(lambda a, b: jnp.stack([a, b]), *subs),
                    **moe_leaves(i)}
        if kind == "ssm":
            layer = {"attn_norm": jnp.ones((cfg.dim,), cfg.dtype), **ssm_leaves(i),
                     "mlp_norm": jnp.ones((cfg.dim,), cfg.dtype)}
        elif kind == "mla":
            layer = mla_leaves(i, 0)
        else:
            layer = {
                "attn_norm": jnp.ones((cfg.dim,), cfg.dtype),
                "wq": dense(next(keys), (cfg.dim, q_dim), cfg.dim),
                "wk": dense(next(keys), (cfg.dim, kv_dim), cfg.dim),
                "wv": dense(next(keys), (cfg.dim, kv_dim), cfg.dim),
                "wo": dense(next(keys), (q_dim, cfg.dim), q_dim),
                "mlp_norm": jnp.ones((cfg.dim,), cfg.dtype),
            }
        if kind == "retention":
            layer.update(retention_leaves(i))
        if kind == "cca":
            layer.update(cca_leaves(i))
        if ffn == "moe":
            layer.update(moe_leaves(i))
        if ffn == "dense":
            layer.update({
                "w_gate": dense(next(keys), (cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_up": dense(next(keys), (cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_down": dense(next(keys), (cfg.hidden_dim, cfg.dim), cfg.hidden_dim),
            })
        return layer

    # layers live as ONE pytree level of [n_layers, ...] arrays, scanned in
    # the forward — one compiled layer body instead of n_layers copies.
    # Stacking is INCREMENTAL and IN PLACE (_place_layer donates the
    # stack). A plain ``s.at[i].set(x)`` copies the stack, and a tree of
    # such copies holds the whole model twice until the old tree is
    # dropped — at 8B int8 that is ~14 GB of a 16 GB chip during boot.
    # (Quantized {"q","scale"} dicts thread per-field through the tree maps.)
    # A model whose layers are not all alike has one such stack a kind (of
    # mixer, or of feed-forward where those differ), ``params["layers"][kind]``,
    # a layer at its index among its kind.
    groups = cfg.ffns if cfg.ffn_stacked else cfg.kinds
    stacks: dict[str, Any] = {}
    placed: dict[str, int] = {}
    for i, (kind, group) in enumerate(zip(cfg.kinds, groups)):
        layer = put(make_layer(kind, i))
        at = placed.get(group, 0)
        if group not in stacks:
            stacks[group] = jax.tree.map(
                lambda x, n=groups.count(group): stack_like(x, n), layer)
        stacks[group] = jax.tree.map(
            lambda s, x, at=at: _place_layer(s, x, at), stacks[group], layer
        )
        placed[group] = at + 1
        del layer
    params["layers"] = stacks if cfg.mixed or cfg.ffn_stacked else stacks[groups[0]]
    if cfg.ffn_kind == "scmoe":
        # [L, 2, ...] -> [2 L, ...]: sublayer j of layer i at 2 i + j
        params["layers"]["sub"] = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), params["layers"]["sub"])
    return params


def _default_mlp(p: dict, h: jnp.ndarray) -> tuple[jnp.ndarray, dict]:
    """Dense SwiGLU MLP (the ``mlp_fn`` default); MoE swaps in routed
    experts here (models/moe.py)."""
    gated = jax.nn.silu(_mm(h, p["w_gate"])) * _mm(h, p["w_up"])
    return _mm(gated, p["w_down"]), {}


def _write_kv(
    stack: jnp.ndarray, new: jnp.ndarray, layer: jnp.ndarray,
    starts: jnp.ndarray,
) -> jnp.ndarray:
    """Write ``new`` [B, s, kv_heads, head_dim] into the stacked cache
    [L, B, kv_heads, max_seq, head_dim] at [layer, row, :, starts[row]:+s].

    One ``dynamic_update_slice`` per row, each of this call's s tokens
    alone: the stack is updated in place and only B·s·kv_heads·head_dim
    elements move; what is transposed into the stored order is the call's
    own tokens, never the stack. (One batched-index update would be a
    scatter, which a backend may widen to the whole operand.) Starts clamp
    as every dynamic_update_slice does, so a full row overwrites its own
    tail."""
    new = jnp.swapaxes(new, 1, 2).astype(stack.dtype)  # [B, kv_heads, s, head_dim]
    for row in range(new.shape[0]):
        stack = jax.lax.dynamic_update_slice(
            stack, new[row][None, None], (layer, row, 0, starts[row], 0)
        )
    return stack


# what a layer holds per expert: [n_experts, ...] a layer, so
# [n_layers, n_experts, ...] in the tree. The layer loops do not slice them
# (a slice of one layer's experts would be copied whole for the kernel that
# reads a few): ``_split_experts`` keeps the stacks out of the scan's xs and
# ops/experts.py indexes [layer, expert] itself.
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
_L2_EPS = 1e-6  # under the root of the "cca" L2 norm: a dead row's q is 0


def _split_experts(layers: dict, routed: bool) -> tuple[dict, Optional[dict]]:
    """(what the layer loop scans, the expert stacks it closes over)."""
    if not routed:
        return layers, None
    return ({k: v for k, v in layers.items() if k not in EXPERT_LEAVES},
            {k: layers[k] for k in EXPERT_LEAVES})


def _shift(x: jnp.ndarray, before: jnp.ndarray) -> jnp.ndarray:
    """``x`` [B, S, C] a token later: position t holds x[t-1], position 0
    holds ``before`` [B, C] (zeros at a sequence's first token)."""
    return jnp.concatenate([before[:, None].astype(x.dtype), x[:, :-1]], axis=1)


def _cca_qkv(
    cfg: TransformerConfig, p: dict, h: jnp.ndarray, tail: jnp.ndarray,
    last: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """q, k, v of a "cca" layer before rotary, from the normed input ``h``
    [B, S, D] and the row's ``tail`` [B, tail_dim] (what the token before
    this call left: zeros at a sequence's start), and the tail this call
    leaves, taken at token ``last`` [B] of each row. Plain jax.numpy: the
    convolutions are 1280 channels wide at the published sizes."""
    b, s, _ = h.shape
    n_q, n_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep, width, half = n_q // n_kv, (n_q + n_kv) * d, n_kv * d // 2
    f32 = jnp.float32
    c_before, c1_before, u_before = jnp.split(tail, [width, 2 * width], axis=-1)
    with jax.named_scope("attn.qkv"):
        qt, kt, u = _mm(h, p["wq"]), _mm(h, p["wk"]), _mm(h, p["wv"])
    with jax.named_scope("attn.cca.conv"):
        c = jnp.concatenate([qt, kt], axis=-1)  # [B, S, width]
        w0, w1 = p["cca_w0"].astype(f32), p["cca_w1"]
        c1 = (w0[0] * _shift(c, c_before).astype(f32) + w0[1] * c.astype(f32)
              + p["cca_b0"].astype(f32)).astype(h.dtype)
        by_head = lambda x: x.reshape(b, s, n_q + n_kv, d)  # noqa: E731
        taps = lambda x, w: jnp.einsum(  # noqa: E731
            "bsgi,gio->bsgo", by_head(x), w, preferred_element_type=f32)
        c2 = (taps(_shift(c1, c1_before), w1[0]) + taps(c1, w1[1])
              + p["cca_b1"].astype(f32))  # [B, S, heads, d] float32
    with jax.named_scope("attn.cca.mix_norm"):
        qh = qt.astype(f32).reshape(b, s, n_q, d)
        kh = kt.astype(f32).reshape(b, s, n_kv, d)
        mq = (qh + jnp.repeat(kh, rep, axis=2)) / 2.0
        mk = jnp.mean(mq.reshape(b, s, n_kv, rep, d), axis=3)
        q, k = c2[:, :, :n_q] + mq, c2[:, :, n_q:] + mk
        unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)
        q = ((d ** 0.5) * unit(q)).astype(h.dtype)
        k = ((d ** 0.5) * p["cca_temp"].astype(f32)[:, None] * unit(k)).astype(h.dtype)
    with jax.named_scope("attn.cca.value_shift"):
        # the first half of a token's value is its own, the second half
        # the projection of the token before: viewed as kv heads
        u_late = u[..., half:]
        v = jnp.concatenate([u[..., :half], _shift(u_late, u_before)], axis=-1)
        v = v.reshape(b, s, n_kv, d)
        at_last = lambda x: jnp.take_along_axis(  # noqa: E731
            x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        new_tail = jnp.concatenate(
            [at_last(c), at_last(c1), at_last(u_late)], axis=-1).astype(tail.dtype)
    return q, k, v, new_tail


def _block(
    cfg: TransformerConfig,
    p: dict,
    x: jnp.ndarray,
    freqs: jnp.ndarray,
    positions: jnp.ndarray,
    kv_cache: Optional[tuple[jnp.ndarray, ...]] = None,
    layer: Optional[jnp.ndarray] = None,
    starts: Optional[jnp.ndarray] = None,
    kv_lens: Optional[jnp.ndarray] = None,
    attn_fn: Optional[Any] = None,
    mlp_fn: Optional[Any] = None,
    valid: Optional[jnp.ndarray] = None,
    live: Optional[jnp.ndarray] = None,
    kind: Optional[str] = None,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, ...], dict]:
    """One decoder block — the single implementation shared by the
    no-cache forward, the cached prefill/decode path, the sequence-parallel
    ring path (which passes ``attn_fn``), and the MoE models (which pass
    ``mlp_fn`` returning (out, aux)).

    Without cache: attention over this call's keys (via ``attn_fn`` when
    given), returns (out, (k, v), aux). With cache: ``kv_cache`` is the
    WHOLE stacked cache (k, v), each [L, B, kv_heads, max_seq, head_dim],
    and ``layer`` this block's index into it. This call's k/v are written
    at [layer, row, :, starts[row] : starts[row] + s] and nothing else of
    the stacks is touched; attention reads its layer out of the stack over
    the full cache window. Returns (out, (k_stack, v_stack), aux): the
    buffers that came in, so the caller's loops carry them in place.

    ``cfg.attn_kind == "retention"``: ``kv_cache`` is the stacked state
    (S, z) instead (ops/retention.py) and ``valid`` [B, S] says which of
    this call's tokens are real: a state has no "past the length" for
    bucket padding to be dead in, so a pad token must not enter it.
    ``live`` [B] says which rows hold a request (the decode pool's slots):
    the one-token step moves no state for the others.

    ``cfg.attn_kind == "cca"``: ``kv_cache`` is (k, tail, v), the tail
    [L, B, tail_dim] beside the K/V stacks. The call reads its layer's tail
    at entry and leaves the one of each row's last ``valid`` token (bucket
    padding does not enter it); a row that is not ``live`` keeps its own.

    ``kind`` is this layer's mixer where the model's layers are not all
    alike (``cfg.layer_kinds``; default ``cfg.attn_kind``), ``layer`` its
    index among the layers of its kind, and ``kv_cache`` that kind's stacks
    (``MIXERS[kind].cache``). A mixer is the part between the first norm
    and the residual: the three attention kinds share the body below; "ssm"
    is ``_ssm_mixer``. ``freqs`` None: no rotary (``cfg.rope_dim == 0``).
    """
    kind = kind or cfg.attn_kind
    call = _Call(freqs, positions, starts, kv_lens, attn_fn, valid, live)
    if cfg.ffn_kind == "scmoe":
        return _shortcut_block(cfg, kind, p, x, kv_cache, layer, call, mlp_fn)
    x, merged = MIXERS[kind].mix(cfg, kind, p, x, kv_cache, layer, call)
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        y, aux = (mlp_fn or _default_mlp)(p, h)
        x = x + y
    return x, merged, aux


def _shortcut_block(
    cfg: TransformerConfig, kind: str, p: dict, x: jnp.ndarray,
    kv_cache: Optional[tuple[jnp.ndarray, ...]], layer: Optional[jnp.ndarray], call: "_Call",
    mlp_fn: Any,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, ...], dict]:
    """The shortcut-connected double layer (``cfg.ffn_kind == "scmoe"``): two
    (mixer, dense SwiGLU) sublayers, and one routed expert product that
    takes the first sublayer's normed input and is added two sublayers
    later, after the second dense one::

        x1 = x  + mix_0(x);    a = rms(x1);   m = experts(a)
        x2 = x1 + swiglu_0(a)
        x3 = x2 + mix_1(x2);   b = rms(x3)
        y  = x3 + swiglu_1(b) + m

    ``p["sub"]`` is the pair of the two sublayers' leaves (``_scan_layers``
    reads them out of the stack over all sublayers, which it keeps out of
    the scan's xs); the mixers' places in the cache are ``2 * layer`` and
    ``2 * layer + 1``; ``mlp_fn(p, a)`` is the routed product."""
    def place(j: int) -> Optional[jnp.ndarray]:
        return None if layer is None else 2 * layer + j

    mix = MIXERS[kind].mix
    p0, p1 = p["sub"]
    x, cache = mix(cfg, kind, p0, x, kv_cache, place(0), call)
    with jax.named_scope("mlp"):
        a = rms_norm(x, p0["mlp_norm"], cfg.norm_eps)
    with jax.named_scope("moe.shortcut"):
        m, aux = mlp_fn(p, a)
    with jax.named_scope("mlp"):
        x = x + _default_mlp(p0, a)[0]
    x, cache = mix(cfg, kind, p1, x, cache, place(1), call)
    with jax.named_scope("mlp"):
        b = rms_norm(x, p1["mlp_norm"], cfg.norm_eps)
        x = x + _default_mlp(p1, b)[0]
    with jax.named_scope("moe.shortcut"):
        x = x + m
    return x, cache, aux


class _Call(NamedTuple):
    """What a forward hands every layer's mixer beside its own parameters
    and cache (``_block``'s arguments of the same names)."""
    freqs: Optional[jnp.ndarray]
    positions: Optional[jnp.ndarray]
    starts: Optional[jnp.ndarray]
    kv_lens: Optional[jnp.ndarray]
    attn_fn: Optional[Any]
    valid: Optional[jnp.ndarray]
    live: Optional[jnp.ndarray]


def _attention_mixer(
    cfg: TransformerConfig, kind: str, p: dict, x: jnp.ndarray,
    kv_cache: Optional[tuple[jnp.ndarray, ...]], layer: Optional[jnp.ndarray], call: _Call,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, ...]]:
    """The mixer of the three attention kinds (``_block``): norm, q, k, v,
    the kind's own steps, attention over the kind's cache, ``wo`` and the
    residual -> (x, the kind's stacks as they came in, this call's tokens
    written)."""
    freqs, positions, starts, kv_lens, attn_fn, valid, live = call
    # the named scopes are names only (HLO op metadata: a device
    # operation in a profiler trace then says which of these lines it
    # came from); they change no program, shape or module name
    b, s, _ = x.shape
    tail_stack = None
    if kind == "cca":
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if kv_cache is None:
            tail = jnp.zeros((b, cfg.tail_dim), x.dtype)
        else:
            k_stack, tail_stack, v_stack = kv_cache
            kv_cache = (k_stack, v_stack)
            tail = jax.lax.dynamic_index_in_dim(tail_stack, layer, 0, keepdims=False)
        last = (jnp.full((b,), s - 1, jnp.int32) if valid is None
                else jnp.maximum(jnp.sum(valid, axis=1) - 1, 0))
        q, k, v, new_tail = _cca_qkv(cfg, p, h, tail, last)
        if tail_stack is not None:
            if live is not None:
                new_tail = jnp.where(live[:, None] > 0, new_tail, tail)
            tail_stack = jax.lax.dynamic_update_slice(
                tail_stack, new_tail[None], (layer, 0, 0))
    else:
        with jax.named_scope("attn.qkv"):
            h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
            # the three products exist as [B, S, width] before anything views
            # them by head. Left to itself the chip's compiler folds the
            # reshape into the product (a convolution ``bf0_0oi->b0f``), which
            # then wants its weight as [heads, head_dim, dim], the stored one
            # transposed: a relaid copy of the whole stack a program run, or
            # of a layer's slab a layer, and the product fed from that copy.
            # Behind the barrier each is ``bf_io->bf`` over the stack as it
            # is stored, the layer index fused in, like every other weight
            # (tests/test_tpu_compile.py holds the compiled text to it)
            q, k, v = jax.lax.optimization_barrier(
                (_mm(h, p["wq"]), _mm(h, p["wk"]), _mm(h, p["wv"])))
            q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if kind == "retention":
        with jax.named_scope("attn.qk_norm"):
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if freqs is not None:
        with jax.named_scope("attn.rope"):
            q = apply_rope(q, freqs, positions)
            k = apply_rope(k, freqs, positions)

    if kind == "retention":
        from gofr_tpu.ops import retention

        with jax.named_scope("attn.gate"):
            log_g = jax.nn.log_sigmoid(jnp.einsum(
                "bsd,dh->bsh", h.astype(jnp.float32), p["w_g"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            ))
        if kv_cache is None:
            with jax.named_scope("attn.retention.chunk"):
                attn = retention.retention_attention(q, k, v, log_g, valid)
            merged = (k, v)
        else:
            attn, s_stack, z_stack = retention.retention_cached(
                q, k, v, log_g, *kv_cache, layer, valid=valid,
                impl=cfg.attn_impl, live=live,
            )
            merged = (s_stack, z_stack)
        attn = attn.astype(x.dtype)
    elif kv_cache is None:
        with jax.named_scope("attn.flash"):
            if attn_fn is not None:
                attn = attn_fn(q, k, v)
            else:
                attn = attention(
                    q, k, v, causal=True, impl=cfg.attn_impl, mesh=cfg.mesh
                )
        merged = (k, v)
    else:
        k_stack, v_stack = kv_cache
        with jax.named_scope("attn.kv_update"):
            k_stack = _write_kv(k_stack, k, layer, starts)
            v_stack = _write_kv(v_stack, v, layer, starts)
        with jax.named_scope("attn.flash"):
            attn = attention(
                q, k_stack, v_stack, causal=True, q_offset=starts,
                kv_lens=kv_lens, impl=cfg.attn_impl, mesh=cfg.mesh,
                layer=layer,
            )
        merged = (k_stack, v_stack)
        if tail_stack is not None:
            merged = (k_stack, tail_stack, v_stack)  # by name, as cache_leaves

    with jax.named_scope("attn.out"):
        x = x + _mm(attn.reshape(b, s, cfg.q_dim), p["wo"])
    return x, merged


def _ssm_mixer(
    cfg: TransformerConfig, kind: str, p: dict, x: jnp.ndarray,
    cache: Optional[tuple[jnp.ndarray, jnp.ndarray]], layer: Optional[jnp.ndarray], call: _Call,
) -> tuple[jnp.ndarray, Optional[tuple[jnp.ndarray, jnp.ndarray]]]:
    """The selective state-space (Mamba-1) mixer with the family's three
    inner norms, for this call's tokens ``x`` [B, T, D] -> (x with the
    mixer's output added, the stacks (conv, ssm) that came in, layer
    ``layer`` advanced). For token t::

        [u, z] = rms(x) W_in
        u_t = silu(b_c + sum_j w_c[j] u_{t-(K-1)+j})      (depthwise, causal)
        [dt, B, C] = u W_x;  dt, B, C = rms(dt), rms(B), rms(C)
        delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
        s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t^T;  y_t = s_t C_t + D u_t
        out = (y * silu(z)) W_out

    ``cache``: ``conv`` [L, B, (K-1) * Di], the K-1 inputs of the
    convolution before this call's first token, oldest first, in the
    model's type (zeros at a sequence's start), and ``ssm`` [L, B, N, Di],
    the state (ops/ssm.py). A token that is not ``valid`` (bucket padding;
    a row's real tokens come first) enters neither: its ``delta`` is 0 and
    the tail is taken at the row's last valid tokens. A row that is not
    ``live`` keeps both. Without a cache the sequence starts from zeros.
    The state and the scan are float32 whatever the model's type."""
    from gofr_tpu.ops import ssm

    valid, live = call.valid, call.live
    b, t, _ = x.shape
    di, n, r, taps = cfg.d_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
    f32 = jnp.float32
    with jax.named_scope("ssm.in_proj"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        uz = _mm(h, p["ssm_in"])
        u, z = uz[..., :di], uz[..., di:]
    with jax.named_scope("ssm.conv"):
        if cache is None:
            conv_stack = ssm_stack = None
            tail = jnp.zeros((b, (taps - 1) * di), x.dtype)
        else:
            conv_stack, ssm_stack = cache
            tail = jax.lax.dynamic_index_in_dim(conv_stack, layer, 0, keepdims=False)
        w = p["ssm_conv_w"].astype(f32)
        if t == 1:
            # lane slices of the flat tail: no [B, K-1, Di] view, which
            # under (8, 128) tiling would be a relayout of it
            taps_in = [tail[:, j * di:(j + 1) * di] for j in range(taps - 1)] + [u[:, 0]]
            conv = sum(w[j] * taps_in[j].astype(f32) for j in range(taps))[:, None]
            new_tail = jnp.concatenate([tail[:, di:], u[:, 0].astype(tail.dtype)], axis=-1)
        else:
            seen = jnp.concatenate([tail.reshape(b, taps - 1, di).astype(u.dtype), u], axis=1)
            conv = sum(w[j] * seen[:, j:j + t].astype(f32) for j in range(taps))
            # the K-1 inputs before the row's next token: those that end
            # at its last valid one
            count = (jnp.full((b,), t, jnp.int32) if valid is None
                     else jnp.sum(valid, axis=1, dtype=jnp.int32))
            new_tail = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
                row, at, taps - 1, axis=0))(seen, count)
            new_tail = new_tail.reshape(b, (taps - 1) * di).astype(tail.dtype)
        # float32 into the scan and the skip; the model's type into the matmul
        uf = jax.nn.silu(conv + p["ssm_conv_b"].astype(f32))
        u = uf.astype(x.dtype)
        if conv_stack is not None:
            if live is not None:
                new_tail = jnp.where(live[:, None] > 0, new_tail, tail)
            conv_stack = jax.lax.dynamic_update_slice(conv_stack, new_tail[None], (layer, 0, 0))
    with jax.named_scope("ssm.dt_bc"):
        # the two small projections give float32 (their sums are float32
        # anyway): what feeds the float32 scan is rounded to the model's
        # type once, where it enters a matmul, and not again on the way out.
        # A step size rounded to bfloat16 before its softplus is 3% off.
        project = lambda y, w: jnp.einsum(  # noqa: E731
            "btd,dk->btk", y, w, preferred_element_type=f32)
        dbc = project(u, p["ssm_x"])
        dt = rms_norm(dbc[..., :r], p["ssm_dt_norm"], cfg.norm_eps)
        b_t = rms_norm(dbc[..., r:r + n], p["ssm_b_norm"], cfg.norm_eps)
        c_t = rms_norm(dbc[..., r + n:], p["ssm_c_norm"], cfg.norm_eps)
        delta = jax.nn.softplus(project(dt.astype(x.dtype), p["ssm_dt"])
                                + p["ssm_dt_b"].astype(f32))
        if valid is not None:
            delta = jnp.where(valid[:, :, None], delta, 0.0)
        a = -jnp.exp(p["ssm_a_log"].astype(f32))  # [N, Di]
    if ssm_stack is None:
        with jax.named_scope("ssm.scan"):
            y, _ = ssm.scan_chunked(uf, delta, a, b_t, c_t, jnp.zeros((b, n, di), f32))
    else:
        with jax.named_scope("ssm.step" if t == 1 else "ssm.scan"):
            y, ssm_stack = ssm.scan_cached(
                uf, delta, a, b_t, c_t, ssm_stack, layer, impl=cfg.attn_impl, live=live)
    with jax.named_scope("ssm.out_proj"):
        y = (y + p["ssm_d"].astype(f32) * uf) * jax.nn.silu(z.astype(f32))
        x = x + _mm(y.astype(x.dtype), p["ssm_out"])
    return x, (None if cache is None else (conv_stack, ssm_stack))


def _pairs_apart(x: jnp.ndarray) -> jnp.ndarray:
    """Interleaved rotary pairs (2i, 2i + 1) laid as ``apply_rope`` takes
    them: the even dims, then the odd ones. Queries and keys are laid alike,
    so every score is what turning the pairs in place would give."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _mla_mixer(
    cfg: TransformerConfig, kind: str, p: dict, x: jnp.ndarray,
    cache: Optional[tuple[jnp.ndarray, jnp.ndarray]], layer: Optional[jnp.ndarray], call: _Call,
) -> tuple[jnp.ndarray, Optional[tuple[jnp.ndarray, jnp.ndarray]]]:
    """Latent attention (MLA) for this call's tokens ``x`` [B, S, D] -> (x
    with the mixer's output added, the stacks (k_rope, latent) that came in
    with this call's tokens written at place ``layer``)::

        h = rms(x);  q = rms(h Wq_a) Wq_b * sqrt(D / q_rank)   [H, nope | rope]
        [c | kr] = h Wkv_a;  c = rms(c) * sqrt(D / kv_rank);  kr = rope(kr)
        (``q_lora_rank`` 0: q = h Wq; neither factor without ``mla_scale``)
        [k_nope_i | v_i] = c Wkv_b  (head i);  softmax((q_nope_i . k_nope_i
        + rope(q_rope_i) . kr) / sqrt(nope + rope)) v_i;  concat_i(...) Wo

    The cache keeps ``c`` (normed and scaled) and ``kr`` (rotated; one head,
    shared by all, not scaled) and nothing else: ``latent`` [L, B, S, rank]
    and ``k_rope`` [L, B, rope, S]. The two forms of the attention itself
    are ops/mla.py's."""
    from gofr_tpu.ops.mla import latent_attention

    freqs, positions, starts, kv_lens = call.freqs, call.positions, call.starts, call.kv_lens
    b, s, _ = x.shape
    h, rc, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    with jax.named_scope("attn.mla.q"):
        hid = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        # the product exists [B, S, width] before it is viewed by head (as
        # ``_attention_mixer``'s do: a reshape folded into the product would
        # want the weight stack relaid)
        q = jax.lax.optimization_barrier(
            _mm(rms_norm(_mm(hid, p["wq_a"]), p["q_norm"], cfg.norm_eps), p["wq_b"])
            if cfg.q_lora_rank else _mm(hid, p["wq"]))
        if cfg.mla_scale:
            q = q * (cfg.dim / cfg.q_lora_rank) ** 0.5
        q = q.reshape(b, s, h, cfg.head_dim)
        q_nope = q[..., :nope]
        q_rope = apply_rope(_pairs_apart(q[..., nope:]), freqs, positions)
    with jax.named_scope("attn.mla.latent"):
        ckr = _mm(hid, p["wkv_a"])
        c = rms_norm(ckr[..., :rc], p["kv_norm"], cfg.norm_eps)
        if cfg.mla_scale:
            c = (c.astype(jnp.float32) * (cfg.dim / rc) ** 0.5).astype(x.dtype)
        kr = apply_rope(_pairs_apart(ckr[..., rc:])[:, :, None], freqs, positions)[:, :, 0]
        kr = jnp.swapaxes(kr, 1, 2)  # [B, rope, S]
        if cache is None:
            # this call's own tokens, as a stack of one place
            k_rope_stack, latent_stack, layer = kr[None], c[None], jnp.int32(0)
            starts = jnp.zeros((b,), jnp.int32)
            kv_lens = jnp.full((b,), s, jnp.int32)
        else:
            k_rope_stack, latent_stack = cache
            for row in range(b):
                latent_stack = jax.lax.dynamic_update_slice(
                    latent_stack, c[row][None, None].astype(latent_stack.dtype),
                    (layer, row, starts[row], 0))
                k_rope_stack = jax.lax.dynamic_update_slice(
                    k_rope_stack, kr[row][None, None].astype(k_rope_stack.dtype),
                    (layer, row, 0, starts[row]))
    attn = latent_attention(q_nope, q_rope, latent_stack, k_rope_stack, p["wkv_b"], starts,
                            kv_lens, layer, impl=cfg.attn_impl)
    with jax.named_scope("attn.mla.out"):
        x = x + _mm(attn.reshape(b, s, h * cfg.v_head_dim), p["wo"])
    return x, (None if cache is None else (k_rope_stack, latent_stack))


def _latent_rows(cfg: TransformerConfig, n: int, batch: int, max_seq: int) -> dict:
    # one latent and one rotated key a token, whatever the heads; the key's
    # 64 dims stand second and the positions minor (64 in the minor place
    # would be padded to 128 by the chip's tiling)
    return {"latent": jnp.zeros((n, batch, max_seq, cfg.kv_lora_rank), cfg.dtype),
            "k_rope": jnp.zeros((n, batch, cfg.qk_rope_dim, max_seq), cfg.dtype)}


def _kv_rows(cfg: TransformerConfig, n: int, batch: int, max_seq: int) -> dict:
    shape = (n, batch, cfg.n_kv_heads, max_seq, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.kv_dtype or cfg.dtype),
            "v": jnp.zeros(shape, cfg.kv_dtype or cfg.dtype)}


def _cca_cache(cfg: TransformerConfig, n: int, batch: int, max_seq: int) -> dict:
    # beside the rows that grow by the token, a fixed tail per row
    # (``TransformerConfig.tail_dim``), in the model's own type whatever K
    # and V are held in: zeros at a sequence's start
    return {**_kv_rows(cfg, n, batch, max_seq),
            "tail": jnp.zeros((n, batch, cfg.tail_dim), cfg.dtype)}


def _retention_state(cfg: TransformerConfig, n: int, batch: int, max_seq: int) -> dict:
    # a state per row, whatever the context: no length axis
    from gofr_tpu.ops.retention import init_state

    # (beside another kind's K/V rows, ``kv_dtype`` is theirs)
    dtype = (None if cfg.mixed else cfg.kv_dtype) or jnp.float32
    s, z = init_state(batch, cfg.n_kv_heads, cfg.head_dim, dtype, layers=n)
    return {"s": s, "z": z}


def _ssm_state(cfg: TransformerConfig, n: int, batch: int, max_seq: int) -> dict:
    # the diagonal state, channels along the lanes (ops/ssm.py), and the
    # convolution's K-1 earlier inputs flat, oldest first, in the model's type
    return {"ssm": jnp.zeros((n, batch, cfg.ssm_state, cfg.d_inner), jnp.float32),
            "conv": jnp.zeros((n, batch, (cfg.ssm_conv - 1) * cfg.d_inner), cfg.dtype)}


@dataclass(frozen=True)
class Mixer:
    """A kind of layer: what stands between a block's first norm and its
    residual, and the cache it keeps. ``mix(cfg, kind, p, x, cache, layer,
    call) -> (x, cache)`` takes the kind's stacks in the order of ``cache``
    (None without a cache) and gives them back, layer ``layer`` of them
    advanced; ``make_cache(cfg, n, batch, max_seq)`` makes them for ``n``
    layers, the row axis second. ``state`` names the leaves that are a
    fixed-size state per row, read and written whole at every token and
    with no length axis (K and V rows grow by the token; a "cca" tail is a
    row's appendix to them)."""
    mix: Any
    cache: tuple
    make_cache: Any
    state: tuple = ()


MIXERS: dict[str, Mixer] = {
    "softmax": Mixer(_attention_mixer, ("k", "v"), _kv_rows),
    "cca": Mixer(_attention_mixer, ("k", "tail", "v"), _cca_cache),
    "retention": Mixer(_attention_mixer, ("s", "z"), _retention_state, state=("s", "z")),
    "ssm": Mixer(_ssm_mixer, ("conv", "ssm"), _ssm_state, state=("conv", "ssm")),
    "mla": Mixer(_mla_mixer, ("k_rope", "latent"), _latent_rows),
}
MIXER_KINDS = tuple(MIXERS)  # what ``TransformerConfig.layer_kinds`` may name
STATE_LEAVES = tuple(name for m in MIXERS.values() for name in m.state)


def _logits(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The output head over ``x`` [..., D], float32. A tied model has no
    ``lm_head``: the head is the embedding table read along its rows."""
    if "lm_head" in params:
        return _mm(x, params["lm_head"]).astype(jnp.float32)
    return jnp.einsum("...d,vd->...v", x, params["embed"]).astype(jnp.float32)


def _scan_layers(
    cfg: TransformerConfig, params: dict, x: jnp.ndarray, stacks: Optional[dict],
    block: Any, token_mask: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, Optional[dict], dict]:
    """The layer loop of every forward. ``block(layer_params, x, stacks,
    layer, mlp_fn, kind)`` runs one ``_block``; the cache stacks (by name)
    ride the loop's CARRY (a scan's ys is a fresh buffer, so stacks passed
    as xs/ys are copied slab by slab every call, and whole at the carry of
    any loop around this one). An expert model also carries the router's
    state beside ``x`` (it lives within one forward) and gives back what
    routing did: ``aux["expert_counts"]`` [L, E], the tokens each expert got.

    A model whose layers are not all alike (``cfg.mixed``) scans the
    pattern's period (``cfg.layer_period``; Jamba's 14 layers twice) and
    inside it each run of equal layers, a single layer inline; every kind's
    stacks ride every carry. A layer reads its kind's parameter stack,
    which the loops close over, at its place among its kind (what a scan
    does with its xs). One compiled body a run of the PERIOD, not of the
    stack: two state-space bodies and one attention body here, whatever
    the depth."""
    if cfg.mixed:
        period, runs = cfg.layer_period
        per_period = {kind: sum(n for k, _, n in runs if k == kind) for kind in cfg.kinds_present}

        def one(carry, kind, layer):
            x, stacks = carry
            layer_params = jax.tree.map(
                lambda leaf: jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False),
                params["layers"][kind])
            y, stacks, _ = block(layer_params, x, stacks, layer, None, kind)
            return y, stacks

        def a_period(carry, rep):
            for kind, first, count in runs:
                at = rep * per_period[kind] + first
                if count == 1:
                    carry = one(carry, kind, at)
                else:
                    carry, _ = jax.lax.scan(
                        lambda c, layer, kind=kind: (one(c, kind, layer), None), carry,
                        at + jnp.arange(count, dtype=jnp.int32))
            return carry, None

        reps = cfg.n_layers // period
        if reps == 1:
            (x, stacks), _ = a_period((x, stacks), jnp.int32(0))
        else:
            (x, stacks), _ = jax.lax.scan(
                a_period, (x, stacks), jnp.arange(reps, dtype=jnp.int32))
        return x, stacks, {}
    kind = cfg.kinds[0]
    if cfg.ffn_stacked:
        return _scan_ffn_runs(cfg, params, x, stacks, block, token_mask)
    scanned, experts = _split_experts(params["layers"], cfg.routed)
    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if experts is None:
        def body(carry, inputs):
            x, stacks = carry
            y, stacks, _ = block(inputs[0], x, stacks, inputs[1], None, kind)
            return (y, stacks), None

        (x, stacks), _ = jax.lax.scan(body, (x, stacks), (scanned, layer_ids))
        return x, stacks, {}

    from gofr_tpu.models.moe import routed_mlp

    # a double layer's sublayers are stacked [2 L, ...], sublayer j of layer
    # i at 2 i + j. The loop closes over the stacks and a layer reads its
    # two out of them (a slice [2, ...] handed in by the scan is copied
    # whole: 1.3 GB a layer at LongCat-Flash's widths)
    subs = scanned.pop("sub", None)

    def body(carry, inputs):
        x, stacks, r = carry
        layer_params, layer = inputs
        if subs is not None:
            layer_params = {**layer_params, "sub": tuple(
                jax.tree.map(lambda leaf, at=2 * layer + j: jax.lax.dynamic_index_in_dim(
                    leaf, at, 0, keepdims=False), subs) for j in range(2))}
        mlp_fn = lambda p, h: routed_mlp(  # noqa: E731
            cfg, p, h, r, experts, layer, token_mask)
        y, stacks, aux = block(layer_params, x, stacks, layer, mlp_fn, kind)
        return (y, stacks, aux["router_state"]), aux["expert_counts"]

    r0 = jnp.zeros(x.shape[:-1] + (cfg.router_dim,), jnp.float32)
    (x, stacks, _), counts = jax.lax.scan(
        body, (x, stacks, r0), (scanned, layer_ids))
    return x, stacks, {"expert_counts": counts}


def _scan_ffn_runs(
    cfg: TransformerConfig, params: dict, x: jnp.ndarray, stacks: Optional[dict],
    block: Any, token_mask: Optional[jnp.ndarray],
) -> tuple[jnp.ndarray, Optional[dict], dict]:
    """``_scan_layers`` for a model whose layers do not all take the same
    feed-forward (``cfg.ffn_kinds``): the runs of equal layers in turn
    (``cfg.ffn_runs``: Moonlight's leading dense layer inline, then one scan
    over its expert layers), each over its kind's parameter stack, which
    the loop closes over and a layer reads at its place among its kind. One
    switch over the kinds inside one scan would hand every layer both
    kinds' stacks, copied whole. A layer's place in the cache is its place
    in the model; ``aux["expert_counts"]`` [expert layers, width]."""
    from gofr_tpu.models.moe import routed_mlp

    kind = cfg.kinds[0]
    r0 = jnp.zeros(x.shape[:-1] + (cfg.router_dim,), jnp.float32)  # a linear gate keeps none
    counts = []
    for ffn, first, at, count in cfg.ffn_runs:
        scanned, experts = _split_experts(params["layers"][ffn], ffn == "moe")

        def one(carry, j, scanned=scanned, experts=experts, first=first, at=at):
            x, stacks = carry
            layer_params = jax.tree.map(
                lambda leaf: jax.lax.dynamic_index_in_dim(leaf, first + j, 0, keepdims=False),
                scanned)
            mlp_fn = None if experts is None else lambda p, h: routed_mlp(  # noqa: E731
                cfg, p, h, r0, experts, first + j, token_mask)
            y, stacks, aux = block(layer_params, x, stacks, at + j, mlp_fn, kind)
            return (y, stacks), aux.get("expert_counts")

        if count == 1:
            (x, stacks), got = one((x, stacks), jnp.int32(0))
            got = None if got is None else got[None]
        else:
            (x, stacks), got = jax.lax.scan(
                one, (x, stacks), jnp.arange(count, dtype=jnp.int32))
        if got is not None:
            counts.append(got)
    return x, stacks, {"expert_counts": jnp.concatenate(counts)}


def transformer_forward(
    params: dict, tokens: jnp.ndarray, cfg: TransformerConfig
) -> jnp.ndarray:
    """Full-sequence forward -> logits [B, S, V] (training / no-cache
    scoring). Layers run under lax.scan over stacked weights."""
    b, s = tokens.shape
    freqs = _rope_table(cfg)
    positions = jnp.arange(s)
    with jax.named_scope("embed"):
        x = params["embed"][tokens]

    def block(layer_params, x, stacks, layer, mlp_fn, kind):
        y, _, aux = _block(cfg, layer_params, x, freqs, positions, mlp_fn=mlp_fn, kind=kind)
        return y, None, aux

    x, _, _ = _scan_layers(cfg, params, x, None, block)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["norm_f"], cfg.norm_eps)
        return _logits(params, x)


# -- KV-cached ragged-batch serving path -------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_seq: int | None = None) -> dict:
    """K and V are laid out [n_layers, B, n_kv_heads, max_seq, head_dim] with
    per-request ``lengths`` [B]: the order the attention kernels read
    (ops/flash.py), stored so everywhere K and V are held (the pool, the
    prefill caches, the block arena, the wire), so no program relays the
    cache where it takes it or gives it back. ``max_seq`` must not exceed
    cfg.max_seq (the RoPE table bounds valid positions).

    The two stacks are ONE buffer each for as long as a program runs:
    every cached forward carries them through its layer loop (and a chunk
    through its step loop), writes a token's k/v at [layer, row, :, position]
    and reads a layer at a time out of the stack (``_run_cached``). A
    caller that donates the cache gets the same buffer back."""
    max_seq = max_seq or cfg.max_seq
    if max_seq > cfg.max_seq:
        raise ValueError(
            f"cache max_seq {max_seq} exceeds config max_seq {cfg.max_seq} "
            "(RoPE table bound)"
        )
    stacks: dict = {}
    for kind in cfg.kinds_present:
        stacks.update(MIXERS[kind].make_cache(
            cfg, cfg.n_of(kind) * cfg.mixers_per_layer, batch, max_seq))
    # ``live``: the rows that hold a request. The decode pool keeps it to
    # its active slots; every row of a prefill's or a solo cache is live,
    # and so is every row of a cache that lacks the leaf.
    return {**stacks, "lengths": jnp.zeros((batch,), jnp.int32),
            "live": jnp.ones((batch,), jnp.int32)}


def cache_leaves(cache: dict) -> tuple[str, ...]:
    """The names of a cache's device state: ``k`` and ``v`` (with ``tail``
    for a "cca" model), a retention model's ``s`` and ``z``, a state-space
    layer's ``ssm`` and ``conv``; a model with layers of two kinds has both
    kinds' leaves, each stacked over the layers of its kind. Every one has
    the row (slot) axis second. The per-row vectors ride beside them:
    ``lengths`` [B] and ``live`` [B]."""
    return tuple(sorted(name for name, leaf in cache.items() if leaf.ndim > 1))


def state_row_bytes(cache: dict) -> int:
    """What one row of the cache holds as a fixed-size state over all its
    layers, in bytes (``Mixer.state``: read and written whole at every
    token); 0 for a cache of K/V rows alone."""
    return sum(leaf.size // leaf.shape[1] * leaf.dtype.itemsize  # (a shape has no nbytes)
               for name, leaf in cache.items() if name in STATE_LEAVES)


def latent_token_bytes(cache: dict) -> int:
    """What one token holds in a latent cache over all its places, in bytes
    (its latent and the shared rotated key); 0 for any other cache."""
    if "latent" not in cache:
        return 0
    latent, k_rope = cache["latent"], cache["k_rope"]
    return (latent.shape[0] * latent.shape[3] * latent.dtype.itemsize
            + k_rope.shape[0] * k_rope.shape[2] * k_rope.dtype.itemsize)


def _run_cached(
    params: dict, tokens: jnp.ndarray, cache: dict, cfg: TransformerConfig,
    lengths: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, dict, jnp.ndarray, dict]:
    """Shared cached-forward body (prefill, decode, and the speculative
    verify all run THIS): ``tokens`` [B, S] starting at per-request
    ``cache['lengths']``. Returns the final-norm hidden states [B, S, D],
    the cache's stacks by name — the buffers that came in, with this
    call's tokens written into them — ``starts`` [B], and what the layers
    report of themselves (``_scan_layers``; empty for a dense model).

    Keys valid for query j of request b: cache positions <= starts_b + j
    (causal handles the per-query bound; kv_lens bounds the written region
    so never-written cache slots are excluded, and is 0 for a row that
    ``cache['live']`` says holds no request). A retention state has no
    such region: there ``lengths`` (this call's real tokens per row) keeps
    bucket padding out of the state, as it does out of a "cca" cache's
    tail and out of every expert's tokens."""
    b, s = tokens.shape
    starts = cache["lengths"]  # [B]
    freqs = _rope_table(cfg)
    positions = starts[:, None] + jnp.arange(s)[None, :]  # [B, S]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    live = cache.get("live")
    written = starts + s  # [B]
    if live is not None:
        # a row that holds no request has no keys: attention issues no
        # read for it and returns zeros (ops/flash.py, the decode form)
        written = jnp.where(live > 0, written, 0)
    valid = None
    # K/V rows have a "past the length" for padding to be dead in; a state,
    # a tail and an expert's tokens have not
    masks_pads = cfg.routed or any(k != "softmax" for k in cfg.kinds_present)
    if masks_pads and lengths is not None:
        valid = jnp.arange(s)[None, :] < lengths[:, None]
    token_mask = None
    if cfg.routed:
        # a pad token and the row of a slot without a request go to no expert
        token_mask = jnp.ones((b, s), bool) if valid is None else valid
        if live is not None:
            token_mask = token_mask & (live[:, None] > 0)
    def block(layer_params, x, stacks, layer, mlp_fn, kind):
        names = MIXERS[kind].cache
        y, merged, aux = _block(
            cfg, layer_params, x, freqs, positions,
            kv_cache=tuple(stacks[name] for name in names), layer=layer, starts=starts,
            kv_lens=written, valid=valid, live=live, mlp_fn=mlp_fn, kind=kind,
        )
        return y, {**stacks, **dict(zip(names, merged))}, aux

    x, stacks, aux = _scan_layers(
        cfg, params, x, {name: cache[name] for name in cache_leaves(cache)}, block,
        token_mask)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["norm_f"], cfg.norm_eps)
    return x, stacks, starts, aux


def _forward_with_cache(
    params: dict,
    tokens: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    lengths: Optional[jnp.ndarray],
    with_aux: bool = False,
) -> tuple:
    """Run ``tokens`` [B, S] starting at per-request ``cache['lengths']``.
    ``lengths`` [B] gives the true (un-padded) token count of this call per
    request (defaults to S). Returns logits at each request's final real
    position and the updated cache; ``with_aux`` adds ``_run_cached``'s
    report of the layers as a third."""
    b, s = tokens.shape
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    x, stacks, starts, aux = _run_cached(
        params, tokens, cache, cfg, lengths if s > 1 else None
    )
    with jax.named_scope("lm_head"):
        # gather each request's last REAL position (pad-aware bucketed
        # prefill)
        last_idx = jnp.clip(lengths - 1, 0, s - 1)  # [B]
        x_last = jnp.take_along_axis(
            x, last_idx[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        logits = _logits(params, x_last)
    new_cache = {**cache, **stacks, "lengths": starts + lengths}
    return (logits, new_cache, aux) if with_aux else (logits, new_cache)


def prefill(
    params: dict,
    tokens: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    lengths: Optional[jnp.ndarray] = None,
    with_aux: bool = False,
) -> tuple:
    """Process a (possibly padded) prompt bucket [B, S]; ``lengths`` [B] are
    true prompt lengths. Returns next-token logits [B, V] + cache (and with
    ``with_aux`` what the layers report: ``_run_cached``).

    Chunk-resume contract (chunked prefill, PREFILL_CHUNK_TOKENS): this
    call starts at ``cache['lengths']`` and attends the full written
    window, so feeding a prompt in bucket-sized slices through the SAME
    executable produces the same cache contents and final logits as one
    full-width call — each slice's keys land at their true positions and
    its queries see every earlier slice's KV. That is what lets the
    serving layer bound per-dispatch prefill compute without changing
    outputs (asserted bit-exact in tests/test_tpu.py)."""
    return _forward_with_cache(params, tokens, cache, cfg, lengths, with_aux)


def decode_step(
    params: dict, token: jnp.ndarray, cache: dict, cfg: TransformerConfig,
    with_aux: bool = False,
) -> tuple:
    """One autoregressive step: ``token`` [B, 1] -> logits [B, V] + cache."""
    return _forward_with_cache(params, token, cache, cfg, None, with_aux)


def verify_chunk(
    params: dict, tokens: jnp.ndarray, cache: dict, cfg: TransformerConfig
) -> tuple[jnp.ndarray, dict]:
    """Target-model verification step for speculative decoding: run
    ``tokens`` [B, S] (the pending token followed by S-1 draft tokens)
    through the SAME cached forward as prefill/decode (``_run_cached``)
    and return the greedy next token at EVERY position [B, S] plus the
    advanced cache. Position i's argmax is the target's continuation
    after consuming tokens[:i+1] — the host accepts the longest draft
    prefix that matches and takes position n as the bonus token. One
    dispatch verifies a whole draft chunk.

    Logits are computed in f32 (same cast as ``_forward_with_cache``) so
    the verify argmax sees the decode path's numerics; note XLA may still
    schedule the [B,S,·] matmuls differently than the [B,1,·] decode
    shapes, so near-tie logits can in principle break exact greedy
    equality on low-precision checkpoints."""
    s = tokens.shape[1]
    x, stacks, starts, _ = _run_cached(params, tokens, cache, cfg)
    logits = _logits(params, x)  # [B, S, V]
    next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_cache = {**cache, **stacks, "lengths": starts + s}
    return next_ids, new_cache


def verify_chunk_sampled(
    params: dict,
    tokens: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    draft_toks: jnp.ndarray,
    q: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray | float,
    top_k: jnp.ndarray | int = 0,
    top_p: jnp.ndarray | float = 1.0,
    min_p: jnp.ndarray | float = 0.0,
) -> tuple:
    """Canonical speculative SAMPLING verification (accept draft token x
    with prob min(1, p(x)/q(x)); on the first reject, resample from the
    residual normalize(max(p - q, 0)); after a full accept, sample the
    bonus from p) — the emitted sequence is distributed EXACTLY as
    sampling from the target's warped p, whatever the draft proposes.

    ``tokens`` [B, k] is the pending token + k-1 draft tokens;
    ``draft_toks`` [B, k-1] and ``q`` [B, k-1, V] are the draft's
    choices and the warped distributions it sampled them from (same
    temperature/top-k/top-p/min-p knobs — the guarantee is for the
    warped target distribution). Only k-1 drafts are tested so the
    accepted prefix always fits the draft cache's k written positions
    (the greedy path's same invariant). Returns (emitted [B, k], n_acc
    [B], advanced key, cache): emitted[:, j] for j < n_acc are accepted
    drafts, emitted[:, n_acc] is the correction/bonus, positions beyond
    are garbage."""
    from gofr_tpu.ops.sampling import warped_probs

    b, s = tokens.shape
    k_drafts = s - 1
    x, stacks, starts, _ = _run_cached(params, tokens, cache, cfg)
    logits = _logits(params, x)  # [B, S, V]
    v = logits.shape[-1]
    p = warped_probs(
        logits.reshape(b * s, v), temperature, top_k, top_p, min_p
    ).reshape(b, s, v)
    # accept tests for the k-1 drafts: u*q(x) < p(x) avoids the division
    px = jnp.take_along_axis(
        p[:, :k_drafts, :], draft_toks[..., None], axis=-1
    )[..., 0]  # [B, k-1]
    qx = jnp.take_along_axis(q, draft_toks[..., None], axis=-1)[..., 0]
    key, ku, kc = jax.random.split(key, 3)
    u = jax.random.uniform(ku, (b, k_drafts))
    acc = (u * qx < px).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)  # [B], <= k-1
    # correction at the reject position (residual) or bonus at position
    # k-1 after a full accept: padding q with a zero row makes the
    # residual there collapse to p — exactly the bonus distribution
    idx = n_acc[:, None, None]
    p_at = jnp.take_along_axis(p, idx, axis=1)[:, 0]  # [B, V]
    q_pad = jnp.pad(q, ((0, 0), (0, 1), (0, 0)))
    q_at = jnp.take_along_axis(q_pad, idx, axis=1)[:, 0]
    resid = jnp.maximum(p_at - q_at, 0.0)
    mass = jnp.sum(resid, axis=-1, keepdims=True)
    # p <= q pointwise means rejection probability 0 — unreachable save
    # for float dust; fall back to p rather than divide by ~0
    dist = jnp.where(mass > 1e-9, resid / jnp.maximum(mass, 1e-9), p_at)
    corr = jax.random.categorical(
        kc, jnp.log(dist + 1e-30), axis=-1
    ).astype(jnp.int32)  # [B]
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
    draft_pad = jnp.pad(draft_toks, ((0, 0), (0, 1)))
    emitted = jnp.where(
        pos < n_acc[:, None], draft_pad,
        jnp.where(pos == n_acc[:, None], corr[:, None], 0),
    )
    new_cache = {**cache, **stacks, "lengths": starts + s}
    return emitted, n_acc, key, new_cache


def draft_chunk_sampled(
    params: dict,
    token: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    n_steps: int,
    key: jax.Array,
    temperature: jnp.ndarray | float,
    top_k: jnp.ndarray | int = 0,
    top_p: jnp.ndarray | float = 1.0,
    min_p: jnp.ndarray | float = 0.0,
) -> tuple:
    """Draft proposal for speculative SAMPLING: ``n_steps`` sampled
    steps that also return the warped per-step distributions q
    [B, n_steps, V] — the verify side needs q at the chosen tokens for
    the accept tests and the full rows for the residual. Returns
    (tokens [B, n_steps], q, advanced key, cache)."""
    from gofr_tpu.ops.sampling import warped_probs

    key, sub = jax.random.split(key)

    def body(carry, _):
        tok, c, k = carry
        logits, c = decode_step(params, tok, c, cfg)
        k, s = jax.random.split(k)
        qrow = warped_probs(logits, temperature, top_k, top_p, min_p)
        nxt = jax.random.categorical(
            s, jnp.log(qrow + 1e-30), axis=-1
        ).astype(jnp.int32)
        return (nxt[:, None], c, k), (nxt, qrow)

    (_, cache, _), (toks, qs) = jax.lax.scan(
        body, (token, cache, sub), None, length=n_steps
    )
    return (
        jnp.transpose(toks),
        jnp.transpose(qs, (1, 0, 2)),
        key,
        cache,
    )


def decode_chunk(
    params: dict,
    token: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    n_steps: int,
    key: jax.Array,
    temperature: jnp.ndarray | float = 0.0,
    top_k: jnp.ndarray | int = 0,
    top_p: jnp.ndarray | float = 1.0,
    min_p: jnp.ndarray | float = 0.0,
    presence: Optional[jnp.ndarray] = None,
    repetition_penalty: jnp.ndarray | float = 1.0,
    counts: Optional[jnp.ndarray] = None,
    presence_penalty: jnp.ndarray | float = 0.0,
    frequency_penalty: jnp.ndarray | float = 0.0,
    bias: jnp.ndarray | float = 0.0,
    with_logprobs: bool = False,
) -> tuple:
    """``n_steps`` autoregressive steps in ONE dispatch: decode + on-device
    sampling under ``lax.scan``, so a whole chunk of tokens costs a single
    host↔device round trip. ``token`` [B, 1] is the last known
    token; returns sampled tokens [B, n_steps] + the advanced cache.
    temperature/top_k/top_p/min_p are dynamic (0 temperature = greedy).

    ``presence`` [B, V] bool (context-token mask) turns on the penalized
    path: logits go through ``apply_penalties`` (CTRL repetition penalty
    over the context mask, plus the additive OpenAI presence/frequency
    penalties over the GENERATED-token ``counts`` [B, V] f32, plus the
    constant ``bias`` [B, V] f32 logit_bias row) before the greedy/sampled
    split, and freshly sampled tokens join presence and counts inside the
    scan; the updated mask and counts come back as extra outputs. All
    penalty knobs are dynamic operands — every combination shares one
    executable.

    ``with_logprobs`` (static) also returns the chosen tokens' RAW model
    log-probabilities [B, n_steps] f32 — log-softmax of the unpenalized
    logits, the standard serving-API logprob — plus the top-k alternative
    values/ids [B, n_steps, TOP_LOGPROBS] as the last outputs."""
    from gofr_tpu.ops.sampling import (
        apply_penalties,
        sample_logits,
        update_counts,
        update_presence,
    )

    if presence is not None and counts is None:
        counts = jnp.zeros(presence.shape, jnp.float32)

    def body(carry, _):
        if presence is None:
            tok, c, k = carry
        else:
            tok, c, k, pres, cnt = carry
        logits, c = decode_step(params, tok, c, cfg)
        with jax.named_scope("sample"):
            k, sub = jax.random.split(k)
            sample_in = (
                logits if presence is None
                else apply_penalties(
                    logits, pres, repetition_penalty, cnt,
                    presence_penalty, frequency_penalty, bias,
                )
            )
            nxt = sample_logits(
                sample_in, sub, temperature, top_k, top_p, min_p
            )
            outs = nxt
            if with_logprobs:
                outs = (nxt, *_lp_outputs(logits, nxt))
        if presence is None:
            return (nxt[:, None], c, k), outs
        pres = update_presence(pres, nxt)
        cnt = update_counts(cnt, nxt)
        return (nxt[:, None], c, k, pres, cnt), outs

    carry0 = (
        (token, cache, key) if presence is None
        else (token, cache, key, presence, counts)
    )
    carry, outs = jax.lax.scan(body, carry0, None, length=n_steps)
    cache = carry[1]
    toks, lps, tvals, tids = outs if with_logprobs else (outs, None, None, None)
    result: tuple = (jnp.transpose(toks), cache)
    if presence is not None:
        result = result + (carry[3], carry[4])
    if with_logprobs:
        result = result + (
            jnp.transpose(lps),
            jnp.transpose(tvals, (1, 0, 2)),
            jnp.transpose(tids, (1, 0, 2)),
        )
    return result


def score_tokens(
    params: dict, tokens: jnp.ndarray, cfg: TransformerConfig
) -> jnp.ndarray:
    """Teacher-forcing scoring: [B, S] token ids -> [B, S-1] f32 where
    output[i-1] = log p(t_i | t_<i) — the loglikelihood primitive eval
    harnesses drive (completions echo+logprobs / max_tokens=0). One
    full-sequence forward; the [B, S, V] log-softmax stays on device and
    only the [B, S-1] chosen values cross the link. Causal attention
    makes bucket zero-padding safe: positions before the true length
    never see the padded tail."""
    logits = transformer_forward(params, tokens, cfg)
    lps = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(
        lps[:, :-1], tokens[:, 1:, None], axis=-1
    )[..., 0]


TOP_LOGPROBS = 5  # OpenAI's completions cap; compiled into every chunk


def pack_expert_counts(ids: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """What routing did, behind a step's token ids ``ids`` [B] int32 in the
    one array the host already fetches: ``counts`` [L, E] (tokens each
    expert of each layer got) flattened after them -> [B + L * E]."""
    return jnp.concatenate([ids, counts.reshape(-1).astype(ids.dtype)])


def unpack_expert_counts(ids: Any, rows: int, n_experts: int) -> tuple[Any, Optional[Any]]:
    """A fetched (numpy) ``pack_expert_counts`` array, rows first ([rows + L
    * E] or, a chunk's, [rows + L * E, steps]) -> (ids, counts [..., L, E]
    with a chunk's steps first); counts None where nothing rode along.
    ``n_experts`` is the counts' width (``TransformerConfig.routing_width``)."""
    if ids.shape[0] == rows:
        return ids, None
    packed = ids[rows:].T if ids.ndim > 1 else ids[rows:]
    return ids[:rows], packed.reshape(packed.shape[:-1] + (-1, n_experts))


def _chosen_logprobs(logits: jnp.ndarray, nxt: jnp.ndarray) -> jnp.ndarray:
    """[B] f32 RAW log-probabilities of the chosen tokens — log-softmax of
    the UNPENALIZED logits, the one logprob convention every decode path
    (solo, pool, penalized pool) shares."""
    return jnp.take_along_axis(
        jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
        nxt[:, None], axis=-1,
    )[:, 0]


def _lp_outputs(
    logits: jnp.ndarray, nxt: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(chosen lp [B], top-k vals [B, TOP_LOGPROBS] f32, top-k ids
    [B, TOP_LOGPROBS] i32) from one shared log-softmax — the alternatives
    OpenAI's ``logprobs: N`` returns, raw-logits convention throughout."""
    lps = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(lps, nxt[:, None], axis=-1)[:, 0]
    tvals, tids = jax.lax.top_k(lps, TOP_LOGPROBS)
    return chosen, tvals, tids.astype(jnp.int32)


def decode_chunk_pool(
    params: dict,
    token: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    n_steps: int,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    min_p: jnp.ndarray | float = 0.0,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jax.Array, dict]:
    """PER-ROW sampling params plus the on-device RNG advance and the
    feed-forward token slice, so one pooled chunk is exactly ONE dispatch:
    every extra tiny host-driven op (a key split, a [B,1] slice) is its
    own dispatch between chunks (its cost is unmeasured on this machine).

    The chosen tokens' RAW log-softmax [B, n_steps] f32 rides every chunk
    unconditionally: one [B, V] log-softmax per step is noise next to the
    weight stream decode is bound by, and folding it in keeps the pool at
    ONE executable while letting logprobs requests (including every
    best_of candidate, which scores by mean logprob) share the batch
    instead of decoding solo. Returns (sampled tokens [B, n_steps],
    logprobs [B, n_steps], top-k logprob values/ids [B, n_steps,
    TOP_LOGPROBS], next input token [B, 1], advanced key, cache)."""
    from gofr_tpu.ops.sampling import sample_logits_rows

    key, sub = jax.random.split(key)
    routed = cfg.routed

    def body(carry, _):
        tok, c, k = carry
        if routed:
            logits, c, aux = decode_step(params, tok, c, cfg, with_aux=True)
        else:
            logits, c = decode_step(params, tok, c, cfg)
        with jax.named_scope("sample"):
            k, s = jax.random.split(k)
            nxt = sample_logits_rows(
                logits, s, temperature, top_k, top_p, min_p
            )
            lp, tv, ti = _lp_outputs(logits, nxt)
        # the cache is this loop's carry and the layer loop's too
        # (_run_cached): the step hands on the buffer it was given
        out = pack_expert_counts(nxt, aux["expert_counts"]) if routed else nxt
        return (nxt[:, None], c, k), (out, lp, tv, ti)

    (tok, cache, _), (toks, lps, tvals, tids) = jax.lax.scan(
        body, (token, cache, sub), None, length=n_steps
    )
    return (jnp.transpose(toks), jnp.transpose(lps),
            jnp.transpose(tvals, (1, 0, 2)), jnp.transpose(tids, (1, 0, 2)),
            tok, key, cache)


def decode_chunk_pool_lora(
    stacked: dict,
    adapter_ids: jnp.ndarray,
    token: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    n_steps: int,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    min_p: jnp.ndarray | float = 0.0,
) -> tuple:
    """``decode_chunk_pool`` with PER-SLOT LoRA adapter selection:
    ``stacked`` is a ``build_lora_stack`` tree (the shared base plus a
    stacked adapter bank on every targeted weight) and ``adapter_ids``
    [B] i32 picks each slot's adapter (0 = base). Slots on the base
    gather the zero adapter — delta is exactly zero — so one executable
    serves any adapter/base slot mix, and adapter traffic shares the
    continuous-batching pool instead of decoding solo. Same outputs as
    ``decode_chunk_pool``."""
    from gofr_tpu.models.lora import attach_lora_ids

    params = attach_lora_ids(stacked, adapter_ids)
    return decode_chunk_pool(
        params, token, cache, cfg, n_steps, key, temperature, top_k,
        top_p, min_p,
    )


def decode_chunk_pool_penalized(
    params: dict,
    token: jnp.ndarray,
    cache: dict,
    cfg: TransformerConfig,
    n_steps: int,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    min_p: jnp.ndarray,
    presence: jnp.ndarray,
    rep: jnp.ndarray,
    counts: jnp.ndarray,
    presence_penalty: jnp.ndarray,
    frequency_penalty: jnp.ndarray,
    bias: jnp.ndarray,
) -> tuple:
    """``decode_chunk_pool`` with PER-SLOT penalty state: ``presence``
    [B, V] bool, ``counts`` [B, V] f32 and ``bias`` [B, V] f32 rows plus
    per-row scalars ``rep``/``presence_penalty``/``frequency_penalty``
    [B]. Slots without penalties carry identity knobs (rep 1, penalties
    0, zero bias row) and sample exactly as the plain pool executable
    does — ONE executable serves any penalized/plain slot mix, chosen by
    the pool only when at least one active slot is penalized (the extra
    [B, V] elementwise work is noise next to the decode matmuls, but the
    plain pool path stays untouched for penalty-free deployments).
    Returns (tokens [B, n_steps], RAW logprobs [B, n_steps] — log-softmax
    of the UNPENALIZED logits, the solo path's convention — top-k
    values/ids [B, n_steps, TOP_LOGPROBS], next token [B, 1], advanced
    key, cache, presence, counts)."""
    from gofr_tpu.ops.sampling import (
        apply_penalties,
        sample_logits_rows,
        update_counts,
        update_presence,
    )

    rep = jnp.asarray(rep, jnp.float32).reshape(-1, 1)
    pp = jnp.asarray(presence_penalty, jnp.float32).reshape(-1, 1)
    fp = jnp.asarray(frequency_penalty, jnp.float32).reshape(-1, 1)
    key, sub = jax.random.split(key)

    def body(carry, _):
        tok, c, k, pres, cnt = carry
        logits, c = decode_step(params, tok, c, cfg)
        with jax.named_scope("sample"):
            k, s = jax.random.split(k)
            penalized = apply_penalties(logits, pres, rep, cnt, pp, fp, bias)
            nxt = sample_logits_rows(
                penalized, s, temperature, top_k, top_p, min_p
            )
            lp, tv, ti = _lp_outputs(logits, nxt)
            pres = update_presence(pres, nxt)
            cnt = update_counts(cnt, nxt)
        return (nxt[:, None], c, k, pres, cnt), (nxt, lp, tv, ti)

    (tok, cache, _, presence, counts), (toks, lps, tvals, tids) = jax.lax.scan(
        body, (token, cache, sub, presence, counts), None, length=n_steps
    )
    return (jnp.transpose(toks), jnp.transpose(lps),
            jnp.transpose(tvals, (1, 0, 2)), jnp.transpose(tids, (1, 0, 2)),
            tok, key, cache, presence, counts)
