"""The routed expert product (``gofr_tpu/ops/experts.py``) against the
all-expert form (``models/moe.py::_moe_mlp_dense``) at top-1: random tokens,
an expert that gets none, every token on one expert, pad tokens and dead
rows; the counts against numpy. Both implementations: ``ragged_dot`` and the
TPU's kernels in interpret mode. CPU, small sizes (lane-aligned widths: the
kernels' blocks are whole rows of 128)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.moe import MoEConfig, _moe_mlp_dense
from gofr_tpu.ops import experts as X

D, F, E = 128, 256, 4
CFG = MoEConfig(dim=D, hidden_dim=F, n_experts=E, top_k=1, dtype=jnp.float32)


def _leaves(seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return {
        "router": jax.random.normal(ks[0], (D, E)),
        "w_gate": jax.random.normal(ks[1], (E, D, F)) * D ** -0.5,
        "w_up": jax.random.normal(ks[2], (E, D, F)) * D ** -0.5,
        "w_down": jax.random.normal(ks[3], (E, F, D)) * F ** -0.5,
    }


def _tokens(t, seed=1):
    # positive, so that a router column of one sign sends every token one way
    return jnp.abs(jax.random.normal(jax.random.key(seed), (t, D))) + 0.1


def _routed(p, x, expert, impl, layer=None):
    return X.routed_experts(x, expert, p["w_gate"], p["w_up"], p["w_down"], layer, impl=impl)


def _choice(p, x):
    return jnp.argmax(x @ p["router"], axis=-1).astype(jnp.int32)


CASES = {
    "random": lambda r: r,
    "one_expert_gets_no_token": lambda r: r.at[:, 2].set(-1.0),
    "every_token_on_one_expert": lambda r: r.at[:, 1].set(10.0),
}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("tokens", [40, 300], ids=["one_row_tile", "three_row_tiles"])
@pytest.mark.parametrize("case", list(CASES))
def test_routed_product_is_the_all_expert_form_at_top_1(case, tokens, impl):
    p = _leaves()
    p["router"] = CASES[case](p["router"])
    x = _tokens(tokens)
    want, _ = _moe_mlp_dense(p, x[None], CFG)
    expert = _choice(p, x)
    got, counts = _routed(p, x, expert, impl)
    np.testing.assert_allclose(got, want[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(counts, np.bincount(np.asarray(expert), minlength=E))
    if case == "one_expert_gets_no_token":
        assert int(counts[2]) == 0
    if case == "every_token_on_one_expert":
        assert int(counts[1]) == tokens


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_pad_tokens_and_dead_rows_add_nothing_and_are_counted_nowhere(impl):
    """Two rows of 24 positions: row 0 has 15 real tokens, row 1 is a slot
    without a request. Their tokens carry expert ``E``: no expert's."""
    p, x = _leaves(), _tokens(48)
    real = np.zeros(48, bool)
    real[:15] = True
    expert = jnp.where(jnp.asarray(real), _choice(p, x), E)
    got, counts = _routed(p, x, expert, impl)
    want, _ = _moe_mlp_dense(p, x[None], CFG)
    np.testing.assert_allclose(got[:15], want[0, :15], rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[15:]).any()
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(expert)[:15], minlength=E))
    assert int(counts.sum()) == 15


def test_no_token_at_all_reads_no_expert():
    p, x = _leaves(), _tokens(32)
    got, counts = _routed(p, x, jnp.full((32,), E, jnp.int32), "pallas")
    assert not np.asarray(got).any() and not np.asarray(counts).any()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_product_reads_its_layer_of_stacked_experts(impl):
    """The model's leaves are [layers, experts, ...]; the kernels index
    [layer, expert] themselves."""
    layers = [_leaves(seed) for seed in (3, 4, 5)]
    stacked = {k: jnp.stack([p[k] for p in layers]) for k in ("w_gate", "w_up", "w_down")}
    x = _tokens(40)
    expert = _choice(layers[1], x)
    got, _ = _routed(stacked, x, expert, impl, layer=jnp.int32(1))
    want, _ = _routed(layers[1], x, expert, "xla")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("counts,tile,tiles", [
    ([5, 0, 30, 5], 16, 3), ([0, 0, 0, 40], 16, 3), ([0, 0, 0, 0], 16, 2),
    ([128, 128, 1, 0], 128, 3), ([1, 1, 1, 1], 16, 1),
])
def test_visit_schedule_covers_each_experts_rows_once_and_no_empty_expert(counts, tile, tiles):
    offsets, expert, row_tile, visits = (np.asarray(a) for a in X.visit_schedule(
        jnp.asarray(counts, jnp.int32), tiles, tile))
    n = int(visits[0])
    assert expert.shape == row_tile.shape == (tiles + len(counts) - 1,)
    want = [(e, t) for e, c in enumerate(counts) if c
            for t in range(offsets[e] // tile, (offsets[e + 1] - 1) // tile + 1)]
    assert list(zip(expert[:n], row_tile[:n])) == want
    # what lies past the last visit repeats it: no block moves
    if n:
        assert (expert[n:] == expert[n - 1]).all() and (row_tile[n:] == row_tile[n - 1]).all()


def test_counts_of_a_chunk_become_the_three_counters():
    from gofr_tpu.tpu.introspect import DispatchRecord

    rng = np.random.default_rng(0)
    counts = rng.integers(0, 4, (8, 3, E))  # steps, layers, experts
    rec = DispatchRecord(1, "decode_chunk")
    rec.note_routing(counts)
    assert rec.expert_tokens == sum(int(c) for c in counts.flat)
    assert rec.experts_read == sum(1 for c in counts.flat if c)
    assert rec.expert_tokens_max == sum(int(max(layer)) for step in counts for layer in step)
    assert {"expert_tokens", "experts_read", "expert_tokens_max"} <= set(rec.to_dict())
