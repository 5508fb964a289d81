"""The selective scan (``gofr_tpu/ops/ssm.py``): the token-by-token
recurrence, the chunked XLA form and both Pallas kernels (interpret mode)
agree; a prompt in slices from a carried state is the prompt in one pass;
padding enters neither the state nor the convolution's tail; a row that is
not live moves nothing. CPU, small widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import transformer as T
from gofr_tpu.models.llama import CONFIGS
from gofr_tpu.ops import ssm

# the same products and sums in another grouping (a fused body, a kernel's
# tiles): float32 rounding, measured 1.5e-6 on outputs of size 5
TOLERANCE = 2e-5


def _inputs(bsz, t, di, n=16, layers=3, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(ks[0], (bsz, t, di))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, di)) - 3.0)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, di))
    b = jax.random.normal(ks[2], (bsz, t, n))
    c = jax.random.normal(ks[3], (bsz, t, n))
    stack = jax.random.normal(ks[4], (layers, bsz, n, di))
    return u, delta, a, b, c, stack


def _by_hand(u, delta, a, b, c, s):
    """The recurrence as the module text writes it, in numpy, one token and
    one row at a time."""
    u, delta, a, b, c, s = (np.asarray(x, np.float64) for x in (u, delta, a, b, c, s))
    ys = np.zeros(u.shape)
    for r in range(u.shape[0]):
        for t in range(u.shape[1]):
            s[r] = (np.exp(delta[r, t][None] * a) * s[r]
                    + (delta[r, t] * u[r, t])[None] * b[r, t][:, None])
            ys[r, t] = (s[r] * c[r, t][:, None]).sum(0)
    return ys, s


def test_the_token_by_token_recurrence_is_the_equations():
    u, delta, a, b, c, stack = _inputs(2, 9, 128)
    y, s = ssm.scan_tokens(u, delta, a, b, c, stack[1])
    want_y, want_s = _by_hand(u, delta, a, b, c, stack[1])
    np.testing.assert_allclose(y, want_y, atol=TOLERANCE)
    np.testing.assert_allclose(s, want_s, atol=TOLERANCE)


@pytest.mark.parametrize("bsz,t,di", [(3, 16, 256), (1, 24, 128), (2, 13, 128)])
def test_the_chunked_form_and_the_scan_kernel_are_the_token_by_token_recurrence(bsz, t, di):
    u, delta, a, b, c, stack = _inputs(bsz, t, di)
    y, s = ssm.scan_tokens(u, delta, a, b, c, stack[1])
    y1, s1 = ssm.scan_chunked(u, delta, a, b, c, stack[1])  # 13 tokens: padded to 16
    np.testing.assert_allclose(y1, y, atol=TOLERANCE)
    np.testing.assert_allclose(s1, s, atol=TOLERANCE)
    if t % ssm.UNROLL:
        with pytest.raises(ValueError, match="T % 8"):
            ssm.scan_pallas(u, delta, a, b, c, stack, jnp.int32(1), interpret=True)
        return
    y2, out = ssm.scan_pallas(u, delta, a, b, c, stack, jnp.int32(1), interpret=True)
    np.testing.assert_allclose(y2, y, atol=TOLERANCE)
    np.testing.assert_allclose(out[1], s, atol=TOLERANCE)
    for other in (0, 2):  # the other layers of the stack as they were
        np.testing.assert_array_equal(out[other], stack[other])


@pytest.mark.parametrize("live", [None, [1, 0, 1, 1], [0, 0, 0, 0], [0, 1, 0, 0], [0, 0, 0, 1]])
def test_the_step_kernel_is_one_token_of_the_recurrence_and_moves_nothing_for_a_dead_row(live):
    u, delta, a, b, c, stack = _inputs(4, 1, 256)
    want_y, want_s = ssm.scan_tokens(u, delta, a, b, c, stack[2])
    mask = None if live is None else jnp.asarray(live, jnp.int32)
    on = np.ones(4, bool) if live is None else np.asarray(live, bool)
    for impl in ("pallas", "xla"):
        y, out = ssm.scan_cached(u, delta, a, b, c, stack, jnp.int32(2), impl=impl, live=mask)
        np.testing.assert_allclose(np.asarray(out[2])[on], np.asarray(want_s)[on], atol=TOLERANCE)
        np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on], atol=TOLERANCE)
        np.testing.assert_array_equal(np.asarray(out[2])[~on], np.asarray(stack[2])[~on])
        np.testing.assert_array_equal(out[:2], stack[:2])


def test_a_row_that_is_not_live_keeps_its_state_through_the_chunked_forms_too():
    u, delta, a, b, c, stack = _inputs(3, 16, 128)
    live = jnp.asarray([1, 0, 1], jnp.int32)
    _, want = ssm.scan_tokens(u, delta, a, b, c, stack[0])
    for impl in ("pallas", "xla"):
        _, out = ssm.scan_cached(u, delta, a, b, c, stack, jnp.int32(0), impl=impl, live=live)
        np.testing.assert_array_equal(out[0, 1], stack[0, 1])
        np.testing.assert_allclose(out[0, ::2], want[::2], atol=TOLERANCE)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_prompt_in_slices_from_a_carried_state_is_the_prompt_in_one_pass(impl):
    u, delta, a, b, c, stack = _inputs(2, 40, 128)
    zero = jnp.zeros_like(stack)
    y, whole = ssm.scan_cached(u, delta, a, b, c, zero, jnp.int32(1), impl=impl)
    carried, ys = zero, []
    for lo, hi in ((0, 16), (16, 32), (32, 40)):
        part, carried = ssm.scan_cached(u[:, lo:hi], delta[:, lo:hi], a, b[:, lo:hi],
                                        c[:, lo:hi], carried, jnp.int32(1), impl=impl)
        ys.append(part)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), y, atol=TOLERANCE)
    np.testing.assert_allclose(carried, whole, atol=TOLERANCE)
    # and the steps that follow it, one token at a time
    step_y, stepped = ssm.scan_cached(u[:, :1], delta[:, :1], a, b[:, :1], c[:, :1], whole,
                                      jnp.int32(1), impl=impl)
    again_y, again = ssm.scan_tokens(u[:, :1], delta[:, :1], a, b[:, :1], c[:, :1], whole[1])
    np.testing.assert_allclose(stepped[1], again, atol=TOLERANCE)
    np.testing.assert_allclose(step_y, again_y, atol=TOLERANCE)


def test_a_state_kept_in_bfloat16_comes_back_in_bfloat16_and_is_rounded_every_step():
    u, delta, a, b, c, stack = _inputs(2, 1, 128)
    low = stack.astype(jnp.bfloat16)
    for impl in ("pallas", "xla"):
        _, out = ssm.scan_cached(u, delta, a, b, c, low, jnp.int32(0), impl=impl)
        assert out.dtype == jnp.bfloat16
        _, want = ssm.scan_tokens(u, delta, a, b, c, low[0].astype(jnp.float32))
        # to a unit of bfloat16's last place: a float32 sum that lands on a
        # rounding boundary may fall either way by the order of its terms
        np.testing.assert_allclose(out[0].astype(jnp.float32), want, rtol=2 ** -7, atol=1e-6)


# -- the mixer around the scan: padding and the convolution's tail ------------------------------

def _mixer(cfg, p, x, cache, valid=None, live=None):
    call = T._Call(None, None, None, None, None, valid, live)
    return T._ssm_mixer(cfg, "ssm", p, x, cache, jnp.int32(1), call)


def _layer():
    cfg = CONFIGS["tiny-jamba"]
    params = T.init_transformer(jax.random.key(3), cfg)
    p = jax.tree.map(lambda leaf: leaf[1], params["layers"]["ssm"])
    p["ssm_conv_b"] = p["ssm_conv_b"] + 0.3  # a bias that is not the init's zero
    cache = T.init_cache(cfg, 2)
    return cfg, p, (cache["conv"], cache["ssm"])


def test_a_buckets_padding_enters_neither_the_state_nor_the_tail():
    cfg, p, cache = _layer()
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.dim))
    lengths = jnp.asarray([11, 16])
    valid = jnp.arange(16)[None, :] < lengths[:, None]
    y, (conv, state) = _mixer(cfg, p, x, cache, valid)
    # row 0 alone, its 11 real tokens and nothing else
    y0, (conv0, state0) = _mixer(cfg, p, x[:1, :11], tuple(leaf[:, :1] for leaf in cache))
    np.testing.assert_allclose(y[0, :11], y0[0], atol=TOLERANCE)
    np.testing.assert_allclose(state[1, 0], state0[1, 0], atol=TOLERANCE)
    np.testing.assert_array_equal(conv[1, 0], conv0[1, 0])
    # garbage in the padding changes neither
    noisy = x.at[0, 11:].set(1e3)
    _, (conv_n, state_n) = _mixer(cfg, p, noisy, cache, valid)
    np.testing.assert_array_equal(conv_n[1], conv[1])
    np.testing.assert_array_equal(state_n[1], state[1])
    # without the mask it does
    _, (conv_bad, state_bad) = _mixer(cfg, p, noisy, cache)
    assert not np.allclose(state_bad[1, 0], state[1, 0])
    assert not np.allclose(conv_bad[1, 0], conv[1, 0])
    # the other layers of both stacks as they were
    np.testing.assert_array_equal(conv[0], cache[0][0])
    np.testing.assert_array_equal(state[2], cache[1][2])


def test_the_tail_is_the_three_inputs_before_the_next_token_oldest_first():
    cfg, p, cache = _layer()
    x = jax.random.normal(jax.random.key(2), (2, 16, cfg.dim))
    _, (conv, state) = _mixer(cfg, p, x, cache)
    u = (T.rms_norm(x, p["attn_norm"], cfg.norm_eps) @ p["ssm_in"])[..., :cfg.d_inner]
    np.testing.assert_allclose(conv[1].reshape(2, 3, cfg.d_inner), u[:, 13:], atol=1e-6)
    # a step from that tail and state is the 17th token of one pass
    more = jax.random.normal(jax.random.key(4), (2, 1, cfg.dim))
    y17, _ = _mixer(cfg, p, jnp.concatenate([x, more], axis=1), cache)
    step, (conv2, _) = _mixer(cfg, p, more, (conv, state))
    np.testing.assert_allclose(step[:, 0], y17[:, 16], atol=TOLERANCE)
    np.testing.assert_allclose(conv2[1].reshape(2, 3, cfg.d_inner)[:, :2], u[:, 14:], atol=1e-6)


def test_a_row_that_is_not_live_keeps_state_and_tail_through_the_mixer():
    cfg, p, cache = _layer()
    x = jax.random.normal(jax.random.key(2), (2, 16, cfg.dim))
    _, held = _mixer(cfg, p, x, cache)
    more = jax.random.normal(jax.random.key(4), (2, 1, cfg.dim))
    live = jnp.asarray([0, 1], jnp.int32)
    _, (conv, state) = _mixer(cfg, p, more, held, live=live)
    np.testing.assert_array_equal(conv[1, 0], held[0][1, 0])
    np.testing.assert_array_equal(state[1, 0], held[1][1, 0])
    assert not np.array_equal(conv[1, 1], held[0][1, 1])
    assert not np.array_equal(state[1, 1], held[1][1, 1])
