"""Mesh request validation: every malformed/unsatisfiable ``TPU_MESH``
fails AT BOOT with a ``ValueError`` that names the offending axis —
never a GSPMD shape error (or a wedge) at first dispatch. Tier-1: the
failing boots never reach a compile (mesh-fit validation runs before
params load), so each case costs milliseconds."""

import os

import pytest

from gofr_tpu.config import EnvConfig
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.device import _parse_mesh_request, new_device


def _boot(**env):
    defaults = {"MODEL_NAME": "echo", "BATCH_MAX_SIZE": "4",
                "BATCH_TIMEOUT_MS": "1"}
    defaults.update(env)
    old = {k: os.environ.get(k) for k in defaults}
    os.environ.update(defaults)
    try:
        return new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_malformed_entry_fails_at_construction():
    # the parse is device-free and runs in __init__ — before any probe
    with pytest.raises(ValueError, match="tp=abc"):
        _boot(TPU_MESH="tp=abc")


def test_unsupported_axis_names_the_axis():
    with pytest.raises(ValueError, match="'pp' not supported"):
        _boot(TPU_MESH="pp=2")


def test_mesh_larger_than_visible_devices():
    # the 8-device virtual mesh cannot host tp=64: the device-count
    # check fires at the probe, naming the request and the counts
    with pytest.raises(ValueError, match="needs 64 devices"):
        _boot(TPU_MESH="tp=64")


def test_tp_not_dividing_kv_heads_fails_before_params_load():
    # tiny has 2 kv heads; tp=4 cannot shard them — ValueError names tp
    # and fires from _validate_mesh_fit, before any checkpoint/init work
    with pytest.raises(ValueError, match=r"n_kv_heads=2 not divisible by tp=4"):
        _boot(MODEL_NAME="tiny", TPU_MESH="tp=4,dp=2")


def test_dp_not_dividing_batch_fails_at_boot():
    with pytest.raises(ValueError, match=r"dp\*fsdp=4"):
        _boot(MODEL_NAME="tiny", BATCH_MAX_SIZE="2", TPU_MESH="dp=4")


def test_tp_not_dividing_block_tokens_fails_echo_boot():
    # the echo host-mesh arena splits each block's tokens over tp:
    # KV_BLOCK_TOKENS=6 cannot split 4 ways — boot fails naming tp
    with pytest.raises(ValueError, match="tp=4 does not divide KV_BLOCK_TOKENS=6"):
        _boot(TPU_MESH="tp=4", KV_BLOCK_TOKENS="6", KV_BLOCKS="16")


def test_parse_is_the_single_grammar():
    assert _parse_mesh_request("tp=2,dp=2") == {"tp": 2, "dp": 2}
    assert _parse_mesh_request("") is None
    assert _parse_mesh_request("2x4") is None  # TPU VM physical grid form
    with pytest.raises(ValueError, match="malformed"):
        _parse_mesh_request("tp=")


def test_seeded_init_places_weights_on_the_mesh_as_created():
    """Building the model whole and sharding it afterwards piles it on one
    device first; under a mesh the seeded init places each weight as it is
    made — same values, same layout as build-then-shard."""
    import jax
    import numpy as np

    from gofr_tpu.models.llama import TINY
    from gofr_tpu.models.transformer import init_transformer
    from gofr_tpu.parallel.mesh import make_mesh
    from gofr_tpu.parallel.sharding import shard_params

    mesh = make_mesh({"fsdp": 2, "tp": 2}, devices=jax.devices()[:4])
    placed = init_transformer(jax.random.key(0), TINY, quantize="int8", mesh=mesh)
    whole = shard_params(
        init_transformer(jax.random.key(0), TINY, quantize="int8"), mesh
    )
    for got, want in zip(jax.tree.leaves(placed), jax.tree.leaves(whole)):
        assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
