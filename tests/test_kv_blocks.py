"""Paged KV allocator (tpu/kv_blocks.py): BlockPool invariants under
unit and fuzzed workloads (no double-free, no leak, refcounts never
negative), copy-on-write, LRU eviction under budget, the admission
ledger, and the host paged engine's aliasing fidelity — all
compile-free (the device arena's scatter/gather roundtrip is the one
small-jit exception)."""

import random

import numpy as np
import pytest

from gofr_tpu.metrics import Registry
from gofr_tpu.tpu.kv_blocks import (
    BlockPool,
    BlockTable,
    HostPagedKV,
    HostTokenArena,
    KVExhausted,
    blocks_for,
)


def _pool(n=16, bt=4, **kw):
    arena = HostTokenArena(n, bt)
    return BlockPool(n, bt, arena=arena, **kw), arena


# -- allocator invariants -----------------------------------------------------

def test_alloc_release_roundtrip():
    pool, _ = _pool()
    a = pool.alloc(5)
    assert len(a) == 5 and len(set(a)) == 5
    st = pool.stats()
    assert st["free"] == 11 and st["active"] == 5
    pool.release_blocks(a)
    assert pool.stats()["free"] == 16


def test_exhaustion_raises_and_counts():
    pool, _ = _pool(n=4)
    pool.alloc(4)
    with pytest.raises(KVExhausted):
        pool.alloc(1)
    assert pool.stats()["kv_exhausted_rejects"] == 1


def test_double_free_raises():
    pool, _ = _pool()
    (b,) = pool.alloc(1)
    pool.release_blocks([b])
    with pytest.raises(RuntimeError, match="double free"):
        pool.release_blocks([b])


def test_incref_of_free_block_raises():
    pool, _ = _pool()
    (b,) = pool.alloc(1)
    pool.release_blocks([b])
    with pytest.raises(RuntimeError, match="use-after-free"):
        pool.incref([b])


def test_scratch_block_never_allocated():
    pool = BlockPool(8, 4, scratch=True)
    got = pool.alloc(7)  # everything allocatable
    assert 0 not in got
    assert pool.total_blocks == 7
    with pytest.raises(KVExhausted):
        pool.alloc(1)


def test_reserve_ensure_trim():
    pool, _ = _pool(n=16, bt=4)
    t = pool.reserve(10)  # 3 blocks of capacity, length 0
    assert len(t.blocks) == 3 and t.length == 0
    pool.ensure(t, 22)  # grow to 6 blocks
    assert len(t.blocks) == 6
    t.length = 9  # only 3 blocks actually used
    assert pool.trim(t) == 3
    assert len(t.blocks) == 3
    pool.release(t)
    assert pool.stats()["free"] == 16 and t.blocks == []


# -- aliasing + copy-on-write -------------------------------------------------

def test_rollback_then_finish_releases_every_rejected_block():
    """The spec reject path end to end at the allocator level: drafts
    appended across block boundaries, rolled back, the sequence
    finished — blocks for rejected tokens must all come back (at
    finish, via trim: live sequences keep their reserved capacity so
    an admitted request can never starve mid-decode)."""
    arena = HostTokenArena(16, 2)
    pool = BlockPool(16, 2, arena=arena)
    engine = HostPagedKV(pool, arena, lcp_min=4)
    seq = engine.admit(np.asarray([1, 2, 3], np.int32), 8)
    capacity = len(seq.table.blocks)
    for t in (10, 11, 12, 13, 14):  # spec drafts across 3 boundaries
        engine.append(seq, t)
    engine.rollback(seq, 4)  # keep one draft, reject four
    # live rollback retains capacity (reserved at admission)...
    assert len(seq.table.blocks) == capacity
    assert seq.table.length == 4
    engine.finish(seq, store=False)
    # ...and finish returns EVERYTHING the request held, rejected-draft
    # blocks included (admission cached the prompt entry by design —
    # purging it must balance the pool to empty)
    assert pool.stats()["active"] == 0
    pool.cache_clear()
    assert pool.stats()["free"] == pool.stats()["total"]


def test_alias_shares_blocks_and_survives_donor_release():
    pool, arena = _pool(bt=4)
    donor = pool.reserve(8)
    arena.write(donor, 0, np.arange(8))
    donor.length = 8
    al = pool.alias(donor, 8)
    assert al.blocks == donor.blocks
    pool.release(donor)
    # aliased blocks still alive (refcounted), content intact
    assert list(arena.read(al)) == list(range(8))
    pool.release(al)
    assert pool.stats()["free"] == 16


def test_cow_boundary_copies_shared_partial_block():
    pool, arena = _pool(bt=4)
    donor = pool.reserve(6)
    arena.write(donor, 0, np.arange(6))
    donor.length = 6
    al = pool.alias(donor, 6)  # boundary block (tokens 4-5) shared
    pool.cow_boundary(al)
    assert al.blocks[-1] != donor.blocks[-1]  # private copy now
    assert pool.stats()["cow_copies"] == 1
    pool.ensure(al, 7)
    arena.write(al, 6, [99])
    al.length = 7
    # the donor's view is untouched by the alias's append
    assert list(arena.read(donor)) == list(range(6))
    assert list(arena.read(al)) == list(range(6)) + [99]


def test_cow_noop_when_private_or_aligned():
    pool, arena = _pool(bt=4)
    t = pool.reserve(6)
    arena.write(t, 0, np.arange(6))
    t.length = 6
    assert pool.cow_boundary(t) is None  # private
    t.length = 4
    al = pool.alias(t, 4)
    assert pool.cow_boundary(al) is None  # block-aligned boundary


# -- cache registry + eviction ------------------------------------------------

def _cached_seq(pool, arena, tokens):
    t = pool.reserve(len(tokens))
    arena.write(t, 0, np.asarray(tokens, np.int32))
    t.length = len(tokens)
    pool.cache_put(np.asarray(tokens, np.int32).tobytes(), t, {"length": len(tokens)})
    return t


def test_cache_put_lookup_lru_bound():
    pool, arena = _pool(n=32, bt=4, cache_entries=2)
    for i in range(4):
        _cached_seq(pool, arena, [i] * 5)
    st = pool.stats()
    assert st["cached_entries"] == 2
    assert st["evictions"] == 2
    # oldest evicted, newest present
    assert pool.cache_lookup(np.asarray([0] * 5, np.int32).tobytes()) is None
    assert pool.cache_lookup(np.asarray([3] * 5, np.int32).tobytes()) is not None


def test_allocation_pressure_evicts_lru_cache():
    pool, arena = _pool(n=8, bt=4)
    _cached_seq(pool, arena, [1] * 8)   # 2 blocks
    _cached_seq(pool, arena, [2] * 8)   # 2 blocks
    live = pool.alloc(4)                # remaining free blocks
    assert pool.stats()["free"] == 0
    got = pool.alloc(2)                 # must evict the LRU entry
    assert pool.stats()["evictions"] == 1
    assert pool.cache_lookup(np.asarray([1] * 8, np.int32).tobytes()) is None
    assert pool.cache_lookup(np.asarray([2] * 8, np.int32).tobytes()) is not None
    pool.release_blocks(live + got)


def test_eviction_spares_blocks_shared_with_live_requests():
    pool, arena = _pool(n=8, bt=4)
    t = _cached_seq(pool, arena, list(range(16)))  # 4 blocks cached
    al = pool.alias(t, 16)  # a live request shares the entry's blocks
    pool.alloc(4)  # the other half of the arena
    with pytest.raises(KVExhausted):
        # the entry's blocks are pinned by the live alias, so eviction
        # could free NOTHING: the doomed alloc must fail upfront, not
        # wipe the cache as collateral
        pool.alloc(2)
    assert pool.stats()["evictions"] == 0
    key = np.asarray(list(range(16)), np.int32).tobytes()
    assert pool.cache_lookup(key) is not None  # entry survived
    assert list(arena.read(al)) == list(range(16))  # content intact
    pool.release(al)  # the live alias drops: blocks become reclaimable
    got = pool.alloc(2)  # NOW eviction frees them and the alloc lands
    assert pool.stats()["evictions"] == 1
    assert pool.cache_lookup(key) is None
    pool.release_blocks(got)


def test_cache_clear_releases_everything():
    pool, arena = _pool(n=16, bt=4)
    for i in range(3):
        _cached_seq(pool, arena, [i] * 6)
    pool.cache_clear()
    st = pool.stats()
    assert st["free"] == 16 and st["cached_entries"] == 0
    assert st["evictions"] == 0  # administrative purge, not pressure


# -- admission ledger ---------------------------------------------------------

def test_ledger_reserve_release_and_exhaustion():
    pool, _ = _pool(n=8, bt=4)
    r1 = pool.reserve_ledger(20)  # 5 blocks of an 8-block ledger
    assert r1 == 5
    with pytest.raises(KVExhausted):
        pool.reserve_ledger(16)  # 4 more don't fit
    r2 = pool.reserve_ledger(12)  # 3 do
    assert pool.stats()["reserved"] == 8
    pool.release_ledger(r1)
    # freed budget admits the next request immediately
    assert pool.reserve_ledger(20) == 5
    pool.release_ledger(r2)


def test_ledger_treats_cached_blocks_as_reclaimable():
    pool, arena = _pool(n=8, bt=4)
    _cached_seq(pool, arena, [7] * 32)  # cache fills the whole arena
    assert pool.stats()["cached"] == 8
    # admission still succeeds: cached blocks evict on demand
    r = pool.reserve_ledger(32)
    assert r == 8
    pool.release_ledger(r)


def test_separate_ledger_budget():
    pool = BlockPool(4, 4, ledger_blocks=10)
    r = pool.reserve_ledger(40)  # 10 blocks, beyond the 4 physical
    assert r == 10
    with pytest.raises(KVExhausted):
        pool.reserve_ledger(4)
    pool.release_ledger(r)


# -- metrics ------------------------------------------------------------------

def test_block_state_gauge_and_eviction_counter():
    registry = Registry()
    arena = HostTokenArena(8, 4)
    pool = BlockPool(8, 4, arena=arena, cache_entries=1, metrics=registry)
    _cached_seq(pool, arena, [1] * 8)
    _cached_seq(pool, arena, [2] * 8)  # evicts the first (entry bound)
    text = registry.expose()
    assert 'gofr_tpu_kv_blocks{state="total"} 8' in text
    assert 'gofr_tpu_kv_blocks{state="cached"} 2' in text
    assert "gofr_tpu_kv_evictions_total 1" in text


# -- fuzz: allocator invariants under random workloads ------------------------

def test_fuzzed_alloc_alias_cow_evict_invariants():
    """Randomized sequences of reserve/ensure/alias/COW/append/finish/
    release against live invariant checks: refcounts consistent, no
    leaks (everything released -> all free), cached accounting exact,
    and every table reads back exactly the tokens written through it."""
    rng = random.Random(1234)
    for round_ in range(20):
        n_blocks, bt = rng.choice([(12, 2), (24, 4), (48, 3)])
        arena = HostTokenArena(n_blocks, bt)
        pool = BlockPool(
            n_blocks, bt, arena=arena,
            cache_entries=rng.choice([0, 2, 4]),
        )
        engine = HostPagedKV(pool, arena, lcp_min=2)
        live = []  # (seq, expected_tokens, decode_budget_left)
        next_tok = 1
        for _ in range(120):
            op = rng.random()
            if op < 0.45:  # admit a new sequence
                size = rng.randint(1, 2 * bt + 1)
                prompt = np.arange(next_tok, next_tok + size) % 251
                next_tok += size
                if rng.random() < 0.3 and live:
                    # force sharing: reuse an existing prompt's tokens
                    prompt = live[rng.randrange(len(live))][1][:size].copy()
                    if prompt.size == 0:
                        continue
                max_new = rng.randint(0, bt)
                try:
                    seq = engine.admit(prompt, max_new)
                except KVExhausted:
                    continue
                assert list(engine.prompt_tokens(seq)) == list(prompt)
                live.append((seq, np.asarray(prompt, np.int32), max_new))
            elif op < 0.65 and live:  # append (COW path)
                i = rng.randrange(len(live))
                seq, toks, budget = live[i]
                if budget <= 0:  # reservation cap: appends never allocate
                    continue
                t = int(next_tok % 251)
                next_tok += 1
                engine.append(seq, t)
                live[i] = (seq, np.append(toks, t).astype(np.int32),
                           budget - 1)
            elif op < 0.8 and live:  # speculative drafts + rollback
                i = rng.randrange(len(live))
                seq, toks, budget = live[i]
                if budget <= 0:
                    continue
                k = rng.randint(1, budget)
                drafts = [int((next_tok + j) % 251) for j in range(k)]
                next_tok += k
                base = seq.table.length
                for t in drafts:
                    engine.append(seq, t)  # speculative writes (COW too)
                keep = rng.randint(0, k)  # verify keeps a prefix
                engine.rollback(seq, base + keep)
                live[i] = (
                    seq,
                    np.append(toks, drafts[:keep]).astype(np.int32),
                    budget - keep,
                )
            elif live:  # finish (store or abort)
                i = rng.randrange(len(live))
                seq, toks, _ = live.pop(i)
                read = arena.read(seq.table)
                assert list(read) == list(toks), (round_, list(read), list(toks))
                engine.finish(seq, store=rng.random() < 0.7)
            # standing invariants
            st = pool.stats()
            assert st["free"] + st["cached"] + st["active"] == st["total"]
            assert st["free"] >= 0 and st["cached"] >= 0 and st["active"] >= 0
        # drain: every content check then full release
        for seq, toks, _ in live:
            assert list(arena.read(seq.table)) == list(toks)
            engine.abort(seq)
        pool.cache_clear()
        st = pool.stats()
        assert st["free"] == st["total"], (round_, st)  # no leak
        assert st["cached"] == 0 and st["active"] == 0


# -- host engine: aliasing fidelity + continuous admission --------------------

def _engine(n=64, bt=4, lcp_min=4, copy_mode=False, cache_entries=8):
    arena = HostTokenArena(n, bt)
    pool = BlockPool(n, bt, arena=arena, cache_entries=cache_entries)
    return HostPagedKV(pool, arena, lcp_min=lcp_min, copy_mode=copy_mode)


def test_aliased_and_copy_paths_read_identical_tokens():
    """THE bit-identity property: the copy-free aliased path returns
    exactly the tokens the slot-model copy path returns, for exact and
    LCP partial hits."""
    prompts = [
        [5, 6, 7, 8, 9, 10, 11, 12],
        [5, 6, 7, 8, 9, 10, 11, 12],          # exact repeat
        [5, 6, 7, 8, 9, 10, 99, 98, 97],      # LCP partial
        [5, 6, 7, 8, 42],                      # shorter LCP
    ]
    outs = {}
    for mode in (False, True):
        eng = _engine(copy_mode=mode)
        got = []
        for p in prompts:
            seq = eng.admit(np.asarray(p, np.int32), 4)
            got.append(list(eng.prompt_tokens(seq)))
            for t in (71, 72):
                eng.append(seq, t)
            assert list(eng.arena.read(seq.table)) == list(p) + [71, 72]
            eng.finish(seq)
        outs[mode] = got
    assert outs[False] == outs[True]
    # and the paged mode actually aliased: exact repeat cost 0 copies
    eng = _engine()
    a = eng.admit(np.asarray(prompts[0], np.int32), 0)
    eng.finish(a)
    before = eng.pool.stats()["copied_kv_bytes"]
    b = eng.admit(np.asarray(prompts[0], np.int32), 0)
    assert b.kind == "hit" and b.aliased_blocks == len(b.table.blocks)
    assert eng.pool.stats()["copied_kv_bytes"] == before  # copy-free
    eng.finish(b)


def test_partial_hit_aliases_whole_blocks_only():
    eng = _engine(bt=4, lcp_min=4)
    a = eng.admit(np.asarray([1, 2, 3, 4, 5, 6], np.int32), 0)
    eng.finish(a)
    b = eng.admit(np.asarray([1, 2, 3, 4, 5, 9, 9], np.int32), 0)
    assert b.kind == "partial_hit"
    assert b.aliased_blocks == 1  # tokens 1-4 shared; 5 sits mid-block
    assert list(eng.prompt_tokens(b)) == [1, 2, 3, 4, 5, 9, 9]
    eng.finish(b)


def test_admission_exhaustion_rolls_back_cleanly():
    eng = _engine(n=8, bt=4, cache_entries=0)
    seq = eng.admit(np.asarray([1] * 8, np.int32), 8)  # 4 blocks
    free_before = eng.pool.stats()["free"]
    with pytest.raises(KVExhausted):
        eng.admit(np.asarray([2] * 24, np.int32), 8)  # needs > free
    assert eng.pool.stats()["free"] == free_before  # full rollback
    eng.finish(seq, store=False)
    # the prompt entry (2 aliased blocks) survives the finish — the
    # doomed admission above must NOT have wiped it
    assert eng.pool.stats()["free"] == 6
    assert eng.pool.stats()["cached"] == 2
    eng.pool.cache_clear()
    assert eng.pool.stats()["free"] == 8


def test_freed_blocks_admit_waiting_request_mid_flight():
    """Continuous batching at block granularity: B cannot admit while A
    holds the arena; the moment A finishes, B admits — while C (admitted
    small) is still mid-decode."""
    eng = _engine(n=12, bt=4, cache_entries=0)
    a = eng.admit(np.asarray([1] * 16, np.int32), 16)  # 8 blocks
    c = eng.admit(np.asarray([3] * 8, np.int32), 4)    # 3 blocks, mid-decode
    eng.append(c, 30)
    with pytest.raises(KVExhausted):
        eng.admit(np.asarray([2] * 16, np.int32), 0)   # 4 blocks: only 1 free
    eng.finish(a, store=False)                          # A's blocks free NOW
    b = eng.admit(np.asarray([2] * 16, np.int32), 0)   # admitted mid-flight
    eng.append(c, 31)                                   # C still decoding fine
    assert list(eng.arena.read(c.table))[-2:] == [30, 31]
    eng.finish(b, store=False)
    eng.finish(c, store=False)


# -- device arena: block <-> row bridge (small jit, CPU-fast) -----------------

def _arena_row(cfg, seed, length):
    """A compute row as ``init_cache`` lays one out, [L, 1, Hkv, S, D]."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    shape = (cfg.n_layers, 1, cfg.n_kv_heads, cfg.max_seq, cfg.head_dim)
    r = rng.standard_normal(shape, dtype=np.float32)
    return {
        "k": jnp.asarray(r, cfg.cache_dtype),
        "v": jnp.asarray(-r, cfg.cache_dtype),
        "lengths": jnp.asarray([length], jnp.int32),
    }


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("kv_dtype", [None, "f8"])
def test_jax_arena_scatter_gather_roundtrip_and_skip(kv_dtype):
    import dataclasses

    import jax.numpy as jnp

    from gofr_tpu.models.llama import CONFIGS
    from gofr_tpu.models.transformer import init_cache
    from gofr_tpu.tpu.kv_blocks import JaxKVArena

    cfg = CONFIGS["tiny"]  # max_seq 128
    if kv_dtype:
        cfg = dataclasses.replace(cfg, kv_dtype=jnp.float8_e4m3fn)
    bt = 32
    arena = JaxKVArena(cfg, n_blocks=9, block_tokens=bt)
    pool = BlockPool(9, bt, block_bytes=arena.block_bytes, scratch=True)
    # blocks in the order the compute caches have: heads before positions
    assert arena.k.shape == (cfg.n_layers, 9, cfg.n_kv_heads, bt, cfg.head_dim)

    length = 70  # 3 blocks, boundary mid-block
    row = _arena_row(cfg, 1, length)
    t = pool.reserve(length)
    t.length = length
    copied = arena.scatter_row(row, t)
    assert copied == 3 * arena.block_bytes
    back = arena.gather_row(t, length)
    # a row as init_cache makes one; bit-identical for every valid
    # position; lengths mirrors the request
    assert back["k"].shape == init_cache(cfg, 1)["k"].shape
    assert int(back["lengths"][0]) == length
    for f in ("k", "v"):
        _same_bits(back[f][:, :, :, :length], row[f][:, :, :, :length])
    # skip_blocks: an aliased prefix keeps its DONOR content even when a
    # different row is scattered over the same table
    other = _arena_row(cfg, 2, length)
    copied2 = arena.scatter_row(other, t, skip_blocks=2)
    assert copied2 == 1 * arena.block_bytes
    back2 = arena.gather_row(t, length)
    for f in ("k", "v"):
        # first 2 blocks: original content; third block: the new row's
        _same_bits(back2[f][:, :, :, : 2 * bt], row[f][:, :, :, : 2 * bt])
        _same_bits(back2[f][:, :, :, 2 * bt : length], other[f][:, :, :, 2 * bt : length])


def test_jax_arena_block_payloads_survive_the_wire():
    """A donor's blocks, framed by fleet/kvwire.py and installed in a
    receiver's arena, gather into the donor's row bit for bit; a peer
    whose blocks lie in another order is refused by the spec check."""
    from gofr_tpu.fleet import kvwire
    from gofr_tpu.models.llama import CONFIGS
    from gofr_tpu.tpu.kv_blocks import JaxKVArena

    cfg = CONFIGS["tiny"]
    bt, length = 32, 70
    donor = JaxKVArena(cfg, n_blocks=9, block_tokens=bt)
    receiver = JaxKVArena(cfg, n_blocks=9, block_tokens=bt)
    spec = donor.wire_spec()
    assert spec["block_shape"] == [cfg.n_layers, cfg.n_kv_heads, bt, cfg.head_dim]
    row = _arena_row(cfg, 5, length)
    t = BlockPool(9, bt, block_bytes=donor.block_bytes, scratch=True).reserve(length)
    t.length = length
    donor.scatter_row(row, t)
    frames = b"".join(kvwire.encode_entry(
        dict(spec, length=length, n_blocks=3),
        (donor.export_block_payload(t, j) for j in range(3))))
    header, payloads = kvwire.decode_stream([frames[:100], frames[100:]], max_blocks=3)
    kvwire.check_spec(header, receiver.wire_spec())
    pool = BlockPool(9, bt, block_bytes=receiver.block_bytes, scratch=True)
    pool.reserve(bt)  # the receiver's block ids differ from the donor's
    t2 = pool.reserve(length)
    t2.length = length
    assert t2.blocks != t.blocks
    for j, payload in enumerate(payloads):
        assert receiver.ingest_block_payload(t2, j, payload) == len(payload)
    back = receiver.gather_row(t2, length)
    for f in ("k", "v"):
        _same_bits(back[f][:, :, :, :length], row[f][:, :, :, :length])
    old_order = dict(spec, block_shape=[cfg.n_layers, bt, cfg.n_kv_heads, cfg.head_dim])
    with pytest.raises(kvwire.VersionSkew, match="block_shape"):
        kvwire.check_spec(old_order, receiver.wire_spec())


def test_jax_arena_rejects_non_tiling_block_size():
    from gofr_tpu.models.llama import CONFIGS
    from gofr_tpu.tpu.kv_blocks import JaxKVArena

    with pytest.raises(ValueError, match="must divide"):
        JaxKVArena(CONFIGS["tiny"], n_blocks=4, block_tokens=48)


def test_jax_arena_sharded_over_tp_matches_unsharded():
    """JaxKVArena(mesh=tp-only): k/v shard their head axis across the
    tp devices, and scatter/gather through the sharded arena is
    bit-identical to the single-device arena — sharding is placement,
    never numerics."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.models.llama import CONFIGS
    from gofr_tpu.parallel.mesh import make_mesh, mesh_shape_for
    from gofr_tpu.tpu.kv_blocks import JaxKVArena

    cfg = CONFIGS["tiny"]  # 2 kv heads: tp=2 puts one head per device
    bt = 32
    mesh = make_mesh(mesh_shape_for(2, tp=2), devices=jax.devices()[:2])
    sharded = JaxKVArena(cfg, n_blocks=9, block_tokens=bt, mesh=mesh)
    plain = JaxKVArena(cfg, n_blocks=9, block_tokens=bt)
    assert len(sharded.k.sharding.device_set) == 2

    # the head axis is the arena's third, as it is the compute caches'
    assert sharded.k.sharding.spec[2] == "tp"
    length = 70
    row = _arena_row(cfg, 3, length)
    for arena in (sharded, plain):
        pool = BlockPool(9, bt, block_bytes=arena.block_bytes, scratch=True)
        t = pool.reserve(length)
        t.length = length
        assert arena.scatter_row(row, t) == 3 * arena.block_bytes
        back = arena.gather_row(t, length)
        for f in ("k", "v"):
            _same_bits(back[f][:, :, :, :length], row[f][:, :, :, :length])


def test_jax_arena_mesh_rejects_indivisible_heads():
    import jax

    from gofr_tpu.models.llama import CONFIGS
    from gofr_tpu.parallel.mesh import make_mesh, mesh_shape_for
    from gofr_tpu.tpu.kv_blocks import JaxKVArena

    mesh = make_mesh(mesh_shape_for(4, tp=4), devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="n_kv_heads=2 not divisible by tp=4"):
        JaxKVArena(CONFIGS["tiny"], n_blocks=5, block_tokens=32, mesh=mesh)


# -- host arena shards (echo host-mesh mode) ----------------------------------

def test_host_arena_sharded_write_read_fidelity():
    """shards=2: every block's token span splits across two fake
    devices; writes landing across shard boundaries reassemble exactly,
    and COW copies preserve content — checked against the unsharded
    arena on identical traffic."""
    ids = (np.arange(37, dtype=np.int32) * 11) % 127 + 1
    for shards in (1, 2, 4):
        arena = HostTokenArena(16, 8, shards=shards)
        pool = BlockPool(16, 8, arena=arena)
        t = pool.reserve(ids.size)
        t.length = ids.size
        # two writes split mid-shard: offsets 0..20 then 20..37
        arena.write(t, 0, ids[:20])
        arena.write(t, 20, ids[20:])
        np.testing.assert_array_equal(arena.read(t), ids)
        if shards > 1:
            assert sum(arena.shard_writes) > 0
    # COW across shards: partial copy keeps the donor's prefix
    arena = HostTokenArena(16, 8, shards=2)
    pool = BlockPool(16, 8, arena=arena)
    t = pool.reserve(8)
    t.length = 6
    arena.write(t, 0, ids[:6])
    dst = pool.alloc(1)[0]
    arena.copy_partial(dst, t.blocks[0], 6)
    t2 = BlockTable([dst], 6)
    np.testing.assert_array_equal(arena.read(t2), ids[:6])


def test_host_arena_shard_divisibility_enforced():
    with pytest.raises(ValueError, match="tp=3 does not divide"):
        HostTokenArena(8, 8, shards=3)


# -- cross-replica transfer pins (fleet KV handoff) ---------------------------

def test_transfer_pin_release_balances_refcounts():
    """The export path pins an entry's blocks for the wire's lifetime;
    a normal close releases them and the pool balances back to its
    pre-pull state."""
    from gofr_tpu.tpu.kv_blocks import TransferPin

    pool, _ = _pool()
    blocks = pool.alloc(3)
    before = pool.stats()
    pin = TransferPin(pool, blocks, ttl_s=60.0)
    assert not pin.released and not pin.expired
    pin.release()
    assert pin.released
    assert pool.stats() == before
    pool.release_blocks(blocks)
    assert pool.stats()["free"] == 16  # nothing leaked overall


def test_transfer_pin_ttl_guard_covers_a_dead_serving_thread():
    """The refcount-leak regression: a pin whose owner dies mid-send
    (release never called) must NOT leak — the named bounded-lifetime
    timer releases it, and the blocks become evictable again."""
    import time

    from gofr_tpu.tpu.kv_blocks import TransferPin

    pool, _ = _pool()
    blocks = pool.alloc(2)
    before = pool.stats()
    pin = TransferPin(pool, blocks, ttl_s=0.1)
    # the serving thread "dies" here: nobody calls release()
    deadline = time.monotonic() + 5.0
    while not pin.released and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pin.expired and pin.released
    assert pool.stats() == before
    pool.release_blocks(blocks)  # the original refs are still exact


def test_transfer_pin_release_is_idempotent_vs_the_timer():
    """Late releaser after the TTL fired (or double release): a no-op,
    never a double-free."""
    import time

    from gofr_tpu.tpu.kv_blocks import TransferPin

    pool, _ = _pool()
    blocks = pool.alloc(1)
    pin = TransferPin(pool, blocks, ttl_s=0.05)
    deadline = time.monotonic() + 5.0
    while not pin.released and time.monotonic() < deadline:
        time.sleep(0.01)
    pin.release()  # the owner wakes up late
    pin.release()  # and is confused
    st = pool.stats()
    assert st["active"] == 1  # only the caller's own alloc refs remain
    pool.release_blocks(blocks)
    assert pool.stats()["free"] == 16


def test_transfer_pin_keeps_cached_entry_alive_through_eviction():
    """The advertise→pull race the pin exists for: the entry is evicted
    WHILE pinned — its blocks must survive until the pin drops, then
    free."""
    from gofr_tpu.tpu.kv_blocks import TransferPin

    arena = HostTokenArena(8, 4)
    pool = BlockPool(8, 4, arena=arena, cache_entries=4)
    ids = np.arange(1, 9, dtype=np.int32)
    t = pool.reserve(8)
    t.length = 8
    arena.write(t, 0, ids)
    pool.cache_put(ids.tobytes(), t, {"length": 8})
    entry = pool.cache_lookup(ids.tobytes())
    pin = TransferPin(pool, entry.table.blocks, ttl_s=60.0)
    pool.cache_clear()  # eviction mid-transfer
    # the wire can still read the pinned blocks' content
    np.testing.assert_array_equal(
        arena.read(BlockTable(list(pin.blocks), 8)), ids
    )
    pin.release()
    assert pool.stats()["free"] == 8  # eviction completed once unpinned
