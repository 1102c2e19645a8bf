"""Prefix-sharing serving tests (PR 6).

Pinned invariants:
  1. greedy continuous batching with the radix prefix cache ON is
     token-identical to the unshared static oracle for dense and MLA,
     across block sizes {chunk, 2*chunk} and all three paged read paths
     (pallas / streamed / gathered) — sharing prompt-position KV computed
     by the same jitted prefill at the same positions is exact by
     construction, and the GN guarantee (masked scores -> exactly-zero
     numerators with sum p = 1) makes a shared block readable through any
     slot's table;
  2. copy-on-write: a partially-matched shared block is forked into a
     private block at attach time, bitwise-identical to its source across
     every arena leaf, and ``write_barrier`` never observes a live slot
     about to write a refcount>1 block;
  3. refcounted recycling: a block returns to its device's FIFO free list
     only at refcount zero (owner + sharers + cache index each hold one);
     under block pressure the pool reclaims LRU cache-only chains
     leaf-first, so surviving chains stay matchable;
  4. admission charges only the *unshared* tail: a request sharing k
     cached blocks reserves blocks_for(footprint) - k, so it can be
     admitted into headroom that could never fit its full footprint —
     while the donor is still live;
  5. compile counters stay exact: one trace per (step kind, horizon
     bucket), prefill=0 — attach/fork/skip-prefill must not retrace;
  6. ``ensure`` growth and COW forks preserve the rest of the arena and
     all live block tables bit-identically;
  7. a reset engine replays the workload with identical tokens AND an
     identical hit/fork/evict sequence (the LRU clock is an op counter,
     never wall time).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, reduce_config
from repro.data.synthetic import DataConfig, batch_at
from repro.models import attention as attention_mod
from repro.models.transformer import make_model
from repro.serve.engine import ContinuousEngine, ServeConfig, static_reference
from repro.serve.kv_cache import BlockPagedKVPool
from repro.serve.prefix_cache import PrefixCache
from repro.serve.scheduler import Request
from repro.serve.workload import required_max_seq, shared_prefix_requests

from _serve_helpers import assert_exact_compile_counters

CHUNK = 4
TWO_DEV = jax.device_count() >= 2
requires_mesh = pytest.mark.skipif(
    not TWO_DEV,
    reason="needs >= 2 devices "
    "(export XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)


@pytest.fixture(scope="module")
def dense():
    # pallas_interpret: the forced 'pallas' read runs in the interpreter here
    cfg = dataclasses.replace(reduce_config(get_config("internlm2-1.8b")),
                              pallas_interpret=True)
    model = make_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mla():
    cfg = reduce_config(get_config("minicpm3-4b"))
    model = make_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _prompt(cfg, length, seed):
    data = DataConfig(vocab=cfg.vocab, seq_len=length, global_batch=1, seed=seed)
    return np.asarray(batch_at(data, 0)["tokens"][0], np.int32)


def _shared_reqs(cfg, **kw):
    # system+persona = 18 tokens: block-misaligned for both block sizes
    # {4, 8}, so later arrivals fork the donor's partial block (COW path)
    kw.setdefault("n_users", 6)
    kw.setdefault("n_personas", 2)
    kw.setdefault("system_len", 12)
    kw.setdefault("persona_len", 6)
    kw.setdefault("user_len", 5)
    kw.setdefault("max_new_tokens", 4)
    # prompts pad to 24 -> 6 prefill ticks at chunk 4; stagger past that so
    # every later arrival sees the donor's phase-flip insert already indexed
    kw.setdefault("stagger", 7)
    return shared_prefix_requests(cfg, **kw)


def _run_prefix_engine(model, params, reqs, block_size, roomy=True, **kw):
    """Prefix-cache engine over ``reqs``.  ``roomy`` doubles the
    slab-equivalent arena so cached chains survive next to full
    reservations (the default arena is exactly num_slots full footprints —
    zero headroom, constant eviction; that regime gets its own test)."""
    num_slots = kw.pop("num_slots", 2)
    max_seq = required_max_seq(reqs)
    if roomy and "num_blocks" not in kw:
        kw["num_blocks"] = 2 * num_slots * -(-max_seq // block_size)
    engine = ContinuousEngine(
        model, params, num_slots=num_slots, max_seq=max_seq,
        cfg=ServeConfig(), chunk=CHUNK, block_size=block_size,
        prefix_cache=True, **kw,
    )
    comps = engine.run(reqs)
    return engine, comps


# ----------------------------------------- greedy identity, cache ON -------
@pytest.mark.parametrize("block_size", [CHUNK, 2 * CHUNK])
@pytest.mark.parametrize("family", ["dense", "mla"])
def test_prefix_identity_vs_unshared_oracle(dense, mla, family, block_size):
    cfg, model, params = dense if family == "dense" else mla
    reqs = _shared_reqs(cfg)
    engine, comps = _run_prefix_engine(model, params, reqs, block_size)
    ref = static_reference(model, params, reqs, ServeConfig())
    assert len(comps) == len(reqs)
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id]), f"req {c.request_id}"
    m = engine.metrics()
    assert m["prefix_cache"] is True
    # every request after the first shares at least the system prompt
    assert m["prefix_hit_requests"] == len(reqs) - 1
    assert m["prefix_hit_rate"] > 0
    # 18 % block_size != 0 for both sizes -> the persona boundary sits
    # mid-block and COW forks must have fired
    assert m["prefix_forks"] > 0
    assert_exact_compile_counters(m)
    # drained: slots are free, but the cache retains its indexed chains
    assert engine.pool.num_free == engine.pool.num_slots
    assert engine.pool.blocks_in_use == engine.pool.cached_blocks > 0
    held = np.flatnonzero(np.asarray(engine.pool.refcounts))
    assert (np.asarray(engine.pool.refcounts)[held] == 1).all()


@pytest.mark.parametrize("path", ["streamed", "gathered", "pallas"])
def test_prefix_identity_across_read_paths(dense, path):
    """Sharing must be exact through every paged read: the Pallas kernel,
    the gather-free streamed tiles, and the gathered full-stream oracle all
    walk the same block tables the prefix cache populated."""
    cfg, model, params = dense
    reqs = _shared_reqs(cfg)
    ref = static_reference(model, params, reqs, ServeConfig())
    attention_mod.FORCE_PAGED_READ = path
    try:
        engine, comps = _run_prefix_engine(model, params, reqs, CHUNK)
        assert engine.metrics()["read_path"] == path
    finally:
        attention_mod.FORCE_PAGED_READ = None
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id]), f"req {c.request_id}"
    assert engine.metrics()["prefix_hit_requests"] == len(reqs) - 1


def test_tight_arena_identity_under_eviction_churn(dense):
    """Regression for the subtree-cut eviction fallback: the slab-equivalent
    arena is exactly two full-footprint reservations, so every cached chain
    must be evicted to readmit — and a live slot's phase-flip insert pins
    descendants under refcount-1 ancestors, which leaf-first eviction alone
    can never reclaim (admission used to promise supply that ``ensure``
    then couldn't get, dying in ``_pop_block``)."""
    cfg, model, params = dense
    reqs = _shared_reqs(cfg, stagger=3)  # the original failing arrival mix
    engine, comps = _run_prefix_engine(model, params, reqs, CHUNK, roomy=False)
    ref = static_reference(model, params, reqs, ServeConfig())
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id]), f"req {c.request_id}"
    m = engine.metrics()
    # slab-equivalent arena == num_slots full footprints: readmission runs
    # the cache out of headroom, so the eviction path really fired
    assert m["num_blocks"] == 2 * engine.pool.max_blocks_per_slot
    assert m["prefix_evictions"] > 0
    assert_exact_compile_counters(m)


# ------------------------------------------------------ COW fork at attach --
def test_cow_fork_on_divergent_tail(dense):
    """Two requests share a block-misaligned 13-token prefix and then
    diverge: the second must fork the donor's partial block (never write
    it), produce oracle-identical tokens, and leave the donor's cached
    chain readable for a third, fully-matching request."""
    cfg, model, params = dense
    base = _prompt(cfg, 16, seed=900)
    div = base.copy()
    div[13:] = (div[13:] + 1) % cfg.vocab  # diverge mid-block (13 % 4 != 0)
    reqs = [
        Request(id=0, tokens=base, max_new_tokens=4, arrival_step=0),
        Request(id=1, tokens=div, max_new_tokens=4, arrival_step=20),
        Request(id=2, tokens=base.copy(), max_new_tokens=4, arrival_step=40),
    ]
    engine, comps = _run_prefix_engine(model, params, reqs, CHUNK)
    ref = static_reference(model, params, reqs, ServeConfig())
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id]), f"req {c.request_id}"
    hits = engine.request_prefix_hits
    assert 0 not in hits  # the donor paid the full prefill
    assert hits[1]["tokens"] == 13 and hits[1]["forked"] is True
    # req 2 matches the full cached prompt, capped at prompt_len - 1 = 15:
    # 3 full blocks + a forked tail (the donor's finish indexed tokens 12:16)
    assert hits[2]["tokens"] == 15 and hits[2]["forked"] is True
    assert engine.metrics()["prefix_forks"] == 2


def test_fork_copies_block_bitwise_and_preserves_arena(dense):
    """Pool-level invariant 6: ``ensure`` growth and an attach-time COW fork
    touch ONLY the destination block — every other arena block and every
    live block table is bit-identical before/after — and the forked block
    is a bitwise copy of its source."""
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=3, max_seq=32, block_size=4,
                            num_blocks=12)
    pool.attach_prefix_cache(PrefixCache(4))
    # deterministic, per-position-distinct arena contents
    pool.cache = dict(pool.cache)
    pool.cache["layers"] = jax.tree.map(
        lambda l: jnp.arange(l.size, dtype=jnp.float32).reshape(l.shape)
        .astype(l.dtype),
        pool.cache["layers"],
    )
    leaves0 = [np.asarray(l) for l in jax.tree.leaves(pool.cache["layers"])]

    s0 = pool.allocate(reserve_tokens=16)
    pool.ensure(s0, 16)
    chain0 = pool.chain_of(s0)
    table0 = pool.tables[s0].copy()
    # growth for another slot must not disturb s0's arena blocks or table
    s1 = pool.allocate(reserve_tokens=8)
    pool.ensure(s1, 8)
    for a, b in zip(leaves0, jax.tree.leaves(pool.cache["layers"])):
        assert np.array_equal(a, np.asarray(b))  # ensure() is host-side only
    assert pool.chain_of(s0) == chain0
    assert np.array_equal(pool.tables[s0], table0)

    # index a 14-token prompt (3 full blocks + 2-token tail), drop the owner
    tokens = _prompt(model.cfg, 14, seed=901)
    pool.prefix_cache.insert(tokens, chain0[:4], 0)
    pool.free(s0)
    pool.free(s1)
    hit = pool.prefix_cache.lookup(tokens)
    assert hit.shared_len == 14 and hit.tail_src == chain0[3]

    s2 = pool.allocate(reserve_tokens=16, prefix=hit)
    pool.attach_prefix(s2, hit)
    assert pool.prefix_forks == 1
    dst = pool.chain_of(s2)[3]
    assert dst != hit.tail_src
    for before, leaf in zip(leaves0, jax.tree.leaves(pool.cache["layers"])):
        after = np.asarray(leaf)
        # the forked block is a bitwise copy of its source...
        assert np.array_equal(after[:, dst], before[:, hit.tail_src])
        # ...and every other block is untouched
        mask = np.ones(after.shape[1], bool)
        mask[dst] = False
        assert np.array_equal(after[:, mask], before[:, mask])
    # the write barrier accepts the private fork and rejects shared blocks
    pool.write_barrier(s2, 14)  # next write -> block idx 3 (the fork): ok
    with pytest.raises(RuntimeError, match="COW violation"):
        pool.write_barrier(s2, 8)  # block idx 2 is shared (refcount 2)


# ------------------------------------------- refcounts, recycle, eviction --
def test_refcount_recycle_and_lru_eviction(dense):
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=3, max_seq=32, block_size=4,
                            num_blocks=8)
    cache = PrefixCache(4)
    pool.attach_prefix_cache(cache)
    tokens = _prompt(model.cfg, 16, seed=902)

    s0 = pool.allocate(reserve_tokens=16)
    pool.ensure(s0, 16)
    chain = pool.chain_of(s0)
    assert all(pool.refcounts[b] == 1 for b in chain)
    cache.insert(tokens, chain, 0)
    assert all(pool.refcounts[b] == 2 for b in chain)
    assert cache.cached_blocks() == 4

    # owner finishes: blocks stay resident (cache ref), none recycle
    pool.free(s0)
    assert all(pool.refcounts[b] == 1 for b in chain)
    assert pool.blocks_in_use == 4 and pool.free_blocks_on(0) == 4

    # a sharer attaches (+1), then finishes (-1): still cached, never freed
    hit = cache.lookup(tokens)
    assert hit.blocks == chain and hit.shared_len == 16 and hit.tail_src is None
    s1 = pool.allocate(reserve_tokens=20, prefix=hit)
    pool.attach_prefix(s1, hit)
    assert all(pool.refcounts[b] == 2 for b in chain)
    pool.ensure(s1, 20)  # pops exactly the 1 unshared block
    assert pool.chain_of(s1)[:4] == chain and len(pool.chain_of(s1)) == 5
    pool.free(s1)
    assert all(pool.refcounts[b] == 1 for b in chain)
    assert pool.blocks_in_use == 4

    # block pressure: a 24-token request needs 6 blocks, only 4 are free ->
    # _pop_block reclaims LRU cache-only blocks leaf-first (deepest chain
    # node first), and the surviving prefix stays matchable
    s2 = pool.allocate(reserve_tokens=24)
    pool.ensure(s2, 24)
    assert pool.prefix_evictions == 2 and cache.evictions == 2
    assert cache.cached_blocks() == 2
    surviving = cache.lookup(tokens, touch=False)
    assert surviving.shared_len == 8 and surviving.blocks == chain[:2]
    pool.free(s2)
    assert pool.blocks_in_use == 2  # only the surviving cached chain
    held = np.flatnonzero(np.asarray(pool.refcounts))
    assert sorted(held.tolist()) == sorted(chain[:2])


def test_admission_charges_only_unshared_tail(dense):
    """Invariant 4, while the donor is still LIVE (nothing evictable): a
    24-token footprint needs 6 blocks but only 2 are free — admission is
    possible only because 4 of them attach from the cache."""
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=3, max_seq=32, block_size=4,
                            num_blocks=6)
    cache = PrefixCache(4)
    pool.attach_prefix_cache(cache)
    tokens = _prompt(model.cfg, 16, seed=903)

    s0 = pool.allocate(reserve_tokens=16)
    pool.ensure(s0, 16)
    chain = pool.chain_of(s0)
    cache.insert(tokens, chain, 0)  # donor live: refcounts 2, evictable 0
    assert pool.free_blocks_on(0) == 2
    assert cache.evictable_count(0, pool.refcounts) == 0

    hit = cache.lookup(tokens)
    assert not pool.can_reserve(24, 0)              # full charge: 6 > 2
    assert pool.can_reserve(24, 0, prefix=hit)      # tail charge: 2 <= 2
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.allocate(reserve_tokens=24)
    s1 = pool.allocate(reserve_tokens=24, prefix=hit)
    assert int(pool._reserved[s1]) == 2             # blocks_for(24) - 4
    pool.attach_prefix(s1, hit)
    pool.ensure(s1, 24)
    assert int(pool._owned[s1]) == 2 and int(pool._shared[s1]) == 4
    assert pool.unfilled_on(0) == 0
    assert all(pool.refcounts[b] == 3 for b in chain)  # owner+sharer+cache
    pool.free(s0)
    pool.free(s1)
    assert pool.blocks_in_use == 4  # the cached chain survives the drain


def test_radix_cap_and_stampfree_hint(dense):
    """``lookup(cap=plen-1)`` always leaves >= 1 token to prefill (the
    sampled logits must come from the request's own final prompt position),
    and the scheduler's ``match_len`` hint never touches LRU stamps or
    hit/miss stats."""
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=2, max_seq=32, block_size=4,
                            num_blocks=8)
    cache = PrefixCache(4)
    pool.attach_prefix_cache(cache)
    tokens = _prompt(model.cfg, 16, seed=904)
    s0 = pool.allocate(reserve_tokens=16)
    pool.ensure(s0, 16)
    cache.insert(tokens, pool.chain_of(s0), 0)

    h0, m0, clock0 = cache.hits, cache.misses, cache._clock
    assert cache.match_len(tokens) == 16
    assert (cache.hits, cache.misses, cache._clock) == (h0, m0, clock0)

    hit = cache.lookup(tokens, cap=15)
    assert hit.shared_len == 15  # 3 full blocks + 3 tokens forked from #4
    assert hit.tail_src == pool.chain_of(s0)[3]
    assert cache.hits == h0 + 1 and cache._clock > clock0


# ----------------------------------------------------- replay determinism --
def test_reset_replays_identical_hits_and_tokens(dense):
    cfg, model, params = dense
    reqs = _shared_reqs(cfg)
    engine, comps = _run_prefix_engine(model, params, reqs, CHUNK)

    def signature(engine, comps):
        m = engine.metrics()
        return (
            {c.request_id: c.tokens.tolist() for c in comps},
            m["prefix_hit_tokens"], m["prefix_forks"], m["prefix_evictions"],
            m["prefix_inserts"], dict(engine.request_prefix_hits),
        )

    first = signature(engine, comps)
    engine.reset()
    assert engine.pool.blocks_in_use == 0  # reset clears the radix cache too
    assert engine.prefix.cached_blocks() == 0
    second = signature(engine, engine.run(reqs))
    assert first == second


# ------------------------------------------------------------ device mesh --
@requires_mesh
def test_sharded_prefix_identity_and_locality(dense):
    """2-device engine, prefix cache ON: oracle-identical tokens, exact
    compile counters, and every hit is device-local (a slot only attaches
    chains from its own device's radix tree)."""
    cfg, model, params = dense
    reqs = _shared_reqs(cfg, n_users=8)
    engine, comps = _run_prefix_engine(model, params, reqs, CHUNK,
                                       num_slots=4, devices=2)
    ref = static_reference(model, params, reqs, ServeConfig())
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id]), f"req {c.request_id}"
    m = engine.metrics()
    assert m["num_devices"] == 2
    assert m["prefix_hit_requests"] > 0
    assert_exact_compile_counters(m)
    assert engine.prefix.num_devices == 2
    bpd = engine.pool.blocks_per_device
    for rid, h in engine.request_prefix_hits.items():
        assert h["device"] in (0, 1), rid
    # each device's radix tree only indexes its own block range
    for d in range(2):
        for node in engine.prefix._iter_nodes(d):
            for b in ([node.block] if node.block is not None else []) + (
                [node.tail[2]] if node.tail is not None else []
            ):
                assert d * bpd <= b < (d + 1) * bpd
