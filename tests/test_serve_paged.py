"""Block-paged KV serving tests (PR 3).

Pinned invariants:
  1. greedy continuous batching under block-paged KV is token-identical to
     the static oracle for the dense and MLA families, across block sizes
     {chunk, 2*chunk}, with churn (fewer slots than requests -> finished
     requests recycle their blocks for later admits);
  2. compile counters are exact ints (no nulls): exactly one trace per
     (step kind, horizon bucket actually seen), bounded by the power-of-two
     bucket grid, prefill=0 — regardless of the prompt-length mix;
  3. block-table bookkeeping: on-demand growth, whole-request reservation
     admission (a request waits for *blocks*, not just a slot), FIFO
     recycling, and full drain back to an empty arena;
  4. recycled-block guard: a reset engine replays bit-identically after a
     sampled (non-greedy) run — stale arena contents are unreachable through
     the causal mask + exactly-zero GN numerators, no zeroing needed;
  5. the paged GN attention kernel preserves the paper's guarantee: Sigma p
     = 1 to one rounding through an arbitrary block layout, and matches the
     contiguous gn_attention reference on an identity table — decode AND
     chunked-query forms, across block sizes {chunk, 2*chunk};
  6. the gather-free streamed read (serving default) is greedy
     token-identical to the gathered full-stream oracle for dense and MLA,
     and per-tick attended width under horizon bucketing stays below the
     full max_bt stream.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, reduce_config
from repro.data.synthetic import DataConfig, batch_at
from repro.kernels.gn_attention.ref import gn_attention_ref
from repro.kernels.gn_paged_attention.ops import (
    gn_paged_attention,
    gn_paged_attention_chunk,
)
from repro.kernels.gn_paged_attention.ref import (
    gn_paged_attention_chunk_ref,
    gn_paged_attention_ref,
)
from repro.models import attention as attention_mod
from repro.models.transformer import make_model
from repro.serve.engine import ContinuousEngine, ServeConfig, static_reference
from repro.serve.kv_cache import BlockPagedKVPool
from repro.serve.scheduler import Request
from repro.serve.workload import required_max_seq

from _serve_helpers import assert_exact_compile_counters

CHUNK = 4


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


def _mixed_requests(cfg, max_new=4):
    # >= 4 distinct prompt lengths, none aligned to the chunk grid, more
    # requests than slots -> finished requests recycle blocks mid-run
    lens = [5, 9, 14, 22, 7]
    return [
        Request(id=i, tokens=_prompt(cfg, L, seed=300 + i), max_new_tokens=max_new,
                arrival_step=i)
        for i, L in enumerate(lens)
    ]


# ----------------------------------------- greedy identity under paging ----
@pytest.mark.parametrize("block_size", [CHUNK, 2 * CHUNK])
@pytest.mark.parametrize("family", ["dense", "mla"])
def test_paged_identity_vs_static_oracle(dense, mla, family, block_size):
    cfg, model, params = dense if family == "dense" else mla
    scfg = ServeConfig()
    reqs = _mixed_requests(cfg)
    engine = ContinuousEngine(model, params, num_slots=2,
                              max_seq=required_max_seq(reqs), cfg=scfg,
                              chunk=CHUNK, block_size=block_size)
    assert engine.paged and isinstance(engine.pool, BlockPagedKVPool)
    comps = engine.run(reqs)
    ref = static_reference(model, params, reqs, scfg)
    assert len(comps) == len(reqs)
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id]), f"req {c.request_id}"
    m = engine.metrics()
    # explicit trace counters: exact ints, never None — one per (step kind,
    # horizon bucket) under horizon bucketing
    assert_exact_compile_counters(m)
    assert m["read_path"] == "streamed"
    # the workload drained: every block is back on the free list
    assert engine.pool.blocks_in_use == 0
    assert engine.pool.num_free == engine.pool.num_slots
    assert m["peak_blocks_in_use"] > 0


# ------------------------------------------------ block-table bookkeeping ---
def test_pool_reserve_ensure_recycle(dense):
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=3, max_seq=16, block_size=4,
                            num_blocks=6)
    s0 = pool.allocate(reserve_tokens=12)  # 3 blocks reserved
    s1 = pool.allocate(reserve_tokens=12)  # 3 more: arena fully reserved
    assert pool.blocks_reserved == 6
    assert not pool.can_reserve(1)  # free slot exists, but no block headroom
    with pytest.raises(RuntimeError):
        pool.allocate(reserve_tokens=4)

    pool.ensure(s0, 5)  # positions [0,5) -> 2 blocks materialize
    assert pool.tables[s0, 0] == 0 and pool.tables[s0, 1] == 1
    assert pool.blocks_in_use == 2 and pool.peak_blocks_in_use == 2
    pool.ensure(s1, 12)
    assert list(pool.tables[s1, :3]) == [2, 3, 4]

    pool.free(s0)  # blocks 0,1 recycle in allocation order
    assert pool.blocks_in_use == 3
    assert pool.can_reserve(8)
    s2 = pool.allocate(reserve_tokens=8)
    pool.ensure(s2, 8)
    # FIFO recycling: the freed blocks (then the never-used tail) are reused
    assert list(pool.tables[s2, :2]) == [5, 0]
    with pytest.raises(ValueError):
        pool.ensure(s2, 17)  # beyond max_seq
    pool.free(s1)
    pool.free(s2)
    assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0
    assert pool.num_free == 3


def test_admission_waits_for_blocks_not_just_slots(dense):
    cfg, model, params = dense
    scfg = ServeConfig()
    reqs = [
        Request(id=i, tokens=_prompt(cfg, 8, seed=330 + i), max_new_tokens=4)
        for i in range(2)
    ]
    # footprint 12 tokens = 3 blocks each; a 3-block arena forces strictly
    # serial service even though TWO slots are free
    engine = ContinuousEngine(model, params, num_slots=2, max_seq=12,
                              cfg=scfg, chunk=CHUNK, block_size=CHUNK,
                              num_blocks=3)
    comps = engine.run(reqs)
    ref = static_reference(model, params, reqs, scfg)
    assert len(comps) == 2
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id])
    first, second = sorted(comps, key=lambda c: c.request_id)
    # req 1 could only be admitted after req 0 finished and recycled blocks
    assert second.admit_step >= first.finish_step
    assert engine.pool.peak_blocks_in_use <= 3


def test_unservable_footprint_raises_at_admission(dense):
    # a request needing more blocks than the whole arena must fail loudly at
    # admission (like the max_seq check), not spin idle until the drain
    # budget explodes with a generic error
    cfg, model, params = dense
    engine = ContinuousEngine(model, params, num_slots=2, max_seq=32,
                              chunk=CHUNK, block_size=CHUNK, num_blocks=4)
    req = Request(id=0, tokens=_prompt(cfg, 20, seed=340), max_new_tokens=8)
    with pytest.raises(ValueError, match="unservable"):
        engine.run([req])


def test_engine_rejects_paging_knobs_for_unpaged_families():
    cfg = reduce_config(get_config("xlstm-350m"))  # ssm: O(1) carries
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ContinuousEngine(model, params, num_slots=1, max_seq=16, block_size=4)
    assert not model.supports_paging
    with pytest.raises(ValueError):
        model.paged_cache_specs(1, 4, 4, 16)
    engine = ContinuousEngine(model, params, num_slots=1, max_seq=16)
    assert not engine.paged  # falls back to the slot-slab pool


# ------------------------------------------- recycled-block stale guard ----
def test_sampled_run_then_reset_replays_bit_identically(dense):
    # a sampled run scatters non-greedy KV through the arena; reset() does
    # NOT zero it (guard, not scrub) — replay must still be bit-identical
    cfg, model, params = dense
    reqs = [
        Request(id=i, tokens=_prompt(cfg, L, seed=350 + i), max_new_tokens=4,
                arrival_step=i)
        for i, L in enumerate([6, 11, 9])
    ]
    engine = ContinuousEngine(model, params, num_slots=2,
                              max_seq=required_max_seq(reqs),
                              cfg=ServeConfig(temperature=0.8, seed=3),
                              chunk=CHUNK)
    assert engine.paged
    first = {c.request_id: c.tokens for c in engine.run(reqs)}
    engine.reset()
    second = {c.request_id: c.tokens for c in engine.run(reqs)}
    assert first.keys() == second.keys()
    for rid in first:
        assert np.array_equal(first[rid], second[rid])


# --------------------------------------------- paged GN kernel guarantees ---
def _paged_kernel_inputs(seed=0):
    rng = np.random.default_rng(seed)
    n, h, kv, d = 3, 4, 2, 16
    nb, bs, max_bt = 10, 4, 5
    q = jnp.asarray(rng.normal(size=(n, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(nb, bs, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(nb, bs, kv, d)), jnp.float32)
    # scrambled, non-contiguous block layout
    tables = jnp.asarray([[7, 2, 9, 0, 0], [1, 5, 0, 0, 0], [3, 8, 6, 4, 0]],
                         jnp.int32)
    lengths = jnp.asarray([11, 6, 17], jnp.int32)
    return q, k, v, tables, lengths, (h // kv, max_bt)


def test_paged_kernel_matches_gathered_ref():
    q, k, v, tables, lengths, (group, _) = _paged_kernel_inputs()
    got = gn_paged_attention(q, k, v, tables, lengths, interpret=True)
    kb = jnp.repeat(k, group, axis=2)
    vb = jnp.repeat(v, group, axis=2)
    want = gn_paged_attention_ref(q, kb, vb, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_paged_kernel_matches_contiguous_gn_attention_ref():
    # identity table -> the paged read must reproduce the contiguous-slab
    # gn_attention reference on each sequence's valid prefix
    q, k, v, _, lengths, (group, max_bt) = _paged_kernel_inputs()
    n, h, d = q.shape
    tables = jnp.broadcast_to(jnp.arange(max_bt, dtype=jnp.int32), (n, max_bt))
    got = gn_paged_attention(q, k, v, tables, lengths, interpret=True)
    kb = jnp.repeat(k, group, axis=2)[tables].reshape(n, -1, h, d).transpose(0, 2, 1, 3)
    vb = jnp.repeat(v, group, axis=2)[tables].reshape(n, -1, h, d).transpose(0, 2, 1, 3)
    for i in range(n):
        t = int(lengths[i])
        want = gn_attention_ref(q[i][None, :, None], kb[i : i + 1, :, :t],
                                vb[i : i + 1, :, :t])
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want[0, :, 0]), atol=5e-5
        )


def test_paged_kernel_sum_to_one_through_block_table():
    # v = 1 turns the output into Sigma p * 1: guaranteed normalization must
    # survive the block table exactly as it survives chunked streaming
    q, k, v, tables, lengths, _ = _paged_kernel_inputs(seed=5)
    out = gn_paged_attention(q, k, jnp.ones_like(v), tables, lengths,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)


def test_paged_softmax_rows_sum_to_one():
    # the jnp serving path's probabilities themselves: gathered scores with a
    # masked tail (stale/foreign block guard) still sum to exactly ~1
    from repro.kernels.gn_paged_attention.ref import gn_paged_softmax_ref

    rng = np.random.default_rng(11)
    s = jnp.asarray(rng.normal(size=(5, 37)) * 6, jnp.float32)
    masked = s.at[:, 29:].set(-1e30)  # tail beyond the causal prefix
    p = gn_paged_softmax_ref(masked)
    np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, atol=2e-6)
    assert float(np.asarray(p)[:, 29:].max()) == 0.0  # guard: exact zeros


# --------------------------------------- streamed vs gathered read paths ----
@pytest.mark.parametrize("family", ["dense", "mla"])
def test_streamed_read_token_identical_to_gathered_oracle(dense, mla, family):
    """The gather-free streamed read (the serving default) must produce the
    same greedy tokens as the full-stream gathered oracle — which PR 3
    proved slab-equal — for dense AND MLA.  Fresh engines per path: the
    forced read is baked in at trace time."""
    cfg, model, params = dense if family == "dense" else mla
    scfg = ServeConfig()
    reqs = _mixed_requests(cfg)
    results = {}
    for path in ("gathered", "streamed"):
        attention_mod.FORCE_PAGED_READ = path
        try:
            engine = ContinuousEngine(model, params, num_slots=2,
                                      max_seq=required_max_seq(reqs),
                                      cfg=scfg, chunk=CHUNK)
            assert engine.model.paged_read_path == path
            results[path] = {c.request_id: c.tokens for c in engine.run(reqs)}
        finally:
            attention_mod.FORCE_PAGED_READ = None
    assert results["gathered"].keys() == results["streamed"].keys()
    for rid in results["gathered"]:
        assert np.array_equal(results["streamed"][rid],
                              results["gathered"][rid]), f"req {rid}"


def test_slab_engine_reports_slab_read_path(dense):
    _, model, params = dense
    engine = ContinuousEngine(model, params, num_slots=1, max_seq=16,
                              paged=False)
    assert engine.metrics()["read_path"] == "slab"


# ------------------------------------------------- horizon bucketing --------
def test_horizon_bucketing_compile_bounds_and_attended_width(dense):
    """Compile counters under horizon bucketing: exactly one trace per
    (step kind, bucket actually seen), bucket grid = powers of two capped
    at max_blocks_per_slot, and the mean attended width per tick must sit
    strictly below the full max_bt stream on a mixed-length workload (the
    whole point: per-tick work scales with live context)."""
    cfg, model, params = dense
    scfg = ServeConfig()
    reqs = _mixed_requests(cfg)
    engine = ContinuousEngine(model, params, num_slots=2,
                              max_seq=required_max_seq(reqs), cfg=scfg,
                              chunk=CHUNK, block_size=CHUNK)
    grid = engine.horizon_bucket_grid
    max_bt = engine.pool.max_blocks_per_slot
    # powers of two, strictly increasing, capped at max_bt
    assert grid[-1] == max_bt
    assert all(b < b2 for b, b2 in zip(grid, grid[1:]))
    assert all(b & (b - 1) == 0 for b in grid[:-1])
    comps = engine.run(reqs)
    assert len(comps) == len(reqs)
    m = engine.metrics()
    assert_exact_compile_counters(m)
    assert m["horizon_buckets"]  # at least one bucket was traced
    # every tick's horizon fits its bucket, and never exceeds the grid cap
    for horizon, bucket in engine.horizon_log:
        assert horizon <= bucket <= max_bt
        assert bucket in grid
    # live-context scaling: the mixed workload spends most ticks well below
    # the full stream, so the mean attended width must be < max_bt * bs
    full = max_bt * engine.pool.block_size
    assert 0 < m["mean_attended_tokens_per_tick"] < full


# ---------------------------------------- chunked-query paged GN kernel -----
def _chunk_kernel_inputs(seed=0, bs=4):
    rng = np.random.default_rng(seed)
    n, c, h, kv, d = 3, CHUNK, 4, 2, 16
    nb = 12
    max_bt = -(-32 // bs)  # cover 32 tokens of context
    q = jnp.asarray(rng.normal(size=(n, c, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(nb, bs, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(nb, bs, kv, d)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, nb, size=(n, max_bt)), jnp.int32)
    starts = jnp.asarray([9, 0, 17], jnp.int32)
    n_valid = jnp.asarray([c, c - 1, c], jnp.int32)
    return q, k, v, tables, starts, n_valid, h // kv


@pytest.mark.parametrize("block_size", [CHUNK, 2 * CHUNK])
def test_chunked_query_kernel_matches_gathered_chunk_ref(block_size):
    q, k, v, tables, starts, n_valid, group = _chunk_kernel_inputs(bs=block_size)
    got = gn_paged_attention_chunk(q, k, v, tables, starts, n_valid,
                                   interpret=True)
    kb = jnp.repeat(k, group, axis=2)
    vb = jnp.repeat(v, group, axis=2)
    want = gn_paged_attention_chunk_ref(q, kb, vb, tables, starts, n_valid)
    # online (single-pass) accumulation vs the one-pass reference: equal up
    # to LUT-entry rounding of the correction factors, not bitwise
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("block_size", [CHUNK, 2 * CHUNK])
def test_chunked_query_kernel_matches_contiguous_gn_attention_ref(block_size):
    # identity table -> each sequence's chunk must reproduce the contiguous
    # gn_attention reference over its causal prefix (kv span = start + C)
    q, k, v, tables, starts, n_valid, group = _chunk_kernel_inputs(bs=block_size)
    n, c, h, d = q.shape
    max_bt = tables.shape[1]
    tables = jnp.broadcast_to(jnp.arange(max_bt, dtype=jnp.int32), (n, max_bt))
    got = gn_paged_attention_chunk(q, k, v, tables, starts,
                                   jnp.full_like(starts, c), interpret=True)
    kb = jnp.repeat(k, group, axis=2).reshape(-1, h, d).transpose(1, 0, 2)
    vb = jnp.repeat(v, group, axis=2).reshape(-1, h, d).transpose(1, 0, 2)
    for i in range(n):
        t = int(starts[i]) + c
        want = gn_attention_ref(
            q[i].transpose(1, 0, 2)[None], kb[None, :, :t], vb[None, :, :t],
            causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want[0]).transpose(1, 0, 2),
            atol=2e-4,
        )


@pytest.mark.parametrize("block_size", [CHUNK, 2 * CHUNK])
def test_chunked_query_kernel_sum_to_one_through_block_table(block_size):
    # v = 1 turns the output into Sigma p * 1: guaranteed normalization must
    # survive chunked queries and any block layout to one rounding
    q, k, v, tables, starts, n_valid, _ = _chunk_kernel_inputs(seed=5,
                                                               bs=block_size)
    out = gn_paged_attention_chunk(q, k, jnp.ones_like(v), tables, starts,
                                   n_valid, interpret=True)
    c = q.shape[1]
    lane_ok = np.arange(c)[None, :] < np.asarray(n_valid)[:, None]
    np.testing.assert_allclose(np.asarray(out)[lane_ok], 1.0, atol=1e-5)


def test_pallas_read_off_tpu_is_not_interpreted_unasked(dense):
    """No hidden fallback: off a TPU, the 'pallas' read of a config that did
    not ask for the interpreter fails to lower instead of interpreting."""
    if jax.default_backend() == "tpu":
        pytest.skip("the kernel compiles on a TPU")
    cfg = dataclasses.replace(dense[0], pallas_interpret=False)
    nb, bs, n = 8, CHUNK, 2
    p = {name: jnp.zeros(shape, jnp.float32) for name, shape in (
        ("wq", (cfg.d_model, cfg.q_features)),
        ("wk", (cfg.d_model, cfg.kv_features)),
        ("wv", (cfg.d_model, cfg.kv_features)),
        ("wo", (cfg.q_features, cfg.d_model)))}
    arena = jnp.zeros((nb, bs, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    x = jnp.zeros((n, CHUNK, cfg.d_model), jnp.float32)
    tables = jnp.arange(nb, dtype=jnp.int32).reshape(n, nb // n)
    per_seq = jnp.full((n,), CHUNK, jnp.int32)
    attention_mod.FORCE_PAGED_READ = "pallas"
    try:
        with pytest.raises(ValueError, match="interpret"):
            attention_mod.attn_paged_chunk(cfg, p, arena, arena, x, per_seq,
                                           per_seq, tables)
    finally:
        attention_mod.FORCE_PAGED_READ = None


def test_paged_chunk_pallas_read_matches_gathered(dense):
    """Wiring test for the 'pallas' read path: a single attn_paged_chunk
    call (chunked queries, interpret mode on CPU) must agree with the
    gathered read through the same arenas to kernel tolerance."""
    cfg, _, _ = dense
    rng = np.random.default_rng(7)
    n, c_len, d_model = 2, CHUNK, cfg.d_model
    nb, bs = 8, CHUNK
    p = {
        "wq": jnp.asarray(rng.normal(size=(d_model, cfg.q_features)) * 0.05, jnp.float32),
        "wk": jnp.asarray(rng.normal(size=(d_model, cfg.kv_features)) * 0.05, jnp.float32),
        "wv": jnp.asarray(rng.normal(size=(d_model, cfg.kv_features)) * 0.05, jnp.float32),
        "wo": jnp.asarray(rng.normal(size=(cfg.q_features, d_model)) * 0.05, jnp.float32),
    }
    ak = jnp.asarray(rng.normal(size=(nb, bs, cfg.n_kv_heads, cfg.head_dim)), jnp.float32)
    av = jnp.asarray(rng.normal(size=(nb, bs, cfg.n_kv_heads, cfg.head_dim)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, c_len, d_model)) * 0.1, jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[: 2 * 4].reshape(n, 4), jnp.int32)
    positions = jnp.asarray([6, 1], jnp.int32)
    n_valid = jnp.asarray([c_len, c_len], jnp.int32)
    outs = {}
    for path in ("gathered", "pallas"):
        attention_mod.FORCE_PAGED_READ = path
        try:
            out, _ = attention_mod.attn_paged_chunk(
                cfg, p, ak, av, x, positions, n_valid, tables)
        finally:
            attention_mod.FORCE_PAGED_READ = None
        outs[path] = np.asarray(out)
    np.testing.assert_allclose(outs["pallas"], outs["gathered"], atol=5e-4)
