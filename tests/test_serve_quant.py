"""Quantized int8 paged KV on the GN fixed-point substrate (PR 9).

Pinned invariants:
  1. guaranteed normalization survives quantization: Σp = 1 to one rounding
     for the paged GN-softmax read over int8-dequantized blocks — swept
     over block sizes {chunk, 2·chunk}, read paths {streamed, gathered,
     pallas-interpret} and the dense + MLA families (property-based via
     hypothesis / the fallback shim).  Quantization perturbs *scores*; the
     GN guarantee is score-independent (the same approximated numerators
     feed the one reciprocal, masked columns saturate to exact zeros);
  2. the paged-serving-read normalization error stays within the analytic
     bound ((t+1)·2^-23 — one reciprocal rounding + one f32 rounding per
     accumulated numerator), pinned through the `norm_error_study` helper;
  3. int8 composes bitwise with every pool subsystem: preempt-spill→restore
     and prefix COW-fork move arena *and* per-block scales bit-exactly,
     including under the 2-device sharded pool;
  4. serving identity/tolerance: an int8 engine runs the fused tick end to
     end (dense + MLA), greedy outputs tolerance-pinned against the fp
     engine (LCP fractions), exact compile counters (kv_dtype adds no trace
     keys), reset-replay bit-identical (recycled blocks re-freeze their
     scale at the new tenant's offset-0 write — no zeroing);
  5. the quantized pool halves+ KV HBM: `hbm_bytes` for int8 arenas + f32
     scales is well under the fp pool's at equal block counts.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # bare container: deterministic fixed-seed sweeps
    from _hypothesis_fallback import given, settings, st

from repro.configs.registry import get_config, reduce_config
from repro.data.synthetic import DataConfig, batch_at
from repro.kernels.gn_paged_attention.ops import gn_paged_attention_chunk
from repro.models import attention as attention_mod
from repro.models import mla as mla_mod
from repro.models.transformer import make_model
from repro.core import get_softmax
from repro.serve.engine import ContinuousEngine, ServeConfig
from repro.serve.kv_cache import BlockPagedKVPool
from repro.serve.scheduler import Request
from repro.serve.workload import required_max_seq

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
    cfg = reduce_config(get_config("internlm2-1.8b"))
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
    lens = [5, 9, 14, 22, 7]
    return [
        Request(id=i, tokens=_prompt(cfg, L, seed=300 + i), max_new_tokens=max_new,
                arrival_step=i)
        for i, L in enumerate(lens)
    ]


def _quantize_arena(arr):
    """Tight per-block int8 quantization of an (nb, bs, ...) fp arena."""
    nb = arr.shape[0]
    amax = np.abs(arr).reshape(nb, -1).max(axis=1)
    scale = np.maximum(amax, 1e-30) / 127.0
    bcast = scale.reshape((nb,) + (1,) * (arr.ndim - 1))
    q = np.clip(np.round(arr / bcast), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(scale, jnp.float32)


def _ones_arena(shape):
    """An int8 arena + scale that dequantizes to EXACTLY 1.0 everywhere
    (64 · 2^-6: both powers of two, no rounding)."""
    nb = shape[0]
    return (jnp.full(shape, 64, jnp.int8),
            jnp.full((nb,), 1.0 / 64.0, jnp.float32))


# given()-decorated tests can't take pytest fixtures (the fallback shim
# rewrites the signature), so the property tests build their own light
# config/params once per module
import functools


@functools.lru_cache(maxsize=None)
def _dense_cfg():
    return reduce_config(get_config("internlm2-1.8b"))


@functools.lru_cache(maxsize=None)
def _mla_setup():
    cfg = reduce_config(get_config("minicpm3-4b"))
    params = make_model(cfg).init(jax.random.PRNGKey(0))
    # layer-0 slice of the stacked (scan-format) per-layer params
    p = jax.tree.map(lambda leaf: leaf[0], params["layers"])["mixer"]
    return cfg, p


# ------------------------------------------------- Σp = 1 property (dense) --
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10**6), bs_mult=st.sampled_from([1, 2]),
       path=st.sampled_from(["streamed", "gathered", "pallas"]))
def test_dense_paged_gn_read_sums_to_one_over_int8_blocks(seed, bs_mult,
                                                          path):
    """V dequantizes to exactly 1 → the read's output IS Σp per query row.
    The K arena is a real per-block int8 quantization of Gaussian data, the
    block layout a random permutation: Σp = 1 to one rounding must hold for
    every read path, through any layout, over int8-dequantized scores."""
    cfg = _dense_cfg()
    rng = np.random.default_rng(seed)
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kv
    n, c, bs, nb = 3, CHUNK, CHUNK * bs_mult, 12
    max_bt = nb // n

    kf = rng.standard_normal((nb, bs, kv, dh)).astype(np.float32) * 2.0
    arena_k, k_scale = _quantize_arena(kf)
    arena_v, v_scale = _ones_arena((nb, bs, kv, dh))
    scales = (k_scale, v_scale)
    tables = jnp.asarray(rng.permutation(nb).reshape(n, max_bt), jnp.int32)
    positions = jnp.asarray(rng.integers(0, (max_bt - 1) * bs, size=n),
                            jnp.int32)
    rows = positions[:, None] + jnp.arange(c)[None, :]

    if path == "streamed":
        qg = jnp.asarray(rng.standard_normal((n, c, kv, g, dh)) * 2.0,
                         jnp.float32)
        out = attention_mod._stream_paged_tiles(
            cfg, qg, arena_k, arena_v, tables, rows, scales=scales)
    elif path == "pallas":
        q = jnp.asarray(rng.standard_normal((n, c, cfg.n_heads, dh)) * 2.0,
                        jnp.float32)
        out = gn_paged_attention_chunk(
            q, arena_k, arena_v, tables, positions,
            jnp.full((n,), c, jnp.int32), interpret=True, scales=scales)
    else:  # gathered oracle: dequantize the gathered stream, same dequant
        # expression the oracle in attn_paged_chunk uses
        qg = jnp.asarray(rng.standard_normal((n, c, kv, g, dh)) * 2.0,
                         jnp.float32)
        k_at = (arena_k[tables].astype(jnp.float32)
                * k_scale[tables][..., None, None, None])
        v_at = (arena_v[tables].astype(jnp.float32)
                * v_scale[tables][..., None, None, None])
        k_at = k_at.reshape(n, -1, kv, dh)
        v_at = v_at.reshape(n, -1, kv, dh)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_at) * dh**-0.5
        t = scores.shape[-1]
        valid = jnp.arange(t)[None, None, :] <= rows[:, :, None]
        scores = jnp.where(valid[:, None, None], scores, attention_mod.NEG_INF)
        pmat = get_softmax(cfg.softmax_impl)(scores)
        out = jnp.einsum("bkgst,btkd->bskgd", pmat, v_at)

    err = float(jnp.max(jnp.abs(1.0 - out)))
    t_max = int(rows.max()) + 1
    assert err <= (t_max + 1) * 2.0**-23, (
        f"Σp drifted: path={path} bs={bs} err={err:.3e}")


# --------------------------------------------------- Σp = 1 property (MLA) --
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10**6), bs_mult=st.sampled_from([1, 2]))
def test_mla_paged_gn_read_sums_to_one_over_int8_blocks(seed, bs_mult):
    """MLA probabilities over int8-dequantized latent blocks sum to 1.  The
    value side rides the latent expansion (no exact-ones trick), so the
    probabilities are computed with the read's own building blocks: the
    gathered branch's dequant expression + score decomposition + the
    configured GN softmax.  The streamed leg is covered through the pinned
    bitwise streamed≡gathered equality of ``mla_paged_chunk`` (asserted
    below over the SAME int8 arenas), which transfers the property."""
    cfg, p = _mla_setup()
    m = cfg.mla
    rng = np.random.default_rng(seed)
    n, c, bs, nb = 3, CHUNK, CHUNK * bs_mult, 12
    max_bt = nb // n
    h = cfg.n_heads

    cf = rng.standard_normal((nb, bs, m.kv_lora_rank)).astype(np.float32)
    rf = rng.standard_normal((nb, bs, m.qk_rope_head_dim)).astype(np.float32)
    arena_c, c_scale = _quantize_arena(cf)
    arena_r, r_scale = _quantize_arena(rf)
    tables = jnp.asarray(rng.permutation(nb).reshape(n, max_bt), jnp.int32)
    positions = jnp.asarray(rng.integers(0, (max_bt - 1) * bs, size=n),
                            jnp.int32)
    rows = positions[:, None] + jnp.arange(c)[None, :]
    x = jnp.asarray(rng.standard_normal((n, c, cfg.d_model)) * 0.3,
                    jnp.float32)
    q_nope, q_rope, _, _ = mla_mod._project(cfg, p, x, rows)

    dt = jnp.float32
    c_kv = (arena_c[tables].astype(dt)
            * c_scale[tables][..., None, None]).reshape(n, -1, m.kv_lora_rank)
    k_rope = (arena_r[tables].astype(dt)
              * r_scale[tables][..., None, None]).reshape(
                  n, -1, m.qk_rope_head_dim)
    kvx = jnp.einsum("btr,rf->btf", c_kv, p["wkv_b"].astype(dt))
    kvx = kvx.reshape(n, -1, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope = kvx[..., : m.qk_nope_head_dim]
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (
        jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
        + jnp.einsum("bshd,btd->bhst", q_rope, k_rope)
    ) * scale
    t = scores.shape[-1]
    mask = (jnp.arange(t)[None, None, :] <= rows[:, :, None])[:, None]
    scores = jnp.where(mask, scores, attention_mod.NEG_INF)
    pmat = get_softmax(cfg.softmax_impl)(scores)
    sums = jnp.sum(pmat, axis=-1)
    t_max = int(rows.max()) + 1
    err = float(jnp.max(jnp.abs(1.0 - sums)))
    assert err <= (t_max + 1) * 2.0**-23, f"Σp drifted: err={err:.3e}"
    # LUT saturation: masked (stale/foreign) columns are EXACT zeros
    assert float(jnp.max(jnp.where(mask, 0.0, pmat))) == 0.0

    # streamed ≡ gathered over the same int8 arenas — transfers the Σp
    # property to the streamed leg bit-for-bit
    prev = attention_mod.FORCE_PAGED_READ
    outs = {}
    try:
        for rd in ("streamed", "gathered"):
            attention_mod.FORCE_PAGED_READ = rd
            out, _ = mla_mod.mla_paged_chunk(
                cfg, p, arena_c, arena_r, x, positions,
                jnp.zeros((n,), jnp.int32),  # read-only: no writes this tick
                tables, scales=(c_scale, r_scale))
            outs[rd] = np.asarray(out)
    finally:
        attention_mod.FORCE_PAGED_READ = prev
    np.testing.assert_allclose(outs["streamed"], outs["gathered"],
                               atol=2e-6, rtol=2e-5)


# --------------------------------- norm-error study: measured vs bound pin --
def test_paged_read_norm_error_within_analytic_bound():
    from repro.serve.norm_error import paged_int8_read_norm_error

    for kd in ("fp", "int8"):
        measured, bound, t = paged_int8_read_norm_error(kv_dtype=kd)
        assert measured <= bound, (
            f"kv_dtype={kd}: measured |1-Σp| {measured:.3e} exceeds the "
            f"analytic bound {bound:.3e} at t={t}")


# ----------------------------------------------- bitwise pool round-trips --
def _randomize_quant_cache(pool, seed=0):
    """Fill every paged layers leaf with random values of its own dtype
    (int8 arenas, f32 scales) so bitwise moves are distinguishable."""
    rng = np.random.default_rng(seed)

    def rand(leaf):
        if leaf.dtype == jnp.int8:
            return jnp.asarray(
                rng.integers(-127, 128, size=leaf.shape), jnp.int8)
        return jnp.asarray(
            rng.uniform(0.01, 1.0, size=leaf.shape).astype(np.float32))

    cache = dict(pool.cache)
    cache["layers"] = jax.tree.map(rand, pool.cache["layers"])
    pool.cache = cache


@pytest.mark.parametrize("family", ["dense", "mla"])
def test_quantized_cow_fork_bitwise(dense, mla, family):
    """A COW block fork copies arena content AND the per-block scale column
    bit-exactly — the forked tenant reads the shared prefix through the
    donor's frozen scale."""
    _, model, _ = dense if family == "dense" else mla
    pool = BlockPagedKVPool(model, num_slots=2, max_seq=16, block_size=4,
                            kv_dtype="int8")
    _randomize_quant_cache(pool, seed=3)
    src, dst = 2, 5
    before = jax.tree.map(np.asarray, pool.cache["layers"])
    pool._fork_copy(src, dst)
    after = jax.tree.map(np.asarray, pool.cache["layers"])
    for k in before:
        np.testing.assert_array_equal(
            after[k][:, dst], before[k][:, src],
            err_msg=f"{k}: forked block differs from donor")
        # untouched blocks stay bitwise put
        keep = [i for i in range(before[k].shape[1]) if i != dst]
        np.testing.assert_array_equal(after[k][:, keep], before[k][:, keep])


@pytest.mark.parametrize("family", ["dense", "mla"])
def test_quantized_spill_restore_bitwise(dense, mla, family):
    """Preempt-spill then restore into a DIFFERENT physical chain is
    bitwise for int8 arenas + scales (payload carries both; only logical
    order matters)."""
    _, model, _ = dense if family == "dense" else mla
    pool = BlockPagedKVPool(model, num_slots=2, max_seq=16, block_size=4,
                            kv_dtype="int8")
    _randomize_quant_cache(pool, seed=7)
    s0 = pool.allocate(reserve_tokens=12)
    pool.ensure(s0, 11)  # 3 blocks
    chain0 = pool.chain_of(s0)
    values0 = {
        k: np.asarray(v)[:, chain0]
        for k, v in pool.cache["layers"].items()
    }
    payload = pool.extract_blocks(s0)
    pool.free(s0)
    # occupy the old chain so the restore lands on different physical blocks
    s_hold = pool.allocate(reserve_tokens=12)
    pool.ensure(s_hold, 11)
    s1 = pool.allocate(reserve_tokens=12)
    pool.ensure(s1, 11)
    chain1 = pool.chain_of(s1)
    assert list(chain1) != list(chain0), "restore chain must differ"
    pool.restore_blocks(s1, payload)
    for k, v in pool.cache["layers"].items():
        np.testing.assert_array_equal(
            np.asarray(v)[:, chain1], values0[k],
            err_msg=f"{k}: restore not bitwise (arena or scale)")


@requires_mesh
def test_quantized_spill_restore_bitwise_sharded(dense):
    """Same bitwise round-trip through a 2-device sharded slot pool: the
    scale leaves shard/replicate with the arenas and survive the spill
    gather/scatter bit-exactly."""
    from repro.parallel.sharding import make_slot_mesh

    _, model, _ = dense
    mesh = make_slot_mesh(2)
    pool = BlockPagedKVPool(model, num_slots=2, max_seq=16, block_size=4,
                            mesh=mesh, num_devices=2, kv_dtype="int8")
    _randomize_quant_cache(pool, seed=11)
    s0 = pool.allocate(reserve_tokens=12)
    pool.ensure(s0, 11)
    chain0 = pool.chain_of(s0)
    values0 = {k: np.asarray(v)[:, chain0]
               for k, v in pool.cache["layers"].items()}
    payload = pool.extract_blocks(s0)
    pool.free(s0)
    s1 = pool.allocate(reserve_tokens=12)
    pool.ensure(s1, 11)
    pool.restore_blocks(s1, payload)
    chain1 = pool.chain_of(s1)
    for k, v in pool.cache["layers"].items():
        np.testing.assert_array_equal(np.asarray(v)[:, chain1], values0[k],
                                      err_msg=f"{k}: sharded restore drifted")


# ------------------------------------------- engine identity / tolerance ---
def _greedy(model, params, reqs, max_seq, **kw):
    eng = ContinuousEngine(model, params, num_slots=2, max_seq=max_seq,
                           cfg=ServeConfig(), chunk=CHUNK, block_size=CHUNK,
                           **kw)
    comps = eng.run(reqs)
    return {c.request_id: np.asarray(c.tokens) for c in comps}, eng


@pytest.mark.parametrize("family", ["dense", "mla"])
def test_int8_engine_greedy_tolerance_pinned_vs_fp(dense, mla, family):
    """Greedy int8 serving vs the fp engine: per-request longest-common-
    prefix fractions stay pinned (min ≥ 0.5, mean ≥ 0.7 — the same
    tolerance discipline as the fused-vs-oracle pin), compile counters are
    exact, and metrics report the kv_dtype."""
    cfg, model, params = dense if family == "dense" else mla
    reqs = _mixed_requests(cfg)
    max_seq = required_max_seq(reqs)
    want, _ = _greedy(model, params, reqs, max_seq, kv_dtype="fp")
    got, eng = _greedy(model, params, reqs, max_seq, kv_dtype="int8")
    m = eng.metrics()
    assert m["kv_dtype"] == "int8"
    assert_exact_compile_counters(m)
    fracs = []
    for rid, w in want.items():
        g = got[rid]
        lcp = 0
        for a, b in zip(w, g):
            if a != b:
                break
            lcp += 1
        fracs.append(lcp / len(w))
    assert min(fracs) >= 0.5, f"per-request LCP fractions collapsed: {fracs}"
    assert float(np.mean(fracs)) >= 0.7, f"mean LCP fraction regressed: {fracs}"
    # drained clean, blocks recycled mid-run (5 reqs, 2 slots)
    assert eng.pool.blocks_in_use == 0


def test_int8_engine_reset_replay_bit_identical(dense):
    """Recycled-block safety under quantization: a reset int8 engine
    replays the same workload bit-identically.  The new tenant's offset-0
    write re-freezes the block scale, so stale scales (like stale arena
    contents) are unreachable without zeroing."""
    cfg, model, params = dense
    reqs = _mixed_requests(cfg)
    max_seq = required_max_seq(reqs)
    eng = ContinuousEngine(model, params, num_slots=2, max_seq=max_seq,
                           cfg=ServeConfig(), chunk=CHUNK, block_size=CHUNK,
                           kv_dtype="int8")
    first = {c.request_id: np.asarray(c.tokens) for c in eng.run(reqs)}
    eng.reset()
    second = {c.request_id: np.asarray(c.tokens) for c in eng.run(reqs)}
    for rid in first:
        np.testing.assert_array_equal(first[rid], second[rid])


@requires_mesh
def test_int8_engine_sharded_identity(dense):
    """2-device int8 engine is greedy token-identical to the 1-device int8
    engine — quantization must not perturb SPMD slot sharding."""
    cfg, model, params = dense
    reqs = _mixed_requests(cfg)
    max_seq = required_max_seq(reqs)
    one, _ = _greedy(model, params, reqs, max_seq, kv_dtype="int8")
    two, eng = _greedy(model, params, reqs, max_seq, kv_dtype="int8",
                       devices=2)
    assert eng.metrics()["kv_dtype"] == "int8"
    for rid in one:
        np.testing.assert_array_equal(one[rid], two[rid])


def test_int8_pool_hbm_well_under_fp(dense):
    """Equal block counts: int8 arenas halve the (bf16) fp pool's arena
    bytes, and the f32 per-block scale rows add only ~1% back — the
    headline equal-HBM lever."""
    _, model, _ = dense
    fp = BlockPagedKVPool(model, num_slots=2, max_seq=32, block_size=4)
    q = BlockPagedKVPool(model, num_slots=2, max_seq=32, block_size=4,
                         kv_dtype="int8")
    assert q.num_blocks == fp.num_blocks
    assert q.hbm_bytes() < 0.55 * fp.hbm_bytes()
    # the quantized cache really is int8 arenas + one f32 scale row per arena
    dtypes = {k: v.dtype for k, v in q.cache["layers"].items()}
    arena_keys = [k for k in dtypes if not k.endswith("_scale")]
    assert arena_keys and all(dtypes[k] == jnp.int8 for k in arena_keys)
    scale_keys = [k for k in dtypes if k.endswith("_scale")]
    assert set(scale_keys) == {f"{k}_scale" for k in arena_keys}
    assert all(dtypes[k] == jnp.float32 for k in scale_keys)


def test_int8_requires_paged_pool(dense):
    _, model, params = dense
    with pytest.raises(ValueError, match="int8"):
        ContinuousEngine(model, params, num_slots=2, max_seq=16,
                         paged=False, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousEngine(model, params, num_slots=2, max_seq=16,
                         kv_dtype="int4")
