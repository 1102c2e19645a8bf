"""The stream-resident GN kernel as the single-TPU paged read.

Pinned here, with the kernel interpreted on the CPU:
  1. its valid rows match the streamed read to LUT-entry rounding, for fp
     and int8 arenas at block sizes {chunk, 2*chunk}, with prefilling,
     decoding and parked lanes in one tick;
  2. its schedule — several blocks per grid step, rows past a decoder's
     8-row tile skipped — leaves valid rows bitwise equal to the one block
     per step, every row schedule;
  3. sentinel channel 0 means the same on every read: +inf for a NaN in a
     live K or V row, under the engine's bound on a healthy tick;
  4. dispatch: the GN read takes the kernel on one TPU device only, with
     heads in whole 128-lane tiles and KV heads in whole int8 words, and an
     engine reports the read its ticks ran; on the kernel its horizon grid
     is one full-width bucket.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, reduce_config
from repro.kernels.gn_paged_attention import kernel as kernel_mod
from repro.kernels.gn_paged_attention.ops import gn_paged_attention_chunk
from repro.models import attention as attention_mod
from repro.models.transformer import make_model
from repro.serve.engine import (
    SENTINEL_SUM_SLACK, ContinuousEngine, ServeConfig, static_reference,
)
from repro.serve.scheduler import Request
from repro.serve.workload import required_max_seq

CHUNK = 4
H, KV, D = 8, 4, 16  # two query heads per KV head; int8 packs 4 heads a word


@pytest.fixture(scope="module")
def dense():
    # pallas_interpret: the kernel read runs in the interpreter here; heads
    # of 128, the lane width a one-TPU engine needs to take the kernel read
    cfg = dataclasses.replace(reduce_config(get_config("internlm2-1.8b"), head_dim=128),
                              pallas_interpret=True)
    model = make_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _quantize(a):
    """Per-block int8 quantization of an (nb, bs, KV, D) arena."""
    a = np.asarray(a, np.float32)
    scale = np.abs(a).reshape(a.shape[0], -1).max(axis=1) / 127.0
    q = np.clip(np.round(a / scale[:, None, None, None]), -127, 127)
    return jnp.asarray(q, jnp.int8), jnp.asarray(scale, jnp.float32)


def _tick(seed, bs, kv_dtype, chunk=CHUNK):
    """One fused tick's read: two prefilling lanes (one mid-prompt, one at
    its first chunk), two decoders, one parked lane; scrambled tables."""
    rng = np.random.default_rng(seed)
    n, max_bt = 5, -(-40 // bs)
    nb = n * max_bt
    dt = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(n, chunk, H, D)), dt)
    k = jnp.asarray(rng.normal(size=(nb, bs, KV, D)), dt)
    v = jnp.asarray(rng.normal(size=(nb, bs, KV, D)), dt)
    scales = None
    if kv_dtype == "int8":
        (k, ks), (v, vs) = _quantize(k), _quantize(v)
        scales = (ks, vs)
    tables = jnp.asarray(rng.permutation(nb).reshape(n, max_bt), jnp.int32)
    starts = jnp.asarray([9, 0, 17, 30, 0], jnp.int32)
    n_valid = jnp.asarray([chunk, chunk, 1, 1, 0], jnp.int32)
    return q, k, v, tables, starts, n_valid, scales


def _valid(n_valid, chunk=CHUNK):
    return np.arange(chunk)[None, :] < np.asarray(n_valid)[:, None]


@pytest.mark.parametrize("bs", [CHUNK, 2 * CHUNK])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_kernel_valid_rows_match_streamed_read(dense, kv_dtype, bs):
    q, k, v, tables, starts, n_valid, scales = _tick(0, bs, kv_dtype)
    got = gn_paged_attention_chunk(q, k, v, tables, starts, n_valid,
                                   interpret=True, scales=scales)
    cfg = dataclasses.replace(dense[0], n_heads=H, n_kv_heads=KV, head_dim=D)
    rows = starts[:, None] + jnp.arange(CHUNK)[None, :]
    qg = q.reshape(*q.shape[:2], KV, H // KV, D)
    want = attention_mod._stream_paged_tiles(cfg, qg, k, v, tables, rows,
                                             scales=scales).reshape(q.shape)
    ok = _valid(n_valid)
    got, want = np.asarray(got, np.float32)[ok], np.asarray(want, np.float32)[ok]
    # online accumulation vs the one-pass softmax: equal up to LUT-entry
    # rounding of the correction factors
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("bs", [CHUNK, 2 * CHUNK])
@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_kernel_schedule_leaves_valid_rows_bitwise(monkeypatch, kv_dtype, bs):
    """Several blocks per grid step and the 8-row decoder tier against one
    block per step over every row, the previous kernel's schedule.  Chunks
    of 8 give 16 query rows, so a decoder computes its first 8 alone."""
    chunk = 2 * CHUNK
    q, k, v, tables, starts, n_valid, scales = _tick(1, bs, kv_dtype, chunk)

    def read():
        return gn_paged_attention_chunk(q, k, v, tables, starts, n_valid,
                                        interpret=True, scales=scales,
                                        with_stats=True)

    outs = [read()]
    with monkeypatch.context() as mp:
        mp.setattr(kernel_mod, "STEP_TOKENS", bs)
        mp.setattr(kernel_mod, "row_tiers", lambda rows: (rows,))
        # the kernel reads its schedule at trace time: drop the cached traces
        # on the way in and out
        jax.clear_caches()
        outs.append(read())
    jax.clear_caches()
    ok = _valid(n_valid, chunk)
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a)[ok], np.asarray(b)[ok])


def _layer_inputs(cfg, seed=3):
    """attn_paged_chunk's operands for a 3-slot tick at f32: a prefilling
    lane and two decoders, each on its own blocks."""
    rng = np.random.default_rng(seed)
    n, nb, bs = 3, 12, CHUNK
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    p = {"wq": f(cfg.d_model, cfg.q_features), "wk": f(cfg.d_model, cfg.kv_features),
         "wv": f(cfg.d_model, cfg.kv_features), "wo": f(cfg.q_features, cfg.d_model)}
    ak = f(nb, bs, cfg.n_kv_heads, cfg.head_dim)
    av = f(nb, bs, cfg.n_kv_heads, cfg.head_dim)
    x = f(n, CHUNK, cfg.d_model)
    tables = jnp.asarray(rng.permutation(nb).reshape(n, nb // n), jnp.int32)
    positions = jnp.asarray([5, 9, 2], jnp.int32)
    n_valid = jnp.asarray([CHUNK, 1, 1], jnp.int32)
    return p, ak, av, x, positions, n_valid, tables


def _channel0(cfg, path, *args):
    attention_mod.FORCE_PAGED_READ = path
    try:
        _, _, health = attention_mod.attn_paged_chunk(cfg, *args, probe=True)
    finally:
        attention_mod.FORCE_PAGED_READ = None
    return np.asarray(health)[:, 0]


READS = ["pallas", "streamed", "gathered"]


@pytest.mark.parametrize("tensor", ["k", "v"])
@pytest.mark.parametrize("path", READS)
def test_nan_in_live_kv_row_drives_channel0_to_inf(dense, path, tensor):
    """A NaN in a K row that slot 0 attends is laundered by the GN exp into
    a finite distribution; only the nonfinite-score channel sees it.  A NaN
    in a V row reaches the output.  Either way slot 0 reads +inf, through
    the kernel's statistics as through the streamed and gathered rows."""
    cfg = dense[0]
    p, ak, av, x, positions, n_valid, tables = _layer_inputs(cfg)
    blk = int(tables[0, 0])  # slot 0's first block: token 1 is attended
    if tensor == "k":
        ak = ak.at[blk, 1, 0, 3].set(jnp.nan)
    else:
        av = av.at[blk, 1, 0, 3].set(jnp.nan)
    ch0 = _channel0(cfg, path, p, ak, av, x, positions, n_valid, tables)
    assert ch0[0] == np.inf
    assert np.isfinite(ch0[1:]).all()


@pytest.mark.parametrize("path", READS)
def test_healthy_tick_channel0_under_engine_bound(dense, path):
    cfg = dense[0]
    args = _layer_inputs(cfg, seed=4)
    positions, n_valid = args[4], args[5]
    ch0 = _channel0(cfg, path, *args)
    # the engine's bound at f32, with t the slot's attended width
    t = np.asarray(positions + n_valid)
    bound = SENTINEL_SUM_SLACK * (t + 1) * float(jnp.finfo(jnp.float32).eps)
    assert (ch0 >= 0).all() and (ch0 <= bound).all(), (ch0, bound)


@pytest.mark.parametrize("on_tpu,devices,change,want", [
    (False, 1, {}, "streamed"),
    (True, 1, {}, "pallas"),
    (True, 2, {}, "streamed"),
    (True, 4, {}, "streamed"),
    (True, 1, {"softmax_impl": "exact"}, "streamed"),
    # the kernel would pad a copy of the arena to 128 lanes every layer
    (True, 1, {"head_dim": 64}, "streamed"),
    # int8 arenas pack four KV heads to a 32-bit word
    (True, 1, {"n_kv_heads": 2}, "streamed"),
])
def test_read_dispatch(dense, monkeypatch, on_tpu, devices, change, want):
    monkeypatch.setattr(attention_mod, "_on_tpu", lambda: on_tpu)
    cfg = dataclasses.replace(dense[0], **change)
    assert attention_mod.paged_read_path(cfg, devices) == want
    assert make_model(cfg).paged_read_path_on(devices) == want


def test_mla_read_stays_streamed_on_one_tpu(monkeypatch):
    monkeypatch.setattr(attention_mod, "_on_tpu", lambda: True)
    model = make_model(reduce_config(get_config("minicpm3-4b")))
    assert model.paged_read_path_on(1) == "streamed"


@pytest.mark.parametrize("on_tpu,want", [(False, "streamed"), (True, "pallas")],
                         ids=["cpu", "one-tpu"])
def test_engine_ticks_report_their_read(dense, monkeypatch, on_tpu, want):
    """A one-device engine on a (mocked) TPU serves every tick through the
    kernel, greedy-identical to the static oracle with no sentinel trip;
    on the CPU no tick does."""
    monkeypatch.setattr(attention_mod, "_on_tpu", lambda: on_tpu)
    cfg, model, params = dense
    rng = np.random.default_rng(9)
    reqs = [Request(id=i, tokens=rng.integers(0, cfg.vocab, size=L).astype(np.int32),
                    max_new_tokens=4, arrival_step=i)
            for i, L in enumerate([5, 9, 14])]
    engine = ContinuousEngine(model, params, num_slots=2,
                              max_seq=required_max_seq(reqs), cfg=ServeConfig(),
                              chunk=CHUNK)
    comps = engine.run(reqs)
    ref = static_reference(model, params, reqs, ServeConfig())
    for c in comps:
        assert np.array_equal(c.tokens, ref[c.request_id]), f"req {c.request_id}"
    m = engine.metrics()
    assert m["read_path"] == want
    assert m["kernel_read_ticks"] == (m["decode_steps"] if on_tpu else 0)
    # the kernel's grid is the one full-width bucket: one program per kind
    max_bt = engine.pool.max_blocks_per_slot
    assert (engine.horizon_bucket_grid == [max_bt]) == on_tpu
    if on_tpu:
        assert m["fused_step_compilations"] == m["decode_compilations"] == 1
    assert m["sentinel_checks"] > 0 and m["sentinel_violations"] == 0
    engine.reset()
    assert engine.metrics()["kernel_read_ticks"] == 0
