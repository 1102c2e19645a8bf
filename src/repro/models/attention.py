"""Attention: GQA/MHA (+sliding window), cross-attention, and decode paths.

The softmax is pluggable (``cfg.softmax_impl``) — 'gn' routes through the
paper's Algorithm 1; baselines and the FP32 oracle are selectable for the
accuracy experiments.  ``cfg.use_pallas`` switches the training/prefill path
to the fused GN flash-attention Pallas kernel (single-chip hot path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import get_softmax
from repro.models.layers import ParamSpec
from repro.models.rope import apply_rope
from repro.serve.tracing import (
    ATTN_PROJ, GN_PROBE, GN_SOFTMAX, KV_WRITE, PAGED_READ,
)

NEG_INF = -1e30


def attn_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {
        "wq": ParamSpec((d, cfg.q_features), ("embed_fsdp", "heads_tp")),
        "wk": ParamSpec((d, cfg.kv_features), ("embed_fsdp", "heads_tp")),
        "wv": ParamSpec((d, cfg.kv_features), ("embed_fsdp", "heads_tp")),
        "wo": ParamSpec((cfg.q_features, d), ("heads_tp", "embed_fsdp")),
    }


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _sdpa(cfg: ModelConfig, q, k, v, mask) -> jax.Array:
    """q: (B,S,H,dh), k/v: (B,T,KV,dh), mask: (B,1,S,T) or (1,1,S,T) bool."""
    b, s, h, dh = q.shape
    t = k.shape[1]
    kv = k.shape[2]
    group = h // kv
    qg = q.reshape(b, s, kv, group, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) * (dh**-0.5)
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, NEG_INF)
    softmax = get_softmax(cfg.softmax_impl)
    p = softmax(scores).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return out.reshape(b, s, h, dh)


def _use_chunked(cfg: ModelConfig, s: int) -> bool:
    """Chunked (flash-style) attention for long sequences — perf B2 (§Perf).

    The one-pass path materializes (S,T) f32 scores; past ~2k tokens that
    dominates the memory roofline.  The chunked path requires the GN or exact
    softmax (baselines are one-pass-only, used in small accuracy studies).
    """
    return s > 2048 and cfg.softmax_impl in ("gn", "exact")


def causal_mask(s: int, t: int, window: int = 0) -> jax.Array:
    """(1, 1, s, t) bool; t >= s (query block is the suffix of the kv span)."""
    rows = jnp.arange(s)[:, None] + (t - s)
    cols = jnp.arange(t)[None, :]
    m = cols <= rows
    if window:
        m &= cols > rows - window
    return m[None, None]


def self_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (B, S)
    causal: bool = True,
) -> jax.Array:
    dt = x.dtype
    b, s, d = x.shape
    q = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wq"].astype(dt)), cfg.n_heads, cfg.head_dim)
    k = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wk"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wv"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cfg.use_pallas:
        from repro.kernels.gn_attention.ops import gn_attention

        out = gn_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
            interpret=cfg.pallas_interpret,
        ).transpose(0, 2, 1, 3)
    elif _use_chunked(cfg, s):
        from repro.models.chunked_attention import chunked_self_attention

        out = chunked_self_attention(cfg, q, k, v, causal)
    else:
        if causal:
            mask = causal_mask(s, s, cfg.sliding_window)
        else:
            mask = jnp.ones((1, 1, s, s), bool)
        out = _sdpa(cfg, q, k, v, mask)
    out = out.reshape(b, s, cfg.q_features)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(dt))


# ------------------------------------------------------------ cross-attn ---
def cross_attn_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {
        "wq": ParamSpec((d, cfg.q_features), ("embed_fsdp", "heads_tp")),
        "wk": ParamSpec((d, cfg.kv_features), ("embed_fsdp", "heads_tp")),
        "wv": ParamSpec((d, cfg.kv_features), ("embed_fsdp", "heads_tp")),
        "wo": ParamSpec((cfg.q_features, d), ("heads_tp", "embed_fsdp")),
    }


def cross_attention(cfg: ModelConfig, p: dict, x, memory) -> jax.Array:
    """x: (B,S,D) queries; memory: (B,M,D) encoder/vision states (no rope)."""
    dt = x.dtype
    b, s, d = x.shape
    m = memory.shape[1]
    q = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wq"].astype(dt)), cfg.n_heads, cfg.head_dim)
    k = _split_heads(jnp.einsum("bmd,df->bmf", memory.astype(dt), p["wk"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(jnp.einsum("bmd,df->bmf", memory.astype(dt), p["wv"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    mask = jnp.ones((1, 1, s, m), bool)
    out = _sdpa(cfg, q, k, v, mask).reshape(b, s, cfg.q_features)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(dt))


# ----------------------------------------------------------------- decode ---
def attn_cache_shape(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    win = cfg.sliding_window or 0
    slots = min(max_seq, win) if win else max_seq
    kv = (batch, slots, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(kv, jnp.dtype(cfg.dtype)),
        "v": jax.ShapeDtypeStruct(kv, jnp.dtype(cfg.dtype)),
    }


def attn_prefill(cfg: ModelConfig, p: dict, x, positions):
    """Run self-attention over the prompt AND return the kv cache to reuse."""
    dt = x.dtype
    b, s, _ = x.shape
    k = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wk"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wv"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wq"].astype(dt)), cfg.n_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    if _use_chunked(cfg, s):
        from repro.models.chunked_attention import chunked_self_attention

        out = chunked_self_attention(cfg, q, k, v, causal=True).reshape(b, s, cfg.q_features)
    else:
        mask = causal_mask(s, s, cfg.sliding_window)
        out = _sdpa(cfg, q, k, v, mask).reshape(b, s, cfg.q_features)
    out = jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(dt))
    if cfg.sliding_window and s > cfg.sliding_window:
        k = k[:, -cfg.sliding_window :]
        v = v[:, -cfg.sliding_window :]
    return out, {"k": k, "v": v}


def attn_decode_chunk(cfg: ModelConfig, p: dict, cache: dict, x, pos, n_valid):
    """Chunked append-decode: C tokens enter the cache at absolute positions
    [pos, pos+n_valid); lanes >= ``n_valid`` are don't-care (their cache
    writes are dropped via an out-of-bounds scatter index, their outputs are
    garbage the caller ignores).  x: (B,C,D); pos/n_valid: traced scalars.

    This is the chunked-prefill workhorse of the fused serving step: row i
    attends over cache[0 .. pos+i] exactly like ``attn_decode_step`` at
    position pos+i, so streaming a prompt through it chunk-by-chunk writes
    the same cache and logits the monolithic ``attn_prefill`` produces.
    Sliding-window configs keep the ring-buffer layout (writes land at
    (pos+i) % slots) and need chunk <= window.
    """
    dt = x.dtype
    b, c_len = x.shape[:2]
    offs = jnp.arange(c_len)
    rows = pos + offs  # absolute positions, one per chunk lane
    posv = jnp.broadcast_to(rows[None], (b, c_len))
    q = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wq"].astype(dt)), cfg.n_heads, cfg.head_dim)
    k_new = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wk"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    v_new = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wv"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, posv, cfg.rope_theta)
    k_new = apply_rope(k_new, posv, cfg.rope_theta)

    slots = cache["k"].shape[1]
    win = cfg.sliding_window or 0
    widx = (rows % slots) if win else rows
    widx = jnp.where(offs < n_valid, widx, slots)  # invalid lanes -> dropped
    k = cache["k"].at[:, widx].set(k_new.astype(cache["k"].dtype), mode="drop")
    v = cache["v"].at[:, widx].set(v_new.astype(cache["v"].dtype), mode="drop")

    idx = jnp.arange(slots)
    if win:
        # A chunk of C writes evicts C ring entries the chunk's *earliest*
        # queries still need, so attend the pre-write ring and the chunk's
        # own k/v side by side instead of the post-write ring.  Pre-write
        # entry j holds absolute position (pos-1) - age_j; it is in row i's
        # window iff it is >= pos+i-win+1 (and exists, >= 0).
        age_old = (((pos - 1) % slots) - idx) % slots  # 0 = newest pre-write
        abs_old = (pos - 1) - age_old  # (slots,)
        valid_old = (abs_old[None, :] >= rows[:, None] - (win - 1)) & (
            abs_old[None, :] >= 0
        )
        valid_new = offs[None, :] <= offs[:, None]  # in-chunk causal (C <= win)
        valid = jnp.concatenate([valid_old, valid_new], axis=1)  # (C, slots+C)
        k_at = jnp.concatenate([cache["k"], k_new.astype(cache["k"].dtype)], axis=1)
        v_at = jnp.concatenate([cache["v"], v_new.astype(cache["v"].dtype)], axis=1)
    else:
        valid = idx[None, :] <= rows[:, None]  # (C, slots)
        k_at, v_at = k, v
    mask = valid[None, None, None]  # broadcast over (b, kv, group)

    kv = cfg.n_kv_heads
    group = cfg.n_heads // kv
    qg = q.reshape(b, c_len, kv, group, cfg.head_dim)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_at) * (cfg.head_dim**-0.5)
    scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
    from repro.core import get_softmax

    pmat = get_softmax(cfg.softmax_impl)(scores).astype(v_at.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", pmat, v_at).reshape(b, c_len, cfg.q_features)
    out = jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(dt))
    return out, {"k": k, "v": v}


# Test/bench override for the paged read dispatch below: None (auto) or one
# of 'pallas' / 'streamed' / 'gathered'.  The gathered path materializes the
# full logical stream and exists as the identity oracle the streamed paths
# are pinned against (tests/test_serve_paged.py) — the serving tick itself
# never takes it unless forced or running an exotic baseline softmax.
FORCE_PAGED_READ: str | None = None

# GN sentinel: any per-block dequantization scale above this is treated as
# corrupt by the in-tick scale-sanity probe.  Legitimate scales are
# QUANT_MARGIN * amax / 127 — O(activation magnitude / 60) — so the ceiling
# has orders of magnitude of headroom; only a scribbled/overflowed scale
# leaf can cross it.
SCALE_SANITY_MAX = 1e4


@jax.named_scope(GN_PROBE)
def _probe_sum_residual(pmat, scores, out, valid, lane_ok):
    """GN sentinel channel 0, per slot: the Σp residual of this layer's
    paged attention read, with nonfinite laundering ruled out.

    pmat/scores: (N, *head_axes, C, T); valid: (N, C, T) causal-column
    mask; lane_ok: (N, C) live-lane mask; out: (N, C, ...) the attention
    output (pre-wo).  Returns (N,) f32: max over the slot's live lanes of
    |Σp − 1| — the paper's guaranteed-normalization residual, analytically
    bounded by (t+1)·2⁻²³ for the GN softmax — forced to +inf when any
    live-region score or output element is nonfinite.  The explicit
    finiteness channels matter: GN's snap-to-grid exp *launders* NaN scores
    into a valid (finite, Σp = 1) distribution, so a poisoned KV block is
    invisible to the residual alone; NaN K surfaces in ``scores``, NaN V in
    ``out``."""
    n = pmat.shape[0]
    heads = pmat.ndim - 3
    lane = lane_ok.reshape(n, *([1] * heads), -1)
    v = valid.reshape(n, *([1] * heads), valid.shape[1], valid.shape[2])
    sumres = jnp.abs(jnp.sum(pmat.astype(jnp.float32), axis=-1) - 1.0)
    res = jnp.max(jnp.where(lane, sumres, 0.0), axis=tuple(range(1, 2 + heads)))
    bad = (~jnp.isfinite(scores)) & v & lane[..., None]
    bad = jnp.any(bad, axis=tuple(range(1, 3 + heads)))
    oflat = out.astype(jnp.float32).reshape(n, out.shape[1], -1)
    obad = jnp.any((~jnp.isfinite(oflat)) & lane_ok[:, :, None], axis=(1, 2))
    return jnp.where(bad | obad, jnp.inf, res)


@jax.named_scope(GN_PROBE)
def paged_probe_word(probe0, positions, n_valid, tables, block_size: int,
                     rd_scales, clip_tok):
    """Assemble one layer's (N, 3) sentinel health word.

    Channels: [0] the Σp/finiteness residual from ``_probe_sum_residual``
    (+inf on any nonfinite live value); [1] the fraction of this tick's
    int8 writes that saturated (freeze-at-first-write scales clip, never
    rescale — persistent clipping means the block's frozen scale no longer
    covers the stream and is the engine's cue for int8→fp fallback); [2] a
    scale-sanity flag over the slot's live-horizon per-block scales
    (nonfinite, negative, or > SCALE_SANITY_MAX ⇒ corrupt scale leaf).
    Parked lanes (n_valid == 0) read stale arena content by design, so
    every channel is zeroed for them — health is only meaningful for live
    slots."""
    n = positions.shape[0]
    active = n_valid > 0
    if clip_tok is not None:
        c_len = clip_tok.shape[0] // n
        lane_ok = jnp.arange(c_len)[None, :] < n_valid[:, None]
        ct = clip_tok.reshape(n, c_len)
        clip = (jnp.sum(jnp.where(lane_ok, ct, False).astype(jnp.float32), axis=1)
                / jnp.maximum(n_valid, 1).astype(jnp.float32))
    else:
        clip = jnp.zeros((n,), jnp.float32)
    if rd_scales is not None:
        h = tables.shape[1]
        max_blk = (positions + jnp.maximum(n_valid, 1) - 1) // block_size
        blk_ok = jnp.arange(h)[None, :] <= max_blk[:, None]
        sbad = jnp.zeros((n,), bool)
        for s in rd_scales:
            s_at = s[tables]  # (N, H) — tiny, horizon-bounded
            bad = (~jnp.isfinite(s_at)) | (s_at < 0) | (s_at > SCALE_SANITY_MAX)
            sbad = sbad | jnp.any(bad & blk_ok, axis=1)
        scalebad = sbad.astype(jnp.float32)
    else:
        scalebad = jnp.zeros((n,), jnp.float32)
    zero = jnp.zeros((n,), jnp.float32)
    return jnp.stack([
        jnp.where(active, probe0, zero),
        jnp.where(active, clip, zero),
        jnp.where(active, scalebad, zero),
    ], axis=1)


# Headroom multiplier on the first-write per-block amax: a block's scale is
# set once, from the first token written into it, and later appends to the
# same block saturate (clip to ±127) rather than rescale — rescaling would
# rewrite already-quantized history and break the bitwise COW/spill/restore
# contract.  The margin absorbs later-token amax drift within a block; the
# GN softmax bounds whatever error saturation leaves (masked numerators are
# exactly zero and Σp = 1 holds over any numerator perturbation).
QUANT_MARGIN = 2.0


def paged_quant_write(flat_arena, scale, new_vals, dest, block_size: int,
                      return_clip: bool = False):
    """Freeze-at-first-write int8 block scatter.

    flat_arena: (nb*bs, ...) int8; scale: (nb,) f32 per-block scales;
    new_vals: (n_tok, ...) fp values for destinations ``dest`` ((n_tok,)
    flattened arena indices, invalid lanes >= nb*bs and dropped).  Returns
    (new flat_arena, new scale) — plus, with ``return_clip``, an (n_tok,)
    bool of which writes saturated the ±127 range (the sentinel's
    clip-fraction channel; frozen scales clip rather than rescale, so
    persistent clipping is a live overflow signal, not a transient).

    Scale discipline: appends are strictly in-order, so the first write any
    tenant makes to a physical block lands at in-block offset 0 — that write
    (re)sets the block's scale from the tick's per-block amax (with
    ``QUANT_MARGIN`` headroom), which also makes recycled blocks safe
    without zeroing: the new tenant's offset-0 write overwrites the stale
    scale.  Every other write reuses the frozen scale and saturates.  A
    COW-forked partial block keeps its donor's frozen scale (the fork
    resumes mid-block, offset > 0), so the shared quantized prefix stays
    bitwise identical through the fork."""
    nb = scale.shape[0]
    blk = dest // block_size  # invalid lanes -> nb, dropped by the scatters
    red = tuple(range(1, new_vals.ndim))
    amax = jnp.max(jnp.abs(new_vals.astype(jnp.float32)), axis=red)  # (n_tok,)
    blk_amax = jnp.zeros((nb,), jnp.float32).at[blk].max(amax, mode="drop")
    first = jnp.zeros((nb,), jnp.int32).at[blk].max(
        (dest % block_size == 0).astype(jnp.int32), mode="drop"
    ) > 0
    scale = jnp.where(first, QUANT_MARGIN * blk_amax / 127.0, scale)
    s_tok = jnp.take(scale, jnp.minimum(blk, nb - 1))  # (n_tok,)
    denom = jnp.where(s_tok > 0, s_tok, 1.0).reshape(
        (new_vals.shape[0],) + (1,) * (new_vals.ndim - 1)
    )
    q_f = jnp.round(new_vals.astype(jnp.float32) / denom)
    q = jnp.clip(q_f, -127.0, 127.0).astype(jnp.int8)
    out = flat_arena.at[dest].set(q, mode="drop")
    if return_clip:
        return out, scale, jnp.any(jnp.abs(q_f) > 127.0, axis=red)
    return out, scale


def paged_read_path(cfg: ModelConfig) -> str:
    """Which paged attention read the serving tick uses for dense KV:
    'pallas' (TPU kernel, online GN accumulation), 'streamed' (lax.scan
    over block tiles emitting score tiles — bitwise equal to the gathered
    read without materializing the K stream), or 'gathered' (full-stream
    materialization; baselines-only oracle)."""
    if FORCE_PAGED_READ is not None:
        return FORCE_PAGED_READ
    if cfg.use_pallas:
        return "pallas"
    # the online accumulation needs a streaming-stable softmax: GN (snap-to-
    # Δ-grid LUT exp) or the exact float path; one-pass-only baselines fall
    # back to the gathered oracle
    return "streamed" if cfg.softmax_impl in ("gn", "exact") else "gathered"


def _stream_paged_tiles(cfg: ModelConfig, qg, arena_k, arena_v, tables, rows,
                        scales=None, probe_nv=None):
    """Gather-free dense paged read: lax.scan over block tiles.

    qg: (N, C, KV, G, dh) in activation dtype; arena_k/arena_v:
    (nb, bs, KV, dh) in cache dtype; tables: (N, H) physical block ids
    (H = the tick's block horizon — compute and HBM traffic scale with live
    context, not max_seq); rows: (N, C) absolute positions.
    Returns (N, C, KV, G, dh) in activation dtype.

    The k scan emits one (.., C, bs) *score* tile per block — each score
    element is an independent dh-dot of the same operands the gathered read
    contracts, so the stacked score row is **bitwise identical** to the
    gathered read's, column for column, without ever materializing the
    gathered K stream.  The one-pass GN softmax then runs on that row
    exactly as in the gathered path (identical probabilities, identical
    Σp = 1 guarantee: masked columns — every stale/foreign table entry
    included — get exactly-zero numerators), and the weighted-value
    contraction is the gathered path's own einsum over the horizon-bounded
    V blocks — so the whole read is **bitwise identical** to the gathered
    oracle while halving the stream materialization and bounding it by the
    live horizon.  (One big AV contraction beats a per-tile value scan on
    every backend tried; the Pallas kernel is the truly stream-resident
    form — single-pass online (m, l, acc) state, LUT'd corrections,
    nothing materialized — equivalent up to LUT-entry rounding.)
    """
    bs = arena_k.shape[1]
    scale = cfg.head_dim**-0.5
    dt = qg.dtype
    k_scale, v_scale = scales if scales is not None else (None, None)
    tbls = jnp.moveaxis(tables, 1, 0)  # (H, N)
    # unroll a constant factor only: full unrolling would make trace/HLO
    # size linear in the top horizon bucket (512 tiles at max_seq 4096 /
    # block 8), exactly the compile blow-up horizon bucketing exists to cap

    def k_body(_, tbl_j):  # tbl_j: (N,) physical block id of logical j
        k_c = arena_k[tbl_j]  # (N, bs, KV, dh)
        if k_scale is not None:
            # dequantize strictly AFTER the per-tile gather: the stream-
            # sized object stays int8, only one (N, bs, KV, dh) tile is
            # ever fp-resident
            k_c = k_c.astype(dt) * k_scale[tbl_j].astype(dt)[:, None, None, None]
        return None, jnp.einsum("bskgd,btkd->bkgst", qg, k_c) * scale

    _, s_tiles = jax.lax.scan(k_body, None, tbls, unroll=8)  # (H, N, KV, G, C, bs)
    scores = jnp.moveaxis(s_tiles, 0, 4)  # (N, KV, G, C, H, bs)
    scores = scores.reshape(*scores.shape[:4], -1)  # logical column order

    t = scores.shape[-1]  # horizon * bs, tail masked below
    valid = jnp.arange(t)[None, None, :] <= rows[:, :, None]  # (N, C, T)
    scores = jnp.where(valid[:, None, None], scores.astype(jnp.float32), NEG_INF)
    from repro.core import get_softmax

    n = rows.shape[0]
    kv, dh = arena_v.shape[2], arena_v.shape[3]
    v_at = arena_v[tables]  # horizon-bounded V blocks (N, H, bs, KV, dh)
    if v_scale is not None:
        # int8 blocks gathered first, dequantized per block after the gather
        v_at = v_at.astype(dt) * v_scale[tables].astype(dt)[..., None, None, None]
    v_at = v_at.reshape(n, -1, kv, dh)
    with jax.named_scope(GN_SOFTMAX):
        pmat = get_softmax(cfg.softmax_impl)(scores).astype(v_at.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", pmat, v_at)
    if probe_nv is not None:
        lane_ok = jnp.arange(rows.shape[1])[None, :] < probe_nv[:, None]
        return out, _probe_sum_residual(pmat, scores, out, valid, lane_ok)
    return out


def attn_paged_chunk(cfg: ModelConfig, p: dict, arena_k, arena_v, x, positions,
                     n_valid, tables, scales=None, probe=False):
    """Block-paged chunked append-decode, batched over slots.

    The slot-monolithic ``attn_decode_chunk`` owns a (max_seq,) slab per
    sequence; here every sequence owns only a *block table* into a shared KV
    arena, so resident HBM scales with live tokens instead of worst-case
    length.  x: (N, C, D); positions/n_valid: (N,) int32 per-slot vectors;
    tables: (N, max_bt) int32 physical-block ids per logical block — the
    engine passes a *horizon-sliced* table (max_bt = the tick's bucketed
    block horizon), so per-tick attention work is bounded by live context;
    arena_k/arena_v: (num_blocks, block_size, KV, dh).

    Lane (s, i) writes absolute position positions[s]+i through the table
    (lanes >= n_valid[s] scatter out of bounds and are dropped — n_valid=0
    drops a whole slot, which is how inactive lanes are kept away from
    blocks they don't own) and attends the logical stream
    [0, positions[s]+i].  Table entries past a slot's allocated prefix may
    point at recycled or foreign blocks: every such column sits beyond the
    causal mask, and the GN softmax turns masked scores into *exactly zero*
    numerators (LUT saturation), so stale block contents cannot leak into
    either the weighted sum or the normalizer — Σp = 1 over the same score
    multiset as the slab path, independent of block layout.

    The read itself is dispatched by ``paged_read_path``: the Pallas kernel
    (TPU; chunked queries included), the streamed block-tile scan (CPU/GPU
    default — bitwise equal to the gathered read, K stream never
    materialized), or the gathered oracle (baselines/tests only).

    ``scales=(k_scale, v_scale)`` ((num_blocks,) f32 each) switches the
    arenas to int8 with per-block dequantization scales: writes quantize
    through ``paged_quant_write`` (freeze-at-first-write) and every read
    path dequantizes strictly *after* its per-block/per-tile gather, so the
    fp stream is never materialized at arena width.  The GN LUT-saturation
    guarantee is what makes this safe: Σp = 1 holds over the dequantized
    numerators exactly as over the fp ones.

    Returns (out (N, C, D), (new arena_k, new arena_v)) — plus
    (new k_scale, new v_scale) appended when ``scales`` is given.

    ``probe=True`` (a static Python bool — the engine binds it as a closure
    constant, so it adds no trace keys) appends a third return: this
    layer's (N, 3) GN sentinel health word (see ``paged_probe_word``).  The
    streamed and gathered reads compute the full Σp-residual/finiteness
    probe from their materialized score rows; the Pallas kernel keeps its
    probabilities in-kernel, so its probe is reduced to output finiteness
    (documented coverage gap: NaN-K laundering is only certified on the
    streamed/gathered paths — the CPU/GPU default and the CI path).
    """
    dt = x.dtype
    b, c_len = x.shape[:2]
    nb, bs = arena_k.shape[:2]
    offs = jnp.arange(c_len)
    rows = positions[:, None] + offs[None, :]  # (N, C) absolute positions
    with jax.named_scope(ATTN_PROJ):
        q = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wq"].astype(dt)), cfg.n_heads, cfg.head_dim)
        k_new = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wk"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
        v_new = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wv"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, rows, cfg.rope_theta)
        k_new = apply_rope(k_new, rows, cfg.rope_theta)

    kv, dh = cfg.n_kv_heads, cfg.head_dim
    clip_tok = None
    with jax.named_scope(KV_WRITE):
        dest = paged_write_indices(rows, n_valid, tables, bs, nb)
        flat_k = arena_k.reshape(nb * bs, kv, dh)
        flat_v = arena_v.reshape(nb * bs, kv, dh)
        if scales is not None:
            k_scale, v_scale = scales
            if probe:
                flat_k, k_scale, kclip = paged_quant_write(
                    flat_k, k_scale, k_new.reshape(b * c_len, kv, dh), dest, bs,
                    return_clip=True)
                flat_v, v_scale, vclip = paged_quant_write(
                    flat_v, v_scale, v_new.reshape(b * c_len, kv, dh), dest, bs,
                    return_clip=True)
                clip_tok = kclip | vclip
            else:
                flat_k, k_scale = paged_quant_write(
                    flat_k, k_scale, k_new.reshape(b * c_len, kv, dh), dest, bs)
                flat_v, v_scale = paged_quant_write(
                    flat_v, v_scale, v_new.reshape(b * c_len, kv, dh), dest, bs)
            arenas = (flat_k.reshape(arena_k.shape), flat_v.reshape(arena_v.shape),
                      k_scale, v_scale)
            rd_scales = (k_scale, v_scale)
        else:
            flat_k = flat_k.at[dest].set(k_new.reshape(b * c_len, kv, dh).astype(flat_k.dtype), mode="drop")
            flat_v = flat_v.at[dest].set(v_new.reshape(b * c_len, kv, dh).astype(flat_v.dtype), mode="drop")
            arenas = (flat_k.reshape(arena_k.shape), flat_v.reshape(arena_v.shape))
            rd_scales = None

    path = paged_read_path(cfg)
    group = cfg.n_heads // kv
    if path == "pallas":
        # single-chip TPU hot path: the Pallas kernel chases the block table
        # with scalar-prefetched index maps instead of materializing the
        # gathered stream; same GN datapath, tiled.
        # Chunked queries ride the same kernel (causal intra-chunk mask);
        # int8 arenas dequantize in-kernel, per block, after the DMA.
        # The GN softmax sits inside the kernel: its time is paged-read time.
        from repro.kernels.gn_paged_attention.ops import gn_paged_attention_chunk

        with jax.named_scope(PAGED_READ):
            out = gn_paged_attention_chunk(
                q,
                flat_k.reshape(nb, bs, kv, dh),
                flat_v.reshape(nb, bs, kv, dh),
                tables,
                positions,
                n_valid,
                interpret=cfg.pallas_interpret,
                scales=rd_scales,
            ).reshape(b, c_len, cfg.q_features)
        if probe:
            # reduced probe: probabilities stay in-kernel, so only output
            # finiteness is observable here (see docstring)
            with jax.named_scope(GN_PROBE):
                lane_ok = jnp.arange(c_len)[None, :] < n_valid[:, None]
                obad = jnp.any(
                    (~jnp.isfinite(out.astype(jnp.float32))) & lane_ok[:, :, None],
                    axis=(1, 2),
                )
                probe0 = jnp.where(obad, jnp.inf, 0.0)
        with jax.named_scope(ATTN_PROJ):
            out = jnp.einsum("bsf,fd->bsd", out.astype(dt), p["wo"].astype(dt))
        if probe:
            return out, arenas, paged_probe_word(
                probe0, positions, n_valid, tables, bs, rd_scales, clip_tok)
        return out, arenas

    if path == "streamed":
        qg = q.reshape(b, c_len, kv, group, dh)
        with jax.named_scope(PAGED_READ):
            res = _stream_paged_tiles(
                cfg, qg,
                flat_k.reshape(nb, bs, kv, dh), flat_v.reshape(nb, bs, kv, dh),
                tables, rows, scales=rd_scales,
                probe_nv=n_valid if probe else None,
            )
        if probe:
            res, probe0 = res
        out = res.reshape(b, c_len, cfg.q_features)
        with jax.named_scope(ATTN_PROJ):
            out = jnp.einsum("bsf,fd->bsd", out.astype(dt), p["wo"].astype(dt))
        if probe:
            return out, arenas, paged_probe_word(
                probe0, positions, n_valid, tables, bs, rd_scales, clip_tok)
        return out, arenas

    # gathered oracle: materialize each slot's logical KV stream (post-write,
    # so the chunk's own keys are already in place — no side concat needed).
    # Tests pin the streamed paths against this; the tick never runs it
    # unless forced or serving a one-pass-only baseline softmax.  Quantized
    # arenas gather int8 blocks first and dequantize the gathered stream —
    # the oracle is allowed its materialization.
    with jax.named_scope(PAGED_READ):
        k_at = flat_k.reshape(nb, bs, kv, dh)[tables]
        v_at = flat_v.reshape(nb, bs, kv, dh)[tables]
        if rd_scales is not None:
            k_at = k_at.astype(dt) * k_scale[tables].astype(dt)[..., None, None, None]
            v_at = v_at.astype(dt) * v_scale[tables].astype(dt)[..., None, None, None]
        k_at = k_at.reshape(b, -1, kv, dh)
        v_at = v_at.reshape(b, -1, kv, dh)
        t = k_at.shape[1]  # horizon * bs, tail masked below

        valid = jnp.arange(t)[None, None, :] <= rows[:, :, None]  # (N, C, T)
        mask = valid[:, None, None]  # broadcast over (kv, group)

        qg = q.reshape(b, c_len, kv, group, cfg.head_dim)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_at) * (cfg.head_dim**-0.5)
        scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
        from repro.core import get_softmax

        with jax.named_scope(GN_SOFTMAX):
            pmat = get_softmax(cfg.softmax_impl)(scores).astype(v_at.dtype)
        att = jnp.einsum("bkgst,btkd->bskgd", pmat, v_at)
    with jax.named_scope(ATTN_PROJ):
        out = jnp.einsum("bsf,fd->bsd", att.reshape(b, c_len, cfg.q_features),
                         p["wo"].astype(dt))
    if probe:
        lane_ok = jnp.arange(c_len)[None, :] < n_valid[:, None]
        probe0 = _probe_sum_residual(pmat, scores, att, valid, lane_ok)
        return out, arenas, paged_probe_word(
            probe0, positions, n_valid, tables, bs, rd_scales, clip_tok)
    return out, arenas


def paged_write_indices(rows, n_valid, tables, block_size: int, num_blocks: int):
    """Flattened arena destinations for a (N, C) grid of absolute positions:
    physical = table[row // bs] * bs + row % bs, with lanes >= n_valid sent
    out of bounds (num_blocks * bs) so `.at[].set(mode='drop')` discards
    them.  Shared by the dense and MLA paged writers."""
    n, c_len = rows.shape
    log_blk = rows // block_size
    phys = jnp.take_along_axis(tables, log_blk, axis=1)  # (N, C)
    dest = phys * block_size + rows % block_size
    lane_ok = jnp.arange(c_len)[None, :] < n_valid[:, None]
    return jnp.where(lane_ok, dest, num_blocks * block_size).reshape(-1)


def attn_decode_step(cfg: ModelConfig, p: dict, cache: dict, x, pos):
    """One-token decode.  x: (B,1,D); pos: scalar int32 (current position).

    Full-attention: cache slot ``pos`` is written.  Sliding window: ring
    buffer slot ``pos % window`` (sub-quadratic memory, the mixtral path).
    """
    dt = x.dtype
    b = x.shape[0]
    q = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wq"].astype(dt)), cfg.n_heads, cfg.head_dim)
    k_new = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wk"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    v_new = _split_heads(jnp.einsum("bsd,df->bsf", x, p["wv"].astype(dt)), cfg.n_kv_heads, cfg.head_dim)
    posv = jnp.full((b, 1), pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rope_theta)
    k_new = apply_rope(k_new, posv, cfg.rope_theta)

    slots = cache["k"].shape[1]
    win = cfg.sliding_window or 0
    slot = (pos % slots) if win else pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))

    idx = jnp.arange(slots)
    if win:
        # ring buffer: slot i holds absolute position  i + floor((pos-i)/slots)*slots
        age = (slot - idx) % slots  # 0 = newest
        valid = (age < win) & (age <= pos)
    else:
        valid = idx <= pos
    mask = valid[None, None, None, :]  # (1,1,1,slots)

    kv = cfg.n_kv_heads
    group = cfg.n_heads // kv
    qg = q.reshape(b, 1, kv, group, cfg.head_dim)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) * (cfg.head_dim**-0.5)
    scores = jnp.where(mask[:, :, None], scores.astype(jnp.float32), NEG_INF)
    from repro.core import get_softmax

    pmat = get_softmax(cfg.softmax_impl)(scores).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", pmat, v).reshape(b, 1, cfg.q_features)
    out = jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(dt))
    return out, {"k": k, "v": v}
