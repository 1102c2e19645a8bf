"""Jit'd public wrappers for paged GN attention (query layout + GQA).

Layout contract with the serving pool: the arena arrives in the pool's
(num_blocks, block_size, KV, dh) layout and reaches the kernel as
(num_blocks, block_size * KV, dh), which is a reshape that moves no bytes.
The head dim is lane-padded only where it is not a multiple of 128, which
copies the arena; the serving dispatch (``attention.paged_read_path``)
sends such configs to the streamed read, so only a read asked for
explicitly pads.  Queries, which are small, are regrouped per KV head in
f32, so the kernel's 8-row slice of a decoder's rows is a whole tile (a
packed dtype's tile has 16 or 32 rows); the output is regrouped back.

``gn_paged_attention_chunk`` is the fused serving tick's entry point: a
(N, C, H, D) query chunk per sequence, causal within the chunk, the prior
context read through the block table.  ``gn_paged_attention`` keeps the
original single-row decode signature as the C=1 special case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.luts import SoftmaxLUTConfig, TPU_SOFTMAX_LUT
from repro.kernels.gn_paged_attention.kernel import gn_paged_attention_pallas

LANE = 128
SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "sm_scale", "interpret", "with_stats"),
)
def gn_paged_attention_chunk(
    q: jax.Array,  # (N, C, H, D) one query chunk per sequence
    k_arena: jax.Array,  # (nb, bs, Hkv, D) — the pool's arena layout
    v_arena: jax.Array,  # (nb, bs, Hkv, D)
    tables: jax.Array,  # (N, max_bt) int32
    starts: jax.Array,  # (N,) int32 absolute position of query row 0
    n_valid: jax.Array,  # (N,) int32 valid lanes (KV read bound; rows past
    #                      it produce don't-care outputs)
    cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT,
    sm_scale: float | None = None,
    interpret: bool = False,
    scales: tuple[jax.Array, jax.Array] | None = None,  # ((nb,), (nb,)) f32
    with_stats: bool = False,
):
    """Chunked-query paged read.  Row i of sequence n attends the logical
    stream [0, starts[n] + i] (causal intra-chunk), bounded by the post-write
    context starts + n_valid.  Returns (N, C, H, D) in q's dtype, which is
    also the MXU operand dtype.

    ``scales`` marks the arenas as int8: per-physical-block dequant scales
    for k and v, applied inside the kernel after each block tile's DMA.

    ``with_stats`` also returns the kernel's per-row sentinel statistics,
    (N, C, H) f32: the row's normalization residual, +inf where its live
    scores or its output are nonfinite (see kernel.py)."""
    n, c, h, d = q.shape
    nb, bs, hkv, _ = k_arena.shape
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    group = h // hkv
    if sm_scale is None:
        sm_scale = d**-0.5  # scale uses the TRUE head dim, not the padded one
    k_scale = v_scale = None
    if scales is not None:
        k_scale, v_scale = scales

    d_p = _round_up(d, LANE)
    rows = _round_up(c * group, SUBLANE)
    qs = q.astype(jnp.float32).reshape(n, c, hkv, group, d)
    qs = qs.transpose(0, 2, 1, 3, 4).reshape(n, hkv, c * group, d)
    qs = jnp.pad(qs, ((0, 0), (0, 0), (0, rows - c * group), (0, d_p - d)))
    if d_p != d:
        pad = ((0, 0), (0, 0), (0, 0), (0, d_p - d))
        k_arena, v_arena = jnp.pad(k_arena, pad), jnp.pad(v_arena, pad)

    out, stats = gn_paged_attention_pallas(
        qs,
        k_arena.reshape(nb, bs * hkv, d_p),
        v_arena.reshape(nb, bs * hkv, d_p),
        tables.astype(jnp.int32),
        starts.astype(jnp.int32),
        (starts + n_valid).astype(jnp.int32),
        cfg=cfg,
        sm_scale=float(sm_scale),
        block_size=bs,
        group=group,
        compute_dtype=q.dtype,
        out_dtype=q.dtype,
        interpret=interpret,
        k_scale=k_scale,
        v_scale=v_scale,
    )
    out = out[:, :, : c * group, :d].reshape(n, hkv, c, group, d)
    out = out.transpose(0, 2, 1, 3, 4).reshape(n, c, h, d)
    if not with_stats:
        return out
    # stats[n, c * group + g, kv head] -> (N, C, H) by query head
    stats = stats[:, : c * group, :hkv].reshape(n, c, group, hkv)
    return out, stats.transpose(0, 1, 3, 2).reshape(n, c, h)


@functools.partial(
    jax.jit, static_argnames=("cfg", "sm_scale", "interpret")
)
def gn_paged_attention(
    q: jax.Array,  # (N, H, D) one decode query per sequence
    k_arena: jax.Array,  # (nb, bs, Hkv, D) — the pool's arena layout
    v_arena: jax.Array,  # (nb, bs, Hkv, D)
    tables: jax.Array,  # (N, max_bt) int32
    lengths: jax.Array,  # (N,) int32 context lengths (incl. the new token)
    cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-row decode read: the C=1 chunk whose query sits at position
    lengths - 1.  Returns (N, H, D)."""
    lengths = lengths.astype(jnp.int32)
    starts = jnp.maximum(lengths - 1, 0)
    out = gn_paged_attention_chunk(
        q[:, None],
        k_arena,
        v_arena,
        tables,
        starts,
        # empty sequences read nothing (all blocks skipped -> zero output),
        # exactly like the pre-chunk decode kernel
        jnp.where(lengths > 0, 1, 0),
        cfg=cfg,
        sm_scale=sm_scale,
        interpret=interpret,
    )
    return out[:, 0]
