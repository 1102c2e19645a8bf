"""Paged-KV chunked-query attention with online GN-Softmax — Pallas TPU kernel.

The serving engine's block-paged KV pool stores each sequence as a chain of
``block_size``-token blocks scattered through a shared arena; a per-sequence
block *table* maps logical block j to its physical arena slot.  This kernel
streams a *chunk* of queries (decode is the chunk=1 special case) over that
chain exactly like ``gn_attention`` streams over a contiguous row: the k/v
BlockSpec index map reads the physical block id out of a scalar-prefetched
table (so the DMA engine chases the table, no gather materialization in
HBM), and the (max, sum, acc) carries use the same snap-to-Δ-grid
stabilizer:

  * the running max is snapped *up* to the Δ grid, so the online correction
    e^{m_old − m_new} goes through the same LUT unit grid-exactly and the
    per-block accumulation order drops out of the result;
  * the final division acc / l divides the accumulated LUT'd numerators by
    their own sum — Σp = 1 holds to one rounding *independent of the block
    layout*, which is the normalization guarantee the paged pool must not
    break.

Chunked-query contract (the fused serving tick): query row i of sequence n
sits at absolute position ``starts[n] + i`` and attends the logical stream
``[0, starts[n] + i]`` — causal *within* the chunk, full prefix before it —
while KV reads are bounded by ``lengths[n]`` (the post-write context
``starts + n_valid``), so rows past a slot's valid lane count read nothing
beyond what the pool actually allocated.  Their outputs are don't-care and
the caller discards them.

Grid: (n_seqs, q_heads, max_blocks_per_seq), block axis innermost/arbitrary;
GQA maps k/v to head ``h // group``.  Blocks at or past a sequence's context
length are skipped entirely (their table entries may point at recycled or
foreign blocks — never read).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.luts import SoftmaxLUTConfig, TPU_SOFTMAX_LUT
from repro.kernels.common import factorized_exp, snap_up_to_grid

NEG_INF = -1e30


def _gn_paged_attention_kernel(
    tables_ref,  # scalar prefetch: (N, max_bt) int32 physical block ids
    starts_ref,  # scalar prefetch: (N,) int32 absolute position of q row 0
    lens_ref,  # scalar prefetch: (N,) int32 post-write context lengths
    *refs,
    # quantized=True prepends two scalar-prefetch refs to ``refs``:
    #   kscale_ref,  # (nb,) f32 per-physical-block K dequant scales
    #   vscale_ref,  # (nb,) f32 per-physical-block V dequant scales
    # then, in both modes:
    #   q_ref,  # (1, 1, bq, d) — rows [0, chunk) are the chunk queries
    #   k_ref,  # (1, 1, bs_p, d) — physical block tables_ref[n, j]
    #   v_ref,  # (1, 1, bs_p, d)
    #   o_ref,  # (1, 1, bq, d)
    #   acc_ref,  # (bq, d) f32 scratch
    #   m_ref,  # (bq, 128) f32 scratch
    #   l_ref,  # (bq, 128) f32 scratch
    cfg: SoftmaxLUTConfig,
    sm_scale: float,
    block_size: int,  # true tokens per block (bs_p >= block_size is padding)
    block_pad: int,
    quantized: bool = False,
):
    if quantized:
        kscale_ref, vscale_ref = refs[:2]
        refs = refs[2:]
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    n = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    start = starts_ref[n]
    length = lens_ref[n]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # per-block dequant AFTER the DMA: the int8 tile is what
            # streamed in; multiply by the physical block's frozen scale
            # (the same clamped table index the BlockSpec DMA'd from)
            last = jnp.maximum((length - 1) // block_size, 0)
            phys = tables_ref[n, jnp.minimum(j, last)]
            k = k * kscale_ref[phys]
            v = v * vscale_ref[phys]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bs_p)
        bq, bs_p = s.shape

        # mask: query row qi (absolute position start + qi) attends absolute
        # column j*block_size + r iff the column is causally visible
        # (col <= start + qi), inside the written context (col < length), and
        # not in the padded tail rows (r >= block_size) of the physical block
        qi = jax.lax.broadcasted_iota(jnp.int32, (bq, bs_p), 0)
        r = jax.lax.broadcasted_iota(jnp.int32, (bq, bs_p), 1)
        col = j * block_size + r
        mask = (r < block_size) & (col < length) & (col <= start + qi)
        s = jnp.where(mask, s, NEG_INF)

        m_old = m_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = snap_up_to_grid(jnp.maximum(m_old, m_cur), cfg)
        any_valid = jnp.max(mask.astype(jnp.int32), axis=-1, keepdims=True) > 0
        m_new = jnp.where(any_valid | (m_old > NEG_INF / 2), m_new, m_old)

        corr_delta = jnp.clip(m_new - m_old, 0.0, cfg.step * (cfg.max_delta_int + 1))
        corr = factorized_exp(corr_delta, cfg)
        corr = jnp.where(m_old > NEG_INF / 2, corr, 0.0)

        y = factorized_exp(jnp.maximum(m_new - s, 0.0), cfg)
        y = jnp.where(mask & (m_new > NEG_INF / 2), y, 0.0)

        l_new = l_ref[:, :1] * corr + jnp.sum(y, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            y, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # skip blocks wholly past the context: their table entries may name
    # recycled/foreign blocks and must never be read
    pl.when(j * block_size < length)(_body)

    @pl.when(j == nj - 1)
    def _fini():
        # guaranteed normalization: same LUT'd numerators over their own sum
        l = l_ref[:, :1]
        l = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] * (1.0 / l)).astype(o_ref.dtype)

    del block_pad  # layout bookkeeping lives in ops.py


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "sm_scale", "block_size", "interpret"),
)
def gn_paged_attention_pallas(
    q: jax.Array,  # (N, H, bq, d) — rows [0, chunk) are the chunk queries
    k_arena: jax.Array,  # (nb, Hkv, bs_p, d)
    v_arena: jax.Array,  # (nb, Hkv, bs_p, d)
    tables: jax.Array,  # (N, max_bt) int32
    starts: jax.Array,  # (N,) int32 absolute position of query row 0
    lengths: jax.Array,  # (N,) int32 post-write context lengths
    cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT,
    sm_scale: float | None = None,
    block_size: int | None = None,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # (nb,) f32 per-block dequant scales
    v_scale: jax.Array | None = None,  # (nb,) f32
) -> jax.Array:
    n, h, bq, d = q.shape
    nb, hkv, bs_p, _ = k_arena.shape
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    group = h // hkv
    max_bt = tables.shape[1]
    block_size = bs_p if block_size is None else block_size
    if sm_scale is None:
        sm_scale = d**-0.5
    quantized = k_scale is not None

    grid = (n, h, max_bt)
    kernel = functools.partial(
        _gn_paged_attention_kernel,
        cfg=cfg,
        sm_scale=float(sm_scale),
        block_size=int(block_size),
        block_pad=bs_p - block_size,
        quantized=quantized,
    )

    # index maps take *_ so the same lambdas serve both prefetch arities
    # (3 scalars fp, 5 scalars with the two per-block scale vectors)
    def kv_index(n_, h_, j, tbl, starts_, lens, *_):
        # clamp skipped grid steps (j past the sequence's last valid block)
        # to the last valid logical block: the kernel's pl.when already
        # skips their compute, and a repeated index lets the pipeline elide
        # the redundant DMA instead of streaming dead blocks for the whole
        # max_bt tail of every short sequence
        last = jnp.maximum((lens[n_] - 1) // block_size, 0)
        return (tbl[n_, jnp.minimum(j, last)], h_ // group, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if quantized else 3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda n_, h_, j, *_: (n_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bs_p, d), kv_index),
            pl.BlockSpec((1, 1, bs_p, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda n_, h_, j, *_: (n_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    scalars = (tables, starts, lengths)
    if quantized:
        scalars = scalars + (
            k_scale.astype(jnp.float32),
            v_scale.astype(jnp.float32),
        )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, q, k_arena, v_arena)
