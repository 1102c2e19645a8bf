"""Paged-KV chunked-query attention with online GN-Softmax — Pallas TPU kernel.

The serving engine's block-paged KV pool stores each sequence as a chain of
``block_size``-token blocks scattered through a shared arena; a per-sequence
block *table* maps logical block j to its physical arena slot.  This kernel
streams a *chunk* of queries (decode is the chunk=1 special case) over that
chain: the k/v BlockSpec index maps read physical block ids out of a
scalar-prefetched table (the DMA engine chases the table, nothing is
gathered in HBM), and the (max, sum, acc) carries use the snap-to-Δ-grid
stabilizer:

  * the running max is snapped *up* to the Δ grid, so the online correction
    e^{m_old − m_new} goes through the same LUT unit grid-exactly;
  * the final division acc / l divides the accumulated LUT'd numerators by
    their own sum — Σp = 1 holds to one rounding *independent of the block
    layout*, which is the normalization guarantee the paged pool must not
    break.  The MXU takes bf16 operands (f32 accumulation); a numerator
    enters P·V as two bf16 terms that hold it exactly, so the weights
    applied to V are the very numerators l sums.

No score, probability or gathered V tile leaves VMEM.

Chunked-query contract (the fused serving tick): query row i of sequence n
sits at absolute position ``starts[n] + i`` and attends the logical stream
``[0, starts[n] + i]`` — causal *within* the chunk, full prefix before it —
while KV reads are bounded by ``lengths[n]`` (the post-write context
``starts + n_valid``).  Rows past a slot's valid lane count are don't-care
and the caller discards them: a slot whose live rows fit one 8-row tile (a
decoding lane) computes that tile alone, a parked lane computes nothing.

Layout.  The arena arrives as (num_blocks, block_size * KV, D): the pool's
(num_blocks, block_size, KV, D) arena with its two middle axes merged, which
is the same bytes under the TPU's tiled layout, so no copy of the arena is
made.  A physical block is one contiguous (block_size * KV, D) tile, rows
token-major and head-minor; the kernel pulls KV head h's rows out of VMEM
with a strided load (packed bf16/int8 rows through a 32-bit view).  Queries
arrive in f32 as (N, KV, R, D), row r = c * group + g for chunk lane c and
query head h * group + g, so all ``group`` query heads of a KV head share
its K/V tiles.

Grid: (n_seqs, steps); one step covers ``STEP_TOKENS // block_size``
consecutive logical blocks of every KV head, so each K/V tile is DMA'd
once.  Steps wholly past a sequence's context are skipped and their DMA
elided (the index maps repeat the last live step's blocks).  Inside a step
the online update runs block by block, exactly as one block per step would:
the step layout changes no result.

Sentinel statistics: besides the output, the kernel writes one f32 per query
row and KV head (lane h of a (R, 128) tile): the normalization residual
|l · (1/l) − 1| of its own arithmetic, forced to +inf when the row's live
scores hold a nonfinite value (their running sum is nonfinite), when its
output is nonfinite, or when l is not a positive finite number.
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
LANE = 128
SUBLANE = 8
# tokens of context one grid step covers (block_size * blocks_per_step): on a
# v5e the docqa tick's read took 0.90 / 0.72 / 0.65 ms a layer at 256 / 512 /
# 1024 tokens a step
STEP_TOKENS = 1024
VMEM_LIMIT = 64 * 1024 * 1024


def _load_head(ref, h, n_tok: int, kv: int) -> jax.Array:
    """KV head h's (n_tok, D) rows of one arena block as f32.

    ``ref`` is a (1, n_tok * kv, D) VMEM tile, rows token-major and head-minor.
    32-bit rows load with a stride; packed rows (bf16, int8) are read through
    a 32-bit view, whose word w of row group r holds rows p*r .. p*r + p - 1
    from the low bits up, and the head's sub-word is shifted out.
    """
    dtype = jnp.dtype(ref.dtype)
    pack = 4 // dtype.itemsize
    if pack == 1:
        return ref[0, pl.ds(h, n_tok, stride=kv), :].astype(jnp.float32)
    words = ref.bitcast(jnp.int32)[0, pl.ds(h // pack, n_tok, stride=kv // pack), :]
    bits = 8 * dtype.itemsize
    top = words << (bits * (pack - 1 - h % pack))  # sub-word to the top bits
    if dtype == jnp.bfloat16:
        return pltpu.bitcast(top & jnp.int32(-(1 << 16)), jnp.float32)
    if dtype == jnp.int8:
        return (top >> 24).astype(jnp.float32)  # arithmetic: sign-extends
    raise NotImplementedError(f"arena dtype {dtype}")


def _gn_paged_attention_kernel(
    tables_ref,  # scalar prefetch: (N, max_bt) int32 physical block ids
    starts_ref,  # scalar prefetch: (N,) int32 absolute position of q row 0
    lens_ref,  # scalar prefetch: (N,) int32 post-write context lengths
    *refs,
    # quantized=True prepends two scalar-prefetch refs to ``refs``:
    #   kscale_ref, vscale_ref: (nb,) f32 per-physical-block dequant scales
    # then, in both modes:
    #   q_ref: (KV, R, D) f32, this sequence's queries
    #   k_refs, v_refs: blocks_per_step (1, block_size * KV, D) tiles each
    #   o_ref: (KV, R, D); st_ref: (R, 128) f32 sentinel statistics
    # scratch: m, l, ssum (KV, R, 128) f32; acc (KV, R, D) f32;
    #   kh (step tokens, D) compute dtype — one head's K rows of the step
    cfg: SoftmaxLUTConfig,
    sm_scale: float,
    block_size: int,
    kv: int,
    group: int,
    blocks_per_step: int,
    row_tiers: tuple[int, ...],
    quantized: bool,
):
    kscale_ref = vscale_ref = None
    if quantized:
        kscale_ref, vscale_ref = refs[:2]
        refs = refs[2:]
    bps = blocks_per_step
    q_ref = refs[0]
    k_refs = refs[1 : 1 + bps]
    v_refs = refs[1 + bps : 1 + 2 * bps]
    o_ref, st_ref, m_ref, l_ref, ss_ref, acc_ref, kh_ref = refs[1 + 2 * bps :]
    cdt = kh_ref.dtype
    bs = block_size
    width = bps * bs
    n = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    start = starts_ref[n]
    length = lens_ref[n]
    live_rows = (length - start) * group
    last = jnp.maximum((length - 1) // bs, 0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def head_tile(refs_, scale_ref, h, i):
        """KV head h's rows of the step's block i in the compute dtype,
        dequantized after the DMA by the physical block's frozen scale (the
        same clamped table entry the index map DMA'd from), in the compute
        dtype as the streamed read dequantizes."""
        x = _load_head(refs_[i], h, bs, kv).astype(cdt)
        if not quantized:
            return x
        scale = scale_ref[tables_ref[n, jnp.minimum(j * bps + i, last)]]
        return x * scale.astype(cdt)

    def pv(y, v):
        """y @ v with f32 accumulation.  A bf16 y is carried as two bf16
        terms, hi + lo, which hold a LUT'd numerator (a multiple of 2^-15 in
        [0, 1]) exactly: the product is the one the f32 numerators give."""
        dims = (((1,), (0,)), ((), ()))
        if cdt == jnp.float32:
            return jax.lax.dot_general(y, v, dims, preferred_element_type=jnp.float32)
        hi = y.astype(cdt)
        lo = (y - hi.astype(jnp.float32)).astype(cdt)
        return (jax.lax.dot_general(hi, v, dims, preferred_element_type=jnp.float32)
                + jax.lax.dot_general(lo, v, dims, preferred_element_type=jnp.float32))

    def step(rows: int):
        """The online update of one step for query rows [0, rows)."""
        chunk_lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        visible = start + chunk_lane  # last column each row may attend
        col = j * width + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        mask = (col < length) & (col <= visible)
        blk_of_col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // bs

        def head(h, carry):
            for i in range(bps):
                kh_ref[pl.ds(i * bs, bs), :] = head_tile(k_refs, kscale_ref, h, i)
            q = q_ref[h, :rows, :].astype(cdt)
            s = jax.lax.dot_general(
                q, kh_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (rows, width)
            # scaled in the compute dtype, as the streamed read and the
            # model's unpaged attention scale their scores
            s = (s.astype(cdt) * jnp.asarray(sm_scale, cdt)).astype(jnp.float32)
            s = jnp.where(mask, s, NEG_INF)
            # running sum of the live scores: nonfinite iff one of them is
            ss_ref[h, :rows, :] = ss_ref[h, :rows, :] + jnp.sum(
                jnp.where(mask, s, 0.0), axis=-1, keepdims=True
            )

            # the running max after each block of the step, as one block per
            # step would carry it
            m_prev = m_ref[h, :rows, :1]
            ms, m = [], m_prev
            for i in range(bps):
                b0 = j * width + i * bs  # the block's first column
                any_valid = (b0 < length) & (b0 <= visible)
                m_cur = jnp.max(s[:, i * bs : (i + 1) * bs], axis=-1, keepdims=True)
                m_new = snap_up_to_grid(jnp.maximum(m, m_cur), cfg)
                m = jnp.where(any_valid | (m > NEG_INF / 2), m_new, m)
                ms.append(m)
            m_col = ms[0]
            for i in range(1, bps):
                m_col = jnp.where(blk_of_col >= i, ms[i], m_col)
            y = factorized_exp(jnp.maximum(m_col - s, 0.0), cfg)
            y = jnp.where(mask & (m_col > NEG_INF / 2), y, 0.0)

            # each block's correction e^{m_before - m_after}, one LUT pass
            # over a (rows, blocks) tile rather than one per (rows, 1) column
            cap = cfg.step * (cfg.max_delta_int + 1)
            blk = jax.lax.broadcasted_iota(jnp.int32, (rows, bps), 1)
            delta = jnp.zeros((rows, bps), jnp.float32)
            before = jnp.full((rows, bps), NEG_INF, jnp.float32)
            for i, (m0, m1) in enumerate(zip([m_prev] + ms[:-1], ms)):
                delta = jnp.where(blk == i, jnp.clip(m1 - m0, 0.0, cap), delta)
                before = jnp.where(blk == i, m0, before)
            corrs = jnp.where(before > NEG_INF / 2, factorized_exp(delta, cfg), 0.0)

            l = l_ref[h, :rows, :1]
            acc = acc_ref[h, :rows, :]
            for i in range(bps):
                corr = corrs[:, i : i + 1]
                y_i = y[:, i * bs : (i + 1) * bs]
                l = l * corr + jnp.sum(y_i, axis=-1, keepdims=True)
                acc = acc * corr + pv(y_i, head_tile(v_refs, vscale_ref, h, i))
            m_ref[h, :rows, :] = jnp.broadcast_to(ms[-1], (rows, LANE))
            l_ref[h, :rows, :] = jnp.broadcast_to(l, (rows, LANE))
            acc_ref[h, :rows, :] = acc
            return carry

        jax.lax.fori_loop(0, kv, head, 0)

    # the smallest row tier holding every live row runs; a parked lane (no
    # live row) runs none
    @pl.when(j * width < length)
    def _compute():
        lo = 0
        for rows in row_tiers:
            pl.when((live_rows > lo) & (live_rows <= rows))(
                functools.partial(step, rows)
            )
            lo = rows

    @pl.when(j == nj - 1)
    def _fini():
        # guaranteed normalization: the same LUT'd numerators over their
        # own sum, and the sentinel's view of that sum
        l = l_ref[:, :, :1]
        ok = (l > 0) & jnp.isfinite(l)
        inv = 1.0 / jnp.where(ok, l, 1.0)
        out = (acc_ref[...] * inv).astype(o_ref.dtype)
        o_ref[...] = out
        bad = (
            ~ok
            | ~jnp.isfinite(ss_ref[:, :, :1])
            | jnp.any(~jnp.isfinite(out.astype(jnp.float32)), axis=-1, keepdims=True)
        )
        stat = jnp.where(bad, jnp.inf, jnp.abs(l * inv - 1.0))  # (KV, R, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, st_ref.shape, 1)
        st = jnp.zeros(st_ref.shape, jnp.float32)
        for h in range(kv):
            st = jnp.where(lane == h, stat[h], st)
        st_ref[...] = st


def row_tiers(rows: int) -> tuple[int, ...]:
    """Row counts a step may compute: one 8-row sublane tile, which holds a
    decoding lane's query heads, or every row.  Each tier is a copy of the
    step body for the compiler, and a prefill chunk is full but for a
    prompt's last one, so no tier lies between."""
    return (SUBLANE, rows) if rows > SUBLANE else (rows,)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "sm_scale", "block_size", "group", "compute_dtype",
                     "out_dtype", "interpret"),
)
def gn_paged_attention_pallas(
    q: jax.Array,  # (N, KV, R, D) f32, row r = c * group + g
    k_arena: jax.Array,  # (nb, block_size * KV, D) — pool arena, axes merged
    v_arena: jax.Array,  # (nb, block_size * KV, D)
    tables: jax.Array,  # (N, max_bt) int32
    starts: jax.Array,  # (N,) int32 absolute position of query row 0
    lengths: jax.Array,  # (N,) int32 post-write context lengths
    cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT,
    sm_scale: float = 1.0,
    block_size: int = 1,
    group: int = 1,  # query heads per KV head
    compute_dtype=jnp.float32,  # MXU operand dtype (f32 accumulation)
    out_dtype=jnp.float32,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # (nb,) f32 per-block dequant scales
    v_scale: jax.Array | None = None,  # (nb,) f32
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (N, KV, R, D) ``out_dtype``, stats (N, R, 128) f32)."""
    n, kv, rows, d = q.shape
    nb, rows_per_block, _ = k_arena.shape
    bs = block_size
    if rows_per_block != bs * kv:
        raise ValueError(f"arena rows {rows_per_block} != {bs} tokens * {kv} heads")
    if rows % SUBLANE or kv > LANE:
        raise ValueError(f"{rows} query rows not a multiple of {SUBLANE}, "
                         f"or {kv} KV heads > {LANE}")
    pack = 4 // jnp.dtype(k_arena.dtype).itemsize
    if kv % pack:
        raise ValueError(f"{kv} KV heads do not fill {k_arena.dtype}'s 32-bit words")
    max_bt = tables.shape[1]
    bps = min(max_bt, max(1, STEP_TOKENS // bs))
    steps = pl.cdiv(max_bt, bps)
    quantized = k_scale is not None

    kernel = functools.partial(
        _gn_paged_attention_kernel,
        cfg=cfg,
        sm_scale=sm_scale,
        block_size=bs,
        kv=kv,
        group=group,
        blocks_per_step=bps,
        row_tiers=row_tiers(rows),
        quantized=quantized,
    )

    # index maps take *_ so the same lambdas serve both prefetch arities
    # (3 scalars fp, 5 with the two per-block scale vectors)
    def kv_index(i):
        def index(n_, j, tbl, starts_, lens, *_):
            # a step past the context repeats the last live step's blocks
            # and a block past it the last live block: the kernel skips
            # their compute, and a repeated index elides their DMA
            last = jnp.maximum((lens[n_] - 1) // bs, 0)
            j = jnp.minimum(j, last // bps)
            return (tbl[n_, jnp.minimum(j * bps + i, last)], 0, 0)
        return index

    kv_spec = [pl.BlockSpec((1, bs * kv, d), kv_index(i)) for i in range(bps)]
    per_seq = lambda n_, j, *_: (n_, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if quantized else 3,
        grid=(n, steps),
        in_specs=[pl.BlockSpec((None, kv, rows, d), per_seq)] + kv_spec + kv_spec,
        out_specs=[
            pl.BlockSpec((None, kv, rows, d), per_seq),
            pl.BlockSpec((None, rows, LANE), lambda n_, j, *_: (n_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv, rows, LANE), jnp.float32),  # m
            pltpu.VMEM((kv, rows, LANE), jnp.float32),  # l
            pltpu.VMEM((kv, rows, LANE), jnp.float32),  # live-score sum
            pltpu.VMEM((kv, rows, d), jnp.float32),  # acc
            pltpu.VMEM((bps * bs, d), compute_dtype),  # one head's K rows
        ],
    )
    scalars = (tables, starts, lengths)
    if quantized:
        scalars = scalars + (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((n, kv, rows, d), out_dtype),
            jax.ShapeDtypeStruct((n, rows, LANE), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*scalars, q, *([k_arena] * bps), *([v_arena] * bps))
