"""Flash attention with online GN-Softmax — Pallas TPU kernel.

This is the paper's technique moved to where transformer softmax actually
lives: inside tiled attention.  The RTL's streaming N-cycle pipeline becomes
a (q_block × k_block) VMEM tiling with running (max, sum, acc) carries:

  * numerators are the two-LUT factorized exponentials of Algorithm 1;
  * the running max is snapped *up* to the Δ grid (common.snap_up_to_grid), so
    the online correction factor e^{m_old − m_new} goes through the *same* LUT
    unit grid-exactly, and tiled accumulation equals the one-pass reference up
    to LUT-entry rounding;
  * the final division — acc / l — divides the accumulated LUT'd numerators by
    their own sum: the normalization guarantee (Σp = 1) survives tiling.

Grid: (batch, q_heads, q_blocks, k_blocks), k innermost/arbitrary; GQA is
handled by index-mapping k/v blocks to head ``h // group`` (no KV repetition
in HBM).  Scratch: acc (bq, d), running m/l as (bq, 128) lane-replicated.
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


def _gn_attention_kernel(
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, bk, d)
    v_ref,  # (1, 1, bk, d)
    o_ref,  # (1, 1, bq, d)
    acc_ref,  # (bq, d) f32 scratch
    m_ref,  # (bq, 128) f32 scratch
    l_ref,  # (bq, 128) f32 scratch
    *,
    cfg: SoftmaxLUTConfig,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the diagonal band
    offset = seq_k - seq_q  # KV prefix length (k may be longer than q)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)

        # masks: causal diagonal + right-edge padding of the kv axis
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ik * block_k
        mask = col < seq_k
        if causal:
            row = (
                jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                + iq * block_q
            )
            mask &= col <= (row + offset)
        s = jnp.where(mask, s, NEG_INF)

        m_old = m_ref[:, :1]                                   # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = snap_up_to_grid(jnp.maximum(m_old, m_cur), cfg)
        # all-masked rows (above diagonal): keep m at NEG_INF
        any_valid = jnp.max(mask.astype(jnp.int32), axis=-1, keepdims=True) > 0
        m_new = jnp.where(any_valid | (m_old > NEG_INF / 2), m_new, m_old)

        # correction for previously accumulated numerators: e^{m_old - m_new}
        # through the same LUT unit (grid-exact because both are on-grid).
        corr_delta = jnp.clip(m_new - m_old, 0.0, cfg.step * (cfg.max_delta_int + 1))
        corr = factorized_exp(corr_delta, cfg)
        corr = jnp.where(m_old > NEG_INF / 2, corr, 0.0)       # first block: no history

        y = factorized_exp(jnp.maximum(m_new - s, 0.0), cfg)  # (bq, bk) numerators
        y = jnp.where(mask & (m_new > NEG_INF / 2), y, 0.0)

        l_new = l_ref[:, :1] * corr + jnp.sum(y, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            y, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1 + offset)
        pl.when(run)(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _fini():
        # guaranteed normalization: same LUT'd numerators over their own sum
        l = l_ref[:, :1]
        l = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] * (1.0 / l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "causal",
        "sm_scale",
        "block_q",
        "block_k",
        "interpret",
        "seq_q_valid",
        "seq_k_valid",
    ),
)
def gn_attention_pallas(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    seq_q_valid: int | None = None,
    seq_k_valid: int | None = None,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    group = h // hkv
    if sq % block_q or sk % block_k:
        raise ValueError("padded seq lens must divide block sizes (see ops.py)")
    if sm_scale is None:
        sm_scale = d**-0.5
    seq_q_valid = sq if seq_q_valid is None else seq_q_valid
    seq_k_valid = sk if seq_k_valid is None else seq_k_valid

    grid = (b, h, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _gn_attention_kernel,
        cfg=cfg,
        sm_scale=float(sm_scale),
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_q=seq_q_valid,
        seq_k=seq_k_valid,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
