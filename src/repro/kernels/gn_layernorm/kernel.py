"""GN-LayerNorm (CoRN-LN) Pallas TPU kernel.

Fig. 4's two-stage datapath mapped to a VMEM-tiled kernel:

  stage (i)  — mean & variance over the feature axis (row-local reduction);
  stage (ii) — normalization with the CoRN reciprocal-sqrt:
               LOD == float32 exponent-field extraction (bitcast, mask),
               compressed mantissa LUT == a select over its entries, then
               ``iters`` mul-only Newton steps
               x <- x(1.5 - 0.5 n x^2).

gamma/beta ride along as (1, cols) blocks replicated over the row grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.luts import INV_SQRT2, PAPER_RSQRT, RsqrtConfig, rsqrt_mantissa_lut
from repro.kernels.common import lut_lookup


def _newton_rsqrt_block(n: jax.Array, cfg: RsqrtConfig) -> jax.Array:
    """CoRN rsqrt on an (r, 1) block: LOD + mantissa LUT + NR steps."""
    bits = jax.lax.bitcast_convert_type(n, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127                      # LOD
    idx = (bits >> (23 - cfg.mantissa_bits)) & ((1 << cfg.mantissa_bits) - 1)
    m_r = lut_lookup(idx, rsqrt_mantissa_lut(cfg))
    e_half = e >> 1
    odd = (e & 1).astype(jnp.float32)
    pow2 = jax.lax.bitcast_convert_type(
        ((127 - e_half) << 23).astype(jnp.int32), jnp.float32
    )
    x = m_r * pow2 * jnp.where(odd > 0, jnp.float32(INV_SQRT2), jnp.float32(1.0))
    for _ in range(cfg.iters):
        x = x * (1.5 - 0.5 * n * x * x)
    return x


def _gn_layernorm_kernel(
    x_ref,
    gamma_ref,
    beta_ref,
    o_ref,
    *,
    cfg: RsqrtConfig,
    valid_cols: int,
    subtract_mean: bool,
):
    x = x_ref[...].astype(jnp.float32)
    rows, cols = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    valid = lane < valid_cols
    x = jnp.where(valid, x, 0.0)
    inv_c = jnp.float32(1.0 / valid_cols)

    # stage (i): moments (padding contributes zeros; divide by true C)
    if subtract_mean:
        mu = jnp.sum(x, axis=-1, keepdims=True) * inv_c
        centered = jnp.where(valid, x - mu, 0.0)
    else:
        centered = x
    var = jnp.sum(centered * centered, axis=-1, keepdims=True) * inv_c

    # stage (ii): CoRN reciprocal sqrt + multiply-only output stage
    rstd = _newton_rsqrt_block(var + 1e-8, cfg)
    y = centered * rstd
    y = y * gamma_ref[...].astype(jnp.float32)
    y = y + beta_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_rows", "interpret", "valid_cols", "subtract_mean"),
)
def gn_layernorm_pallas(
    x: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    cfg: RsqrtConfig = PAPER_RSQRT,
    block_rows: int = 256,
    interpret: bool = False,
    valid_cols: int | None = None,
    subtract_mean: bool = True,
) -> jax.Array:
    """2D entry: x (rows, cols_p), gamma/beta (1, cols_p); rows % block == 0."""
    rows, cols = x.shape
    if valid_cols is None:
        valid_cols = cols
    if rows % block_rows:
        raise ValueError(f"rows {rows} not a multiple of block_rows {block_rows}")
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(
            _gn_layernorm_kernel,
            cfg=cfg,
            valid_cols=valid_cols,
            subtract_mean=subtract_mean,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, cols), lambda i: (0, 0)),
            pl.BlockSpec((1, cols), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=interpret,
    )(x, gamma, beta)
