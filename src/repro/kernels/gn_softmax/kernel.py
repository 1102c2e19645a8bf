"""GN-Softmax Pallas TPU kernel.

TPU adaptation of the paper's Fig. 3 datapath (see DESIGN.md §2):

* rows stream through VMEM in ``(block_rows, cols)`` tiles — the Pallas
  analogue of the RTL's N-cycle streaming pipeline;
* the two exponential LUTs (7-entry coarse, ``R·2^f``-entry residual) are
  fixed by the LUT config and applied as **selects over their entries** —
  the VPU equivalent of a ROM lookup (TPU has no cheap per-lane gather);
* the single per-row reciprocal (FxP_Div in silicon) is one VPU ``1/z``;
  numerator and denominator use the same approximated ``y``, so ``Σp = 1``.

Lane/sublane alignment: ``cols`` must be a multiple of 128 and ``block_rows``
a multiple of 8 (callers pad; see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.luts import SoftmaxLUTConfig, TPU_SOFTMAX_LUT
from repro.kernels.common import factorized_exp


def _gn_softmax_kernel(x_ref, o_ref, *, cfg: SoftmaxLUTConfig, valid_cols: int):
    x = x_ref[...].astype(jnp.float32)
    rows, cols = x.shape

    # mask padding lanes so they contribute nothing
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    valid = lane < valid_cols
    x = jnp.where(valid, x, jnp.full_like(x, -1e30))

    # (i) max-subtraction stage (stabilizer snapped onto the Δ grid, matching
    # the RTL's integer-domain max; see core/gn_softmax.py)
    m = jnp.max(x, axis=-1, keepdims=True)
    m = jnp.ceil(m * jnp.float32(1.0 / cfg.step)) * jnp.float32(cfg.step)
    delta = jnp.maximum(m - x, 0.0)

    # (ii) exponential stage: Δ-grid quantization + two-LUT factorization
    y = factorized_exp(delta, cfg)
    y = jnp.where(valid, y, 0.0)

    # (iii) normalization stage: one reciprocal per row, shared numerator /
    # denominator => sum(p) == 1 up to the reciprocal rounding.
    z = jnp.sum(y, axis=-1, keepdims=True)
    p = y * (1.0 / z)
    o_ref[...] = p.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("cfg", "block_rows", "interpret", "valid_cols")
)
def gn_softmax_pallas(
    x: jax.Array,
    cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT,
    block_rows: int = 256,
    interpret: bool = False,
    valid_cols: int | None = None,
) -> jax.Array:
    """2D entry point: x (rows, cols_padded); rows % block_rows == 0.

    ``valid_cols``: true (unpadded) width — lanes beyond it are masked out of
    the max and the sum.  Use :func:`repro.kernels.gn_softmax.ops.gn_softmax`
    for arbitrary shapes.
    """
    rows, cols = x.shape
    if valid_cols is None:
        valid_cols = cols
    if rows % block_rows:
        raise ValueError(f"rows {rows} not a multiple of block_rows {block_rows}")
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_gn_softmax_kernel, cfg=cfg, valid_cols=valid_cols),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=interpret,
    )(x)
