"""GN-Softmax — the paper's Algorithm 1 as a composable JAX op.

Three entry points, all row-wise over the last axis:

* :func:`gn_softmax` — float-faithful datapath (default inside models).  Same
  algorithm as the RTL (two-LUT factorized exponential on a fixed-point Δ grid
  + renormalization by the true sum of the approximated numerators) but with
  the integer product carried in float32.  Differentiable via ``custom_jvp``.
* :func:`gn_softmax_hwsim` — bit-accurate INT datapath: int32 LUT entries,
  integer product, shift-subtract FxP_Div.  This is what accuracy experiments
  measure; it matches the RTL number-for-number.
* :func:`exact_softmax` — the FP32 oracle.

The normalization guarantee: probabilities are ``y_i * S`` with a *single*
reciprocal scale ``S ≈ 1/Z``, ``Z = Σ y_i`` of the same approximated ``y`` —
so ``Σ p = Z * S ≈ 1`` regardless of how coarse the exponential approximation
is.  ``|1 − Σp|`` is bounded by the reciprocal's rounding, not by the LUT.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import luts
from repro.core.fixedpoint import RECIP_BITS, shift_subtract_div
from repro.core.luts import RADIX, SoftmaxLUTConfig, TPU_SOFTMAX_LUT


def exact_softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    """FP32 reference softmax (the paper's 'FP32 baseline, ideal')."""
    x32 = x.astype(jnp.float32)
    m = jnp.max(x32, axis=axis, keepdims=True)
    e = jnp.exp(x32 - m)
    return (e / jnp.sum(e, axis=axis, keepdims=True)).astype(x.dtype)


def _round_fxp(y: jax.Array, value_bits: int) -> jax.Array:
    """Round to the Q1.value_bits fixed-point grid (the LUT/register grid)."""
    scale = float(1 << value_bits)
    return jnp.round(y * scale) / scale


def _factorized_exp(delta: jax.Array, cfg: SoftmaxLUTConfig) -> jax.Array:
    """e^{-delta} via the two-LUT factorization on the fixed-point Δ grid.

    delta >= 0, float32.  Returns the fixed-point-rounded product a*b.

    TPU lowering note (perf iteration B1, EXPERIMENTS.md §Perf): the obvious
    ``coarse[frac]`` indexing lowers to a *gather over the whole score
    tensor* — 2.4e14 bytes on the deepseek prefill_32k cell.  The LUT entries
    are by construction ``round_fxp(exp(-grid))``, so we compute them
    arithmetically from the quantized Δ — elementwise exp+round, zero gathers,
    same values.  (The ROM-indexed datapath survives bit-exactly in
    :func:`gn_softmax_hwsim`, which accuracy experiments use.)
    """
    inv_step = 1.0 / cfg.step
    # Quantize Δ to the grid (hardware: Δ arrives already quantized).
    d_int = jnp.round(delta * inv_step).astype(jnp.int32)
    d_int = jnp.clip(d_int, 0, cfg.max_delta_int)
    shift = 3 + cfg.frac_bits               # divide by R*2^f == >> (3+f)
    frac = d_int >> shift                   # coarse index (mul/div-free)
    rem = d_int & (cfg.residual_entries - 1)
    # LUT-entry values, computed instead of loaded: a = lut_coarse[frac],
    # b = lut_residual[rem] with the same Q1.vb rounding as luts.exp_luts.
    a = _round_fxp(
        jnp.exp(frac.astype(jnp.float32) * (-float(RADIX) * cfg.delta_scale)),
        cfg.lut_value_bits,
    )
    b = _round_fxp(jnp.exp(rem.astype(jnp.float32) * -cfg.step), cfg.lut_value_bits)
    # Product is rounded to the LUT fixed-point grid, as the RTL multiplier
    # output register would be.
    return _round_fxp(a * b, cfg.lut_value_bits)


def factorized_exp_ste(delta: jax.Array, cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT) -> jax.Array:
    """:func:`_factorized_exp` with a straight-through backward.

    The streaming (flash) GN-attention path inlines the factorized exponential
    inside a scan, where the custom_jvp of :func:`gn_softmax` does not apply;
    integer quantization would otherwise kill the gradient.  Forward value is
    the fixed-point LUT product; backward is the exact d/dΔ e^{-Δ} evaluated
    at the continuous point.
    """
    cont = jnp.exp(-delta)
    return cont + jax.lax.stop_gradient(_factorized_exp(delta, cfg) - cont)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def gn_softmax(x: jax.Array, cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT) -> jax.Array:
    """Algorithm 1, float-faithful, over the last axis."""
    x32 = x.astype(jnp.float32)
    m = jnp.max(x32, axis=-1, keepdims=True)
    # snap the stabilizer UP onto the Δ grid: in the RTL the inputs are already
    # integer-quantized so max(X) is on-grid by construction; mirroring that
    # here makes tiled/online evaluation (flash attention) bit-consistent with
    # this one-pass form.  The uniform e^{-c} shift cancels in normalization.
    m = jnp.ceil(m / cfg.step) * cfg.step
    delta = jnp.maximum(m - x32, 0.0)        # Δ_i = max(X) − X_i  >= 0
    y = _factorized_exp(delta, cfg)
    z = jnp.sum(y, axis=-1, keepdims=True)
    # FxP_Div (float carrier): one reciprocal per row; numerator and
    # denominator share the same approximated y => Σp = 1 up to rcp rounding.
    p = y * (1.0 / z)
    return p.astype(x.dtype)


@gn_softmax.defjvp
def _gn_softmax_jvp(cfg, primals, tangents):
    """Straight-through Jacobian: exact softmax derivative at the approx p.

    Preserves Σ dp = 0, the tangent of the normalization guarantee.
    """
    (x,) = primals
    (dx,) = tangents
    p = gn_softmax(x, cfg)
    p32 = p.astype(jnp.float32)
    dx32 = dx.astype(jnp.float32)
    inner = jnp.sum(p32 * dx32, axis=-1, keepdims=True)
    dp = p32 * (dx32 - inner)
    return p, dp.astype(p.dtype)


def gn_softmax_hwsim(
    x: jax.Array,
    cfg: SoftmaxLUTConfig = luts.PAPER_SOFTMAX_LUT,
    recip_bits: int = RECIP_BITS,
) -> jax.Array:
    """Bit-accurate integer datapath of Fig. 3 (max-sub -> LUTs -> FxP_Div).

    Input is float; the unit quantizes Δ onto its INT grid (in hardware the
    quantizer lives upstream).  All arithmetic after that point is integer and
    matches the RTL: Q1.f LUT entries, integer product with truncation,
    restoring shift-subtract division for the reciprocal scale, shift-add
    rescale with truncation.
    """
    coarse_i, residual_i = luts.exp_luts_int(cfg)
    vb = cfg.lut_value_bits

    x32 = x.astype(jnp.float32)
    m = jnp.max(x32, axis=-1, keepdims=True)
    delta = m - x32
    d_int = jnp.round(delta / cfg.step).astype(jnp.int32)
    d_int = jnp.clip(d_int, 0, cfg.max_delta_int)
    shift = 3 + cfg.frac_bits
    frac = d_int >> shift
    rem = d_int & (cfg.residual_entries - 1)
    with jax.enable_x64(True):
        coarse = jnp.asarray(coarse_i).astype(jnp.int64)
        residual = jnp.asarray(residual_i).astype(jnp.int64)
        a = coarse[frac]                           # Q1.vb int
        b = residual[rem]                          # Q1.vb int
        y = (a * b) >> vb                          # Q1.vb, truncating mul
        z = jnp.sum(y, axis=-1, keepdims=True)     # row sum, wide accumulator
        z = jnp.maximum(z, 1)                      # Δ=0 term guarantees z>=~2^vb
        # FxP_Div: S = floor(2^recip_bits * 2^vb / Z)  (reciprocal in
        # Q.recip_bits of the Q1.vb domain).  One shift-subtract divider per
        # row, then a shift-add rescale of every y.
        s = shift_subtract_div(jnp.int64(1) << vb, z, recip_bits)
        # shift-add rescale with round-to-nearest (add half-ulp before shift)
        p_int = (y * s + (jnp.int64(1) << (vb - 1))) >> vb
        p = p_int.astype(jnp.float32) / float(1 << recip_bits)
    return p.astype(x.dtype)


def gn_log_softmax(x: jax.Array, cfg: SoftmaxLUTConfig = TPU_SOFTMAX_LUT) -> jax.Array:
    """log(gn_softmax) with a numerically safe floor (for perplexity eval)."""
    p = gn_softmax(x, cfg).astype(jnp.float32)
    return jnp.log(jnp.maximum(p, 1e-30))
