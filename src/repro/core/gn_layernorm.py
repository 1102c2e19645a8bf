"""GN-LayerNorm — the paper's Algorithm 2 (CoRN-LN) as a composable JAX op.

The costly 1/sqrt is replaced by a Newton iteration whose initial guess comes
from a Leading-One-Detector (exponent extraction) refined by a small mantissa
LUT — the "compressed" CoRN table.  Reformulated in reciprocal-square-root
form, every Newton step and the output stage are multiplications only:

    x_{k+1} = x_k * (1.5 - 0.5 * n * x_k^2)          (mul-only NR for 1/sqrt n)

which is the division-free realization of the paper's Eq. (5) fixed point
(attractor 1/sqrt(n)).  Unit variance is guaranteed to the rsqrt's relative
error: with a 16-entry mantissa LUT and 2 iterations, |1 - sigma| < ~1e-6.

Variants:
* :func:`gn_layernorm`      — full LN (mean subtraction), paper-faithful.
* :func:`gn_rmsnorm`        — sigma-guaranteed RMSNorm for llama-family archs
                              (mean path disabled; Newton unit unchanged).
* :func:`gn_layernorm_hwsim`— bit-accurate integer datapath (Q8.8 in, Q.16
                              Newton, integer LOD) for accuracy experiments.
* :func:`exact_layernorm`   — FP32 oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fxp
from repro.core import luts
from repro.core.luts import INV_SQRT2, PAPER_RSQRT, RsqrtConfig


def exact_layernorm(x, gamma=None, beta=None, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(x.dtype)


def exact_rmsnorm(x, gamma=None, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps)
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    return y.astype(x.dtype)


def newton_rsqrt(n: jax.Array, cfg: RsqrtConfig = PAPER_RSQRT) -> jax.Array:
    """CoRN reciprocal square root: LOD + mantissa LUT + mul-only NR steps.

    n: positive float32.  TPU-native LOD = exponent-field extraction (bitcast
    and mask), the direct analogue of a hardware priority encoder.
    """
    n32 = n.astype(jnp.float32)
    e = fxp.float_lod(n32)                         # floor(log2 n)
    idx = fxp.float_mantissa_index(n32, cfg.mantissa_bits)
    lut = jnp.asarray(luts.rsqrt_mantissa_lut(cfg))
    m_r = lut[idx]                                 # ~ 1/sqrt(mantissa)
    e_half = e >> 1                                # arithmetic shift == floor
    odd = (e & 1).astype(jnp.float32)
    # 2^{-e_half} built by exponent-field assembly (no transcendental).
    pow_bits = (127 - e_half) << 23
    pow2 = jax.lax.bitcast_convert_type(pow_bits.astype(jnp.int32), jnp.float32)
    x0 = m_r * pow2 * jnp.where(odd > 0, jnp.float32(INV_SQRT2), jnp.float32(1.0))
    x = x0
    for _ in range(cfg.iters):
        x = x * (1.5 - 0.5 * n32 * x * x)
    return x


@functools.partial(jax.custom_jvp, nondiff_argnums=(3, 4))
def _gn_normalize(x, gamma, beta, cfg: RsqrtConfig, subtract_mean: bool):
    x32 = x.astype(jnp.float32)
    if subtract_mean:
        # Algorithm 2 accumulates E[x], E[x^2] in *exact* integer accumulators;
        # the float32-faithful equivalent of that exactness is the centered
        # (cancellation-free) form.  The hw-sim path keeps the literal
        # one-pass E[x^2]-E[x]^2 in wide integers.  (DESIGN.md §2.)
        ex = jnp.mean(x32, axis=-1, keepdims=True)
        centered = x32 - ex
        var = jnp.mean(jnp.square(centered), axis=-1, keepdims=True)
    else:
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        centered = x32
    rstd = newton_rsqrt(var + 1e-8, cfg)
    y = centered * rstd
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(x.dtype)


@_gn_normalize.defjvp
def _gn_normalize_jvp(cfg, subtract_mean, primals, tangents):
    """Straight-through tangent: exact norm Jacobian at the approx normalizer."""
    x, gamma, beta = primals
    dx, dgamma, dbeta = tangents
    x32 = x.astype(jnp.float32)
    if subtract_mean:
        ex = jnp.mean(x32, axis=-1, keepdims=True)
        centered = x32 - ex
        var = jnp.mean(jnp.square(centered), axis=-1, keepdims=True)
    else:
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        centered = x32
    rstd = newton_rsqrt(var + 1e-8, cfg)
    xhat = centered * rstd

    dx32 = jnp.zeros_like(x32) if _is_sym_zero(dx) else dx.astype(jnp.float32)
    if subtract_mean:
        dmu = jnp.mean(dx32, axis=-1, keepdims=True)
        dc = dx32 - dmu
    else:
        dc = dx32
    # d xhat = r*(dc - xhat * mean(xhat*dc))   [exact LN/RMS tangent at xhat]
    proj = jnp.mean(xhat * dc, axis=-1, keepdims=True)
    dxhat = rstd * (dc - xhat * proj)

    y = xhat
    dy = dxhat
    if gamma is not None:
        g32 = gamma.astype(jnp.float32)
        dg = jnp.zeros_like(g32) if _is_sym_zero(dgamma) else dgamma.astype(jnp.float32)
        dy = dy * g32 + xhat * dg
        y = y * g32
    if beta is not None:
        b32 = beta.astype(jnp.float32)
        db = jnp.zeros_like(b32) if _is_sym_zero(dbeta) else dbeta.astype(jnp.float32)
        dy = dy + db
        y = y + b32
    return y.astype(x.dtype), dy.astype(x.dtype)


def _is_sym_zero(t) -> bool:
    from jax.custom_derivatives import SymbolicZero  # local import: private-ish

    return isinstance(t, SymbolicZero) or (
        hasattr(jax.interpreters.ad, "Zero") and isinstance(t, jax.interpreters.ad.Zero)
    )


def gn_layernorm(x, gamma=None, beta=None, cfg: RsqrtConfig = PAPER_RSQRT):
    """Algorithm 2: sigma-guaranteed LayerNorm (mean subtraction on)."""
    return _gn_normalize(x, gamma, beta, cfg, True)


def gn_rmsnorm(x, gamma=None, cfg: RsqrtConfig = PAPER_RSQRT):
    """sigma-guaranteed RMSNorm (GN applied to llama-family norms)."""
    return _gn_normalize(x, gamma, None, cfg, False)


# --- Bit-accurate integer datapath (Fig. 4) ----------------------------------

def _int_rsqrt_q16(v: jax.Array, cfg: RsqrtConfig) -> jax.Array:
    """Integer CoRN rsqrt.  v: int64 variance in Q.16 (>0).  Returns Q.16.

    LOD (priority encoder) -> mantissa LUT -> ``cfg.iters`` integer NR steps:
        x <- x * (3*2^16 - ((v*x >> 16) * x >> 16)) >> 17
    """
    p = fxp.lod(v.astype(jnp.int32) | 1)           # leading-one position
    e = p - 16                                     # real exponent of n = v/2^16
    mb = cfg.mantissa_bits
    # mantissa bits just below the leading one (guard for small p)
    sh = jnp.maximum(p - mb, 0)
    idx = ((v >> sh) & ((1 << mb) - 1)).astype(jnp.int32)
    import numpy as np

    lut_q16 = jnp.asarray(
        np.round(luts.rsqrt_mantissa_lut(cfg) * (1 << 16)).astype("int64")
    )
    x = lut_q16[idx]                               # Q.16 of 1/sqrt(mantissa)
    h = e >> 1
    o = e & 1
    inv_sqrt2_q16 = jnp.int64(round(INV_SQRT2 * (1 << 16)))
    x = jnp.where(o == 1, (x * inv_sqrt2_q16) >> 16, x)
    # scale by 2^{-h} (clamped shifts: both jnp.where branches are evaluated)
    x = jnp.where(h >= 0, x >> jnp.maximum(h, 0), x << jnp.maximum(-h, 0))
    three = jnp.int64(3 << 16)

    for _ in range(cfg.iters):
        nx = (v * x) >> 16
        nxx = (nx * x) >> 16
        x = (x * (three - nxx)) >> 17
    return x


def gn_layernorm_hwsim(
    x, gamma=None, beta=None, cfg: RsqrtConfig = PAPER_RSQRT, subtract_mean: bool = True
):
    """Fig. 4 integer datapath: Q8.8 input, wide accumulators, integer CoRN."""
    q = fxp.LN_IN_Q
    xi32 = q.quantize(x.astype(jnp.float32))                     # Q8.8 int32
    c = x.shape[-1]
    with jax.enable_x64(True):
        xi = xi32.astype(jnp.int64)
        ex = jnp.sum(xi, axis=-1, keepdims=True) // c            # Q8.8 mean
        ex2 = jnp.sum(xi * xi, axis=-1, keepdims=True) // c      # Q.16
        if subtract_mean:
            var = jnp.maximum(ex2 - ex * ex, 1)                  # Q.16
            centered = xi - ex
        else:
            var = jnp.maximum(ex2, 1)
            centered = xi
        rstd = _int_rsqrt_q16(var, cfg)                          # Q.16
        # output stage: multiplier + round-to-nearest, Q8.8 out
        y_q8 = (centered * rstd + (jnp.int64(1) << 15)) >> 16
        y = y_q8.astype(jnp.float32) / q.scale
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(x.dtype)
