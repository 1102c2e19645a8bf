"""Fixed-point (Q-format) arithmetic simulation for the hardware units.

The paper's datapath is INT8-in / fixed-point-internal.  We simulate it
bit-accurately with int32 JAX arrays so accuracy experiments measure the
*hardware's* numbers, not a float approximation of them.

Conventions
-----------
A Q(f) value stores ``round(x * 2**f)`` as an integer; ``f`` is the number of
fractional bits.  All helpers are pure and jit-safe.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class QFormat:
    """Fixed-point format: ``total_bits`` wide, ``frac_bits`` fractional."""

    total_bits: int
    frac_bits: int
    signed: bool = False

    @property
    def scale(self) -> float:
        return float(2 ** self.frac_bits)

    @property
    def max_int(self) -> int:
        if self.signed:
            return 2 ** (self.total_bits - 1) - 1
        return 2 ** self.total_bits - 1

    @property
    def min_int(self) -> int:
        if self.signed:
            return -(2 ** (self.total_bits - 1))
        return 0

    def quantize(self, x: jax.Array) -> jax.Array:
        """Float -> saturating Q(f) integer (round-to-nearest-even)."""
        q = jnp.round(x * self.scale)
        q = jnp.clip(q, self.min_int, self.max_int)
        return q.astype(jnp.int32)

    def dequantize(self, q: jax.Array) -> jax.Array:
        return q.astype(jnp.float32) / self.scale


# Formats used by the paper-faithful datapath ---------------------------------
# Softmax: Δ is the INT-domain stabilized logit, y the LUT product, Z the sum.
DELTA_Q = QFormat(total_bits=8, frac_bits=0)          # INT8 Δ (paper Table III)
LUT_Q = QFormat(total_bits=16, frac_bits=15)          # LUT entries in Q1.15
PROD_Q = QFormat(total_bits=16, frac_bits=15)         # y = a*b, renormalized
RECIP_BITS = 24  # D_max = 2**24 in FxP_Div (24-bit probabilities: the paper's
#                  sub-2e-7 Fig.5 normalization errors require >=24-bit rescale)
# LayerNorm: inputs Q8.8, accumulators wide.
LN_IN_Q = QFormat(total_bits=16, frac_bits=8, signed=True)
LN_STD_Q = QFormat(total_bits=24, frac_bits=16)


def shift_subtract_div(numer: jax.Array, denom: jax.Array, out_bits: int) -> jax.Array:
    """Restoring (shift-subtract) integer division: floor(numer << out_bits / denom).

    This is the FxP_Div primitive of the paper: one sequential divider shared
    per row, producing an ``out_bits``-fractional-bit reciprocal scale.  We
    simulate the restoring-division loop with a fori_loop over bit positions so
    the result is bit-exact with the RTL (floor division), not a float rcp.

    numer/denom: int32 (denom > 0).  Returns int32 quotient with ``out_bits``
    fractional bits.  Inputs must satisfy numer << out_bits < 2**62 — callers
    keep numer in <= 30 bits.
    """
    with jax.enable_x64(True):
        numer = jnp.asarray(numer).astype(jnp.int64)
        denom = jnp.asarray(denom).astype(jnp.int64)
        numer, denom = jnp.broadcast_arrays(numer, denom)

        # MSB-first restoring division over the virtual numerator
        # N = numer << out_bits.  The partial remainder is shifted (never the
        # divisor), so every intermediate fits comfortably in int64 — exactly
        # like the RTL's shift register.
        total_bits = 46 + out_bits  # numer is kept <= 46 bits by callers

        def body(i, carry):
            rem, quot = carry
            bit_pos = total_bits - 1 - i
            src = bit_pos - out_bits
            nbit = jnp.where(src >= 0, (numer >> jnp.maximum(src, 0)) & 1, 0)
            rem = (rem << 1) | nbit
            take = rem >= denom
            rem = jnp.where(take, rem - denom, rem)
            quot = (quot << 1) | take.astype(jnp.int64)
            return rem, quot

        rem0 = jnp.zeros_like(numer)
        quot0 = jnp.zeros_like(numer)
        _, quot = jax.lax.fori_loop(0, total_bits, body, (rem0, quot0))
        return quot


def lod(x: jax.Array) -> jax.Array:
    """Leading-one detector: position of the highest set bit of int32 x (>=1).

    lod(1) == 0, lod(2) == 1, lod(3) == 1 ...  Hardware LOD is a priority
    encoder; we simulate with a clz-style loop (jit-safe, no float log).
    """
    x = x.astype(jnp.uint32)

    def body(i, carry):
        pos, xs = carry
        has = xs > 1
        pos = jnp.where(has, pos + 1, pos)
        xs = jnp.where(has, xs >> 1, xs)
        return pos, xs

    pos0 = jnp.zeros_like(x, dtype=jnp.int32)
    pos, _ = jax.lax.fori_loop(0, 32, body, (pos0, x))
    return pos


def float_lod(x: jax.Array) -> jax.Array:
    """LOD for positive float32: floor(log2(x)) via exponent-field extraction.

    This is the TPU-native analogue of a hardware leading-one detector —
    bit-cast and mask, no transcendental.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def float_mantissa_index(x: jax.Array, lut_bits: int) -> jax.Array:
    """Top ``lut_bits`` of the float32 mantissa (index into a refinement LUT)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return (bits >> (23 - lut_bits)) & ((1 << lut_bits) - 1)
