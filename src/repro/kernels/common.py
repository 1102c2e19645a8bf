"""Shared in-kernel helpers for the GN Pallas kernels.

The LUT units of the paper: a ROM lookup is a select over the table's
entries, which are fixed by the LUT config and so enter the kernel as scalar
constants; the factorized exponential is two such lookups plus a
fixed-point-rounded product (Eq. 4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import luts as lut_lib
from repro.core.luts import SoftmaxLUTConfig


def lut_lookup(idx: jax.Array, values: np.ndarray) -> jax.Array:
    """ROM lookup ``values[idx]`` as a chain of selects over the entries.

    The entries are trace-time constants, so the result is bit-equal to the
    table; indices outside it read 0.  Selects lower on any tile shape (a
    one-hot matmul needs an (r*c, 1) reshape that Mosaic refuses).
    """
    out = jnp.zeros(idx.shape, jnp.float32)
    for j, v in enumerate(values):
        out = jnp.where(idx == j, jnp.float32(v), out)
    return out


def factorized_exp(delta: jax.Array, cfg: SoftmaxLUTConfig) -> jax.Array:
    """e^{-Δ} on the fixed-point grid via the coarse/residual LUT pair.

    Δ >= 0 float32 (any 2D block shape).  Entries beyond the coarse LUT's
    reach saturate to 0, exactly like the RTL.
    """
    inv_step = jnp.float32(1.0 / cfg.step)
    d_int = jnp.round(delta * inv_step).astype(jnp.int32)
    sat = d_int > cfg.max_delta_int
    d_int = jnp.clip(d_int, 0, cfg.max_delta_int)
    frac = d_int >> (3 + cfg.frac_bits)
    rem = d_int & (cfg.residual_entries - 1)
    coarse, residual = lut_lib.exp_luts(cfg)
    y = lut_lookup(frac, coarse) * lut_lookup(rem, residual)
    scale = jnp.float32(1 << cfg.lut_value_bits)
    y = jnp.round(y * scale) / scale
    return jnp.where(sat, 0.0, y)


def snap_up_to_grid(m: jax.Array, cfg: SoftmaxLUTConfig) -> jax.Array:
    """Ceil a running max onto the Δ grid.

    With the row max on the grid, online-softmax correction factors
    e^{m_old - m_new} are grid-exact, so tiled accumulation matches the
    single-pass reference up to LUT-entry rounding only (see kernel.py).
    The uniform shift cancels in the final normalization.
    """
    step = jnp.float32(cfg.step)
    return jnp.ceil(m / step) * step
