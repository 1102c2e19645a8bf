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
    """ROM lookup ``values[idx]`` as a tree of selects over the entries.

    The entries are trace-time constants, so the result is bit-equal to the
    table; indices outside it read 0.  Like a ROM's output mux, bit b of the
    index picks between the halves of each 2^(b+1)-entry subtable: n - 1
    selects and one bit test per level, where comparing the index with each
    entry in turn takes n compares and n selects.  Selects lower on any tile
    shape (a one-hot matmul needs an (r*c, 1) reshape that Mosaic refuses).
    """
    n = len(values)
    depth = max(1, (n - 1).bit_length())
    table = [np.float32(v) for v in values] + [np.float32(0)] * ((1 << depth) - n)
    bit = [((idx >> b) & 1) == 1 for b in range(depth)]

    def pick(lo: int, level: int):  # entries [lo, lo + 2^level)
        if level == 0:
            return table[lo]
        half = 1 << (level - 1)
        return jnp.where(bit[level - 1], pick(lo + half, level - 1),
                         pick(lo, level - 1))

    out = jnp.broadcast_to(jnp.asarray(pick(0, depth), jnp.float32), idx.shape)
    return jnp.where((idx >= 0) & (idx < n), out, jnp.float32(0))


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
