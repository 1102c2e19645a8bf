"""Normalization error of the block-paged serving read.

The same |1 - Σp| lens as ``core.metrics`` pointed at the streamed paged
GN-softmax read, over fp or int8-quantized KV blocks.  Used by
``examples/norm_error_study.py`` and pinned against its analytic bound in
``tests/test_serve_quant.py``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, reduce_config
from repro.models import attention as attention_mod


def paged_int8_read_norm_error(seed=0, n=3, chunk=4, block_size=4, nb=12,
                               kv_dtype="int8"):
    """Normalization error of the paged serving read (streamed block-tile
    scan, the serving default) with ``kv_dtype`` arenas.

    Crafts a scrambled block layout, quantizes a Gaussian K arena to int8
    with per-block scales, and sets the V arena so it dequantizes to
    *exactly* 1.0 (int8 value 64, scale 1/64 — both powers of two): the
    read's output then equals Σp per query row, so ``|1 - out|`` IS the
    normalization error of the GN softmax over int8-dequantized scores.

    Returns ``(measured_max, analytic_bound, t_max)``.  The bound is the
    float-datapath guarantee: Σp = Z·S with one reciprocal rounding plus one
    f32 rounding per accumulated numerator — ``(t + 1) · 2^-23`` for a
    ``t``-column valid stream.  The LUT-saturation half of the guarantee
    (masked/stale columns contribute exactly-zero numerators) is what keeps
    ``t`` the *valid* count: table entries past the causal prefix never
    enter the sum at all.
    """
    cfg = reduce_config(get_config("internlm2-1.8b"))
    rng = np.random.default_rng(seed)
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kv
    bs = block_size
    max_bt = nb // n

    kf = rng.standard_normal((nb, bs, kv, dh)).astype(np.float32)
    if kv_dtype == "int8":
        k_amax = np.abs(kf).reshape(nb, -1).max(axis=1)
        k_scale = np.maximum(k_amax, 1e-30) / 127.0
        arena_k = jnp.asarray(
            np.clip(np.round(kf / k_scale[:, None, None, None]), -127, 127),
            jnp.int8)
        arena_v = jnp.full((nb, bs, kv, dh), 64, jnp.int8)
        scales = (jnp.asarray(k_scale, jnp.float32),
                  jnp.full((nb,), 1.0 / 64.0, jnp.float32))
    else:
        arena_k = jnp.asarray(kf)
        arena_v = jnp.ones((nb, bs, kv, dh), jnp.float32)
        scales = None

    qg = jnp.asarray(rng.standard_normal((n, chunk, kv, g, dh)) * 2.0,
                     jnp.float32)
    tables = jnp.asarray(
        rng.permutation(nb).reshape(n, max_bt), jnp.int32)
    positions = jnp.asarray(rng.integers(0, (max_bt - 1) * bs, size=n),
                            jnp.int32)
    rows = positions[:, None] + jnp.arange(chunk)[None, :]
    out = attention_mod._stream_paged_tiles(
        cfg, qg, arena_k, arena_v, tables, rows, scales=scales)
    measured = float(jnp.max(jnp.abs(1.0 - out)))
    t_max = int(rows.max()) + 1
    bound = (t_max + 1) * 2.0**-23
    return measured, bound, t_max
