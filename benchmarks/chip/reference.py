"""Plain float32 reference of the benchmark's models, and their weights.

The weights are made here, from the run seed, in one jitted call on the
device, in the layout the program under test takes them; the program is
handed them and the reference reads the same arrays.  The reference
imports nothing of the program: the forward pass is written out from the
configuration file (published widths plus the ``gn`` section), in
``jax.numpy`` at float32 with ``Precision.HIGHEST`` matmuls, one sequence at
a time, layer by layer under ``lax.scan``, with the attention rows taken in
blocks so that long contexts fit beside the weights.

The GN units are computed from their definitions:

* GN softmax: the row maximum snapped up onto the Delta grid (step
  2^-frac_bits), Delta_i = max - x_i rounded onto the grid and saturated at
  ``coarse_entries * 8 * 2^frac_bits - 1`` steps, e^-Delta as the product of
  a coarse entry e^(-8 k) and a residual entry e^(-j step), each rounded to
  Q1.value_bits, the product rounded again, and p = y / sum(y).  Masked
  columns get y = 0.
* GN RMS norm: x * rsqrt(mean(x^2) + norm_eps) * gain.

``lowp=True`` is the control: every matmul operand is rounded to float8
e4m3 with a per-tensor absmax scale, the step below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
RADIX = 8
NORM_KEYS = ("gamma", "q_norm", "kv_norm")


# ------------------------------------------------------------- weights ---
def _seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def make_weights(struct_tree, seed: int, dtype=jnp.float32):
    """Weights shaped like ``struct_tree`` (a tree of ShapeDtypeStruct, as
    ``jax.eval_shape`` of the program's init gives it): normal(0, 0.02)
    matrices, unit norm gains, zero biases; made on the default device in
    one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct_tree)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    shapes = [tuple(s.shape) for _, s in flat]

    def init(key):
        out = []
        for i, (name, shape) in enumerate(zip(names, shapes)):
            if name in NORM_KEYS:
                out.append(jnp.ones(shape, dtype))
            elif name in ("beta", "gate"):
                out.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init)(_seed_key(seed))


# ------------------------------------------------------------ GN units ---
def gn_softmax(x, mask, gn: dict):
    """GN softmax over the last axis; ``mask`` False columns get p = 0."""
    f = int(gn["softmax_frac_bits"])
    vb = float(1 << int(gn["softmax_value_bits"]))
    step = 1.0 / (1 << f)
    per = RADIX * (1 << f)
    top = int(gn["softmax_coarse_entries"]) * per - 1
    xm = jnp.where(mask, x, -jnp.inf)
    m = jnp.ceil(jnp.max(xm, axis=-1, keepdims=True) / step) * step
    d = jnp.clip(jnp.round(jnp.where(mask, m - x, 0.0) / step), 0, top)
    coarse = jnp.floor(d / per)
    rem = d - coarse * per
    a = jnp.round(jnp.exp(-float(RADIX) * coarse) * vb) / vb
    b = jnp.round(jnp.exp(-rem * step) * vb) / vb
    y = jnp.where(mask, jnp.round(a * b * vb) / vb, 0.0)
    return y / jnp.sum(y, axis=-1, keepdims=True)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """Rotary embedding over the last axis, halves rotated as pairs."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    if x.ndim == 3:
        ang = ang[:, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --------------------------------------------------------------- matmul ---
def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(lowp: bool):
    def ein(spec, a, b):
        if lowp:
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=jnp.float32)
    return ein


# -------------------------------------------------------------- forward ---
def _attention(ein, q, k, v, mask_fn, gn, scale, qblock):
    """q: (S, H, dq), k: (S, H, dq), v: (S, H, dv) -> (S, H, dv), causal
    GN attention with the query rows taken ``qblock`` at a time."""
    s = q.shape[0]
    nblk = s // qblock

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock, axis=0)
        sc = ein("qhd,khd->hqk", qb, k) * scale
        rows = i * qblock + jnp.arange(qblock)
        p = gn_softmax(sc, mask_fn(rows)[None], gn)
        return ein("hqk,khd->qhd", p, v)

    out = jax.lax.map(one, jnp.arange(nblk))
    return out.reshape(s, *out.shape[2:])


def forward_stats(cfg: dict, weights, tokens, targets, valid_len, *, lowp=False,
                  qblock=256, hblock=256):
    """Per position of ``tokens`` (S,), the reference's largest logit, its
    logit of ``targets`` (S,) and its top token.  Positions at or beyond
    ``valid_len`` attend nothing past it and are don't-care."""
    ein = _mm(lowp)
    gn = cfg["gn"]
    eps = float(gn["norm_eps"])
    s = tokens.shape[0]
    pos = jnp.arange(s)
    kpos = jnp.arange(s)

    def mask_fn(rows):
        return (kpos[None, :] <= rows[:, None]) & (kpos[None, :] < valid_len)

    x = weights["embed"]["tok"][tokens].astype(jnp.float32)
    nh = int(cfg["num_attention_heads"])
    theta = float(cfg["rope_theta"])
    mla = "kv_lora_rank" in cfg

    def layer(x, lp):
        h = rms(x, lp["ln1"]["gamma"], eps)
        a = lp["mixer"]
        if mla:
            dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
            dv, rk = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
            ql = rms(ein("sd,dr->sr", h, a["wq_a"]), a["q_norm"], eps)
            q = ein("sr,rf->sf", ql, a["wq_b"]).reshape(s, nh, dn + dr)
            q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, theta)], axis=-1)
            kva = ein("sd,dr->sr", h, a["wkv_a"])
            c = rms(kva[:, :rk], a["kv_norm"], eps)
            kr = rope(kva[:, rk:], pos, theta)
            kv = ein("sr,rf->sf", c, a["wkv_b"]).reshape(s, nh, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(kr[:, None, :], (s, nh, dr))], axis=-1)
            v = kv[..., dn:]
            att = _attention(ein, q, k, v, mask_fn, gn, (dn + dr) ** -0.5, qblock)
            y = ein("sf,fd->sd", att.reshape(s, nh * dv), a["wo"])
        else:
            nkv = int(cfg["num_key_value_heads"])
            dh = int(cfg["hidden_size"]) // nh
            q = rope(ein("sd,df->sf", h, a["wq"]).reshape(s, nh, dh), pos, theta)
            k = rope(ein("sd,df->sf", h, a["wk"]).reshape(s, nkv, dh), pos, theta)
            v = ein("sd,df->sf", h, a["wv"]).reshape(s, nkv, dh)
            g = nh // nkv  # query head j reads key/value head j // g
            k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
            att = _attention(ein, q, k, v, mask_fn, gn, dh ** -0.5, qblock)
            y = ein("sf,fd->sd", att.reshape(s, nh * dh), a["wo"])
        x = x + y
        h2 = rms(x, lp["ln2"]["gamma"], eps)
        m = lp["mlp"]
        act = jax.nn.silu(ein("sd,df->sf", h2, m["wg"])) * ein("sd,df->sf", h2, m["wi"])
        return x + ein("sf,fd->sd", act, m["wo"]), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = rms(x, weights["final_norm"]["gamma"], eps)

    def head(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * hblock, hblock, axis=0)
        tb = jax.lax.dynamic_slice_in_dim(targets, i * hblock, hblock, axis=0)
        lg = ein("sd,dv->sv", xb, weights["lm_head"]["w"])
        tl = jnp.take_along_axis(lg, tb[:, None], axis=1)[:, 0]
        return jnp.max(lg, axis=1), tl, jnp.argmax(lg, axis=1).astype(jnp.int32)

    mx, tl, am = jax.lax.map(head, jnp.arange(s // hblock))
    return mx.reshape(-1), tl.reshape(-1), am.reshape(-1)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, lowp: bool, qblock: int, hblock: int):
    return jax.jit(functools.partial(forward_stats, json.loads(cfg_json), lowp=lowp,
                                     qblock=qblock, hblock=hblock))


def pad_len(n: int, grain: int = 512) -> int:
    return max(grain, -(-n // grain) * grain)


def served_gaps(cfg: dict, weights, prompt, served, *, lowp=False,
                grain: int = 512) -> np.ndarray:
    """For each served token, by how much the reference's logit of it lies
    below the reference's best at that position (0 where it is the best).
    With ``lowp`` the token compared is the control's own top token at each
    position of the same sequence instead of the served one."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served])
    n_in = len(seq) - 1  # the last served token is never an input
    sp = pad_len(n_in, grain)
    toks = np.zeros(sp, np.int32)
    toks[:n_in] = seq[:n_in]
    tgt = np.zeros(sp, np.int32)
    tgt[:n_in] = seq[1:]
    block = math.gcd(sp, 256)
    key = json.dumps(cfg, sort_keys=True, default=repr)
    ref = _jitted(key, False, block, block)
    mx, tl, _ = jax.device_get(ref(weights, jnp.asarray(toks), jnp.asarray(tgt), n_in))
    at = slice(len(prompt) - 1, n_in)
    if not lowp:
        return np.asarray(mx[at] - tl[at], np.float64)
    ctl = _jitted(key, True, block, block)
    _, _, top = jax.device_get(ctl(weights, jnp.asarray(toks), jnp.asarray(tgt), n_in))
    _, tl8, _ = jax.device_get(ref(weights, jnp.asarray(toks), jnp.asarray(top), n_in))
    return np.asarray(mx[at] - tl8[at], np.float64)
