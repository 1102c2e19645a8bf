"""Shared building blocks: init, norm dispatch, MLP, embeddings.

Everything is functional: params are nested dicts of jnp arrays; modules are
(init, apply) function pairs.  Logical sharding axes for every parameter are
declared alongside its initializer (see ``ParamSpec``) so the dry-run can
materialize ShapeDtypeStructs with NamedShardings without allocating.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import get_norm
from repro.configs.base import ModelConfig
from repro.serve.tracing import GN_NORM, MLP


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    logical_axes: tuple  # logical axis name (or None) per dim
    init: str = "normal"  # normal | zeros | ones
    scale: float = 0.02
    dtype: str = "float32"


def make_param(key, spec: ParamSpec):
    dtype = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    return (jax.random.normal(key, spec.shape) * spec.scale).astype(dtype)


def init_tree(key, spec_tree):
    """Initialize a pytree of ParamSpec into a pytree of arrays."""
    leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    vals = [make_param(k, s) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def spec_struct(spec_tree):
    """ParamSpec tree -> ShapeDtypeStruct tree (for AOT lowering)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def stack_specs(spec_tree, n: int, axis_name=None):
    """Prepend a stacked (scan) layer dimension to every ParamSpec."""
    return jax.tree.map(
        lambda s: ParamSpec(
            (n, *s.shape), (axis_name, *s.logical_axes), s.init, s.scale, s.dtype
        ),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# ------------------------------------------------------------------- norms --
def norm_specs(cfg: ModelConfig, d: int | None = None) -> dict:
    d = d or cfg.d_model
    specs = {"gamma": ParamSpec((d,), (None,), init="ones")}
    if _norm_has_beta(cfg.norm_impl):
        specs["beta"] = ParamSpec((d,), (None,), init="zeros")
    return specs


def _norm_has_beta(norm_impl: str) -> bool:
    return "ln" in norm_impl  # LayerNorm variants carry beta; RMS variants don't


@jax.named_scope(GN_NORM)
def apply_norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    fn = get_norm(cfg.norm_impl)
    gamma = p["gamma"]
    beta = p.get("beta")
    return fn(x, gamma, beta) if beta is not None else fn(x, gamma)


# -------------------------------------------------------------------- MLP ---
def mlp_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_act == "swiglu":
        return {
            "wi": ParamSpec((d, f), ("embed_fsdp", "ff")),
            "wg": ParamSpec((d, f), ("embed_fsdp", "ff")),
            "wo": ParamSpec((f, d), ("ff", "embed_fsdp")),
        }
    return {
        "wi": ParamSpec((d, f), ("embed_fsdp", "ff")),
        "wo": ParamSpec((f, d), ("ff", "embed_fsdp")),
    }


@jax.named_scope(MLP)
def apply_mlp(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    if cfg.mlp_act == "swiglu":
        h = jnp.einsum("...d,df->...f", x, p["wi"].astype(dt))
        g = jnp.einsum("...d,df->...f", x, p["wg"].astype(dt))
        h = jax.nn.silu(g) * h
    else:
        h = jnp.einsum("...d,df->...f", x, p["wi"].astype(dt))
        h = jax.nn.gelu(h)
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(dt))


# -------------------------------------------------------------- embeddings --
def embed_specs(cfg: ModelConfig) -> dict:
    return {
        "tok": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_fsdp")),
    }


def lm_head_specs(cfg: ModelConfig) -> dict:
    return {"w": ParamSpec((cfg.d_model, cfg.vocab), ("embed_fsdp", "vocab"))}
