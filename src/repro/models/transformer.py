"""Model assembly: all 10 assigned families behind one functional API.

``Model(cfg)`` exposes:
  * ``param_specs()`` / ``init(key)``           — ParamSpec tree / materialized params
  * ``loss(params, batch)`` / ``forward``       — training path (scan over layers)
  * ``cache_specs`` / ``init_cache``            — decode-cache ShapeDtypeStructs
  * ``prefill(params, batch, max_seq)``         — prompt pass, returns (logits, cache)
  * ``decode_step(params, cache, token, pos)``  — one-token serve step

Families:
  dense (GQA/MHA/MLA)   — standard pre-norm residual blocks
  moe                   — dense attention + GShard top-k MoE MLP
  ssm                   — mLSTM (xLSTM) blocks, no separate MLP
  hybrid                — Zamba2: groups of Mamba2 layers, each group preceded
                          by ONE weight-shared attention block (its KV cache is
                          per-application); grouped two-level scan
  encdec                — Whisper: encoder over stub frame embeddings, decoder
                          with self+cross attention
  vlm                   — Llama-3.2-V: gated cross-attention every K layers
                          over stub patch embeddings; grouped two-level scan

Layers are scanned (stacked params) so HLO size is depth-independent; remat
policy per config.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import get_norm
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    ParamSpec,
    apply_mlp,
    apply_norm,
    embed_specs,
    init_tree,
    lm_head_specs,
    mlp_specs,
    norm_specs,
    spec_struct,
    stack_specs,
)
from repro.parallel.sharding import shard
from repro.serve.tracing import GN_PROBE, LM_HEAD


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _lm_head(cfg, params, x):
    x = apply_norm(cfg, params["final_norm"], x)
    with jax.named_scope(LM_HEAD):
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"]["w"].astype(x.dtype)
        )
        return shard(logits, "batch", "seq", "vocab")


def _chunk_head(cfg, params, x, n_valid, last_only):
    """LM head for a chunk step: project all C rows, or (last_only) just the
    next-token row n_valid-1 — per-row matmuls make the gather bit-exact."""
    if last_only:
        x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    return _lm_head(cfg, params, x)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.family == "hybrid":
            assert cfg.n_layers % cfg.attn_every == 0, "hybrid needs L % cadence == 0"
        if cfg.family == "vlm":
            assert cfg.n_layers % cfg.cross_attn_every == 0

    # ------------------------------------------------------------- specs ---
    def _mixer_specs(self) -> dict:
        cfg = self.cfg
        if cfg.mla is not None:
            return mla_mod.mla_specs(cfg)
        if cfg.family == "ssm":
            return (
                ssm_mod.mlstm_specs(cfg)
                if cfg.ssm.kind == "mlstm"
                else ssm_mod.mamba2_specs(cfg)
            )
        if cfg.family == "hybrid":
            return ssm_mod.mamba2_specs(cfg)
        return attn.attn_specs(cfg)

    def _block_specs(self) -> dict:
        cfg = self.cfg
        specs: dict[str, Any] = {"ln1": norm_specs(cfg), "mixer": self._mixer_specs()}
        if cfg.family in ("ssm", "hybrid"):
            return specs  # these archs carry no separate MLP (d_ff folded in)
        specs["ln2"] = norm_specs(cfg)
        specs["mlp"] = moe_mod.moe_specs(cfg) if cfg.moe else mlp_specs(cfg)
        return specs

    def _attn_block_specs(self) -> dict:
        cfg = self.cfg
        return {
            "ln1": norm_specs(cfg),
            "attn": attn.attn_specs(cfg),
            "ln2": norm_specs(cfg),
            "mlp": mlp_specs(cfg),
        }

    def param_specs(self) -> dict:
        cfg = self.cfg
        specs: dict[str, Any] = {
            "embed": embed_specs(cfg),
            "layers": stack_specs(self._block_specs(), cfg.n_layers, "layers"),
            "final_norm": norm_specs(cfg),
            "lm_head": lm_head_specs(cfg),
        }
        if cfg.family == "hybrid":
            specs["shared_attn"] = self._attn_block_specs()  # ONE shared block
        if cfg.family == "encdec":
            specs["encoder"] = {
                "layers": stack_specs(self._attn_block_specs(), cfg.encoder_layers, "layers"),
                "pos": ParamSpec((cfg.encoder_seq, cfg.d_model), (None, "embed_fsdp")),
                "final_norm": norm_specs(cfg),
            }
            dec_block = dict(self._block_specs())
            dec_block["ln_x"] = norm_specs(cfg)
            dec_block["xattn"] = attn.cross_attn_specs(cfg)
            specs["layers"] = stack_specs(dec_block, cfg.n_layers, "layers")
        if cfg.family == "vlm":
            n_x = cfg.n_layers // cfg.cross_attn_every
            xblock = {
                "ln": norm_specs(cfg),
                "xattn": attn.cross_attn_specs(cfg),
                "gate": ParamSpec((1,), (None,), init="zeros"),
                "vis_proj": ParamSpec((cfg.d_model, cfg.d_model), ("embed_fsdp", None)),
            }
            specs["xattn_layers"] = stack_specs(xblock, n_x, "layers")
        return specs

    def init(self, key) -> dict:
        return init_tree(key, self.param_specs())

    def param_structs(self) -> dict:
        return spec_struct(self.param_specs())

    # ------------------------------------------------------ train blocks ---
    def _mixer_train(self, lp, h, positions):
        cfg = self.cfg
        if cfg.mla is not None:
            return mla_mod.mla_self_attention(cfg, lp["mixer"], h, positions)
        if cfg.family == "ssm":
            blk = ssm_mod.mlstm_block if cfg.ssm.kind == "mlstm" else ssm_mod.mamba2_block
            y, _ = blk(cfg, lp["mixer"], h)
            return y
        if cfg.family == "hybrid":
            y, _ = ssm_mod.mamba2_block(cfg, lp["mixer"], h)
            return y
        return attn.self_attention(cfg, lp["mixer"], h, positions)

    def _block_train(self, lp, x, positions, memory=None):
        cfg = self.cfg
        x = shard(x, "batch", "seq", "embed_act")
        aux = {}
        x = x + self._mixer_train(lp, apply_norm(cfg, lp["ln1"], x), positions)
        if memory is not None and "xattn" in lp:
            hx = apply_norm(cfg, lp["ln_x"], x)
            x = x + attn.cross_attention(cfg, lp["xattn"], hx, memory)
        if "mlp" in lp:
            h2 = apply_norm(cfg, lp["ln2"], x)
            if cfg.moe:
                y, aux = moe_mod.apply_moe(cfg, lp["mlp"], h2)
            else:
                y = apply_mlp(cfg, lp["mlp"], h2)
            x = x + y
        return shard(x, "batch", "seq", "embed_act"), aux

    def _attn_block_train(self, sp, x, positions, causal=True):
        cfg = self.cfg
        h = apply_norm(cfg, sp["ln1"], x)
        x = x + attn.self_attention(cfg, sp["attn"], h, positions, causal=causal)
        h = apply_norm(cfg, sp["ln2"], x)
        return x + apply_mlp(cfg, sp["mlp"], h)

    def _xattn_block(self, xp, x, patches):
        cfg = self.cfg
        mem = jnp.einsum(
            "bmd,de->bme", patches.astype(x.dtype), xp["vis_proj"].astype(x.dtype)
        )
        h = apply_norm(cfg, xp["ln"], x)
        y = attn.cross_attention(cfg, xp["xattn"], h, mem)
        return x + jnp.tanh(xp["gate"].astype(x.dtype)) * y

    def _group_tree(self, tree, n_groups):
        return jax.tree.map(lambda a: a.reshape(n_groups, -1, *a.shape[1:]), tree)

    def _run_layers_train(self, params, x, positions, memory=None):
        """Returns (x, aux_sums).  Handles plain / hybrid / vlm groupings."""
        cfg = self.cfg

        if cfg.family == "hybrid":
            g = cfg.n_layers // cfg.attn_every
            layers = self._group_tree(params["layers"], g)
            shared = params["shared_attn"]

            def group_body(x, gp):
                x = self._attn_block_train(shared, x, positions)

                def inner(x2, lp):
                    x2, _ = self._block_train(lp, x2, positions)
                    return x2, None

                x, _ = jax.lax.scan(_remat(cfg, inner), x, gp)
                return x, None

            x, _ = jax.lax.scan(group_body, x, layers)
            return x, jnp.zeros((2,))

        if cfg.family == "vlm":
            g = cfg.n_layers // cfg.cross_attn_every
            layers = self._group_tree(params["layers"], g)

            def group_body(x, scanned):
                gp, xp = scanned
                x = self._xattn_block(xp, x, memory)

                def inner(x2, lp):
                    x2, _ = self._block_train(lp, x2, positions)
                    return x2, None

                x, _ = jax.lax.scan(_remat(cfg, inner), x, gp)
                return x, None

            x, _ = jax.lax.scan(group_body, x, (layers, params["xattn_layers"]))
            return x, jnp.zeros((2,))

        def body(x, lp):
            x, aux = self._block_train(lp, x, positions, memory=memory)
            aux_vec = jnp.stack(
                [
                    jnp.asarray(aux.get("load_balance", 0.0), jnp.float32),
                    jnp.asarray(aux.get("router_z", 0.0), jnp.float32),
                ]
            )
            return x, aux_vec

        x, auxs = jax.lax.scan(_remat(cfg, body), x, params["layers"])
        return x, jnp.sum(auxs, 0)

    def _encode(self, params, frames):
        """Whisper encoder over stub frame embeddings (B, M, D)."""
        cfg = self.cfg
        enc = params["encoder"]
        dt = jnp.dtype(cfg.dtype)
        x = frames.astype(dt) + enc["pos"][None].astype(dt)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

        def body(x, lp):
            return self._attn_block_train(lp, x, positions, causal=False), None

        x, _ = jax.lax.scan(_remat(cfg, body), x, enc["layers"])
        return apply_norm(cfg, enc["final_norm"], x)

    # ----------------------------------------------------------- forward ---
    def forward(self, params, batch: dict):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        tokens = batch["tokens"]
        x = params["embed"]["tok"].astype(dt)[tokens]
        x = shard(x, "batch", "seq", "embed_act")
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)

        memory = None
        if cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
        elif cfg.family == "vlm":
            memory = batch["patches"]
        x, aux = self._run_layers_train(params, x, positions, memory=memory)
        logits = _lm_head(cfg, params, x)
        return logits, {"load_balance": aux[0], "router_z": aux[1]}

    def loss(self, params, batch: dict):
        cfg = self.cfg
        logits, aux = self.forward(params, batch)
        logits = logits.astype(jnp.float32)
        targets = batch["tokens"][:, 1:]
        logits = logits[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(nll)
        metrics = {"nll": loss}
        if cfg.moe is not None:
            loss = loss + 0.01 * aux["load_balance"] / cfg.n_layers
            loss = loss + 1e-3 * aux["router_z"] / cfg.n_layers
            metrics.update(aux)
        return loss, metrics

    # ------------------------------------------------------------- cache ---
    def _ssm_cache_tuple(self, batch):
        cfg = self.cfg
        if cfg.family == "ssm" and cfg.ssm.kind == "mlstm":
            return ssm_mod.mlstm_cache_shape(cfg, batch)
        return ssm_mod.mamba2_cache_shape(cfg, batch)

    def cache_specs(self, batch: int, max_seq: int):
        cfg = self.cfg
        L = cfg.n_layers

        def stack(tree, n=L):
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype), tree
            )

        if cfg.mla is not None:
            cache = {"layers": stack(mla_mod.mla_cache_shape(cfg, batch, max_seq))}
        elif cfg.family == "ssm":
            cache = {"layers": stack({i: s for i, s in enumerate(self._ssm_cache_tuple(batch))})}
        elif cfg.family == "hybrid":
            g = L // cfg.attn_every
            cache = {
                "layers": stack({i: s for i, s in enumerate(self._ssm_cache_tuple(batch))}),
                "shared": stack(attn.attn_cache_shape(cfg, batch, max_seq), n=g),
            }
        else:
            cache = {"layers": stack(attn.attn_cache_shape(cfg, batch, max_seq))}
        if cfg.family == "encdec":
            kvshape = (L, batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim)
            cache["cross"] = {
                "k": jax.ShapeDtypeStruct(kvshape, jnp.dtype(cfg.dtype)),
                "v": jax.ShapeDtypeStruct(kvshape, jnp.dtype(cfg.dtype)),
            }
        if cfg.family == "vlm":
            cache["patches"] = jax.ShapeDtypeStruct(
                (batch, cfg.num_patches, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        return cache

    def cache_logical_axes(self):
        """Logical sharding axes tree, parallel to cache_specs (see
        parallel/sharding.py for the rules; 'kv_seq' switches to SP for
        long-context decode)."""
        cfg = self.cfg
        if cfg.mla is not None:
            cache = {"layers": {
                "c_kv": ("layers", "batch", "kv_seq", None),
                "k_rope": ("layers", "batch", "kv_seq", None),
            }}
        elif cfg.family == "ssm" and cfg.ssm.kind == "mlstm":
            cache = {"layers": {
                0: ("layers", "batch", None, "ff"),
                1: ("layers", "batch", None, None, "heads_tp"),
                2: ("layers", "batch", None, "heads_tp"),
                3: ("layers", "batch", None),
            }}
        elif cfg.family in ("ssm", "hybrid"):
            cache = {"layers": {
                0: ("layers", "batch", None, "ff"),
                1: ("layers", "batch", "heads_tp", None, None),
            }}
        else:
            kvax = ("layers", "batch", "kv_seq", "heads_tp", None)
            cache = {"layers": {"k": kvax, "v": kvax}}
        if cfg.family == "hybrid":
            cache["shared"] = {
                "k": ("layers", "batch", "kv_seq", "heads_tp", None),
                "v": ("layers", "batch", "kv_seq", "heads_tp", None),
            }
        if cfg.family == "encdec":
            cache["cross"] = {
                "k": ("layers", "batch", None, "heads_tp", None),
                "v": ("layers", "batch", None, "heads_tp", None),
            }
        if cfg.family == "vlm":
            cache["patches"] = ("batch", None, None)
        return cache

    def init_cache(self, batch: int, max_seq: int):
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.cache_specs(batch, max_seq)
        )

    # ------------------------------------------------- slot-cache helpers ---
    def cache_batch_axes(self):
        """Tree parallel to ``cache_specs`` giving the batch-axis index of
        every cache leaf (derived from ``cache_logical_axes``).  The serving
        engine treats the batch dim as a *slot* dim; these indices drive the
        per-slot insert/extract below and the vmapped multi-position decode."""
        return jax.tree.map(
            lambda ax: ax.index("batch"),
            self.cache_logical_axes(),
            is_leaf=lambda x: isinstance(x, tuple),
        )

    def insert_cache_slot(self, pool_cache, request_cache, slot):
        """Write a single-request cache (batch dim 1, same max_seq layout)
        into slot ``slot`` of a pool cache (batch dim = num_slots).  ``slot``
        may be a traced scalar, so one jit covers every slot."""

        def upd(dst, src, ax):
            starts = tuple(slot if i == ax else 0 for i in range(dst.ndim))
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), starts)

        return jax.tree.map(upd, pool_cache, request_cache, self.cache_batch_axes())

    def extract_cache_slot(self, pool_cache, slot):
        """Read slot ``slot`` back out as a single-request (batch=1) cache."""

        def ext(src, ax):
            starts = tuple(slot if i == ax else 0 for i in range(src.ndim))
            sizes = tuple(1 if i == ax else d for i, d in enumerate(src.shape))
            return jax.lax.dynamic_slice(src, starts, sizes)

        return jax.tree.map(ext, pool_cache, self.cache_batch_axes())

    def decode_step_slots(self, params, cache, tokens, positions):
        """Per-slot decode for continuous batching: like ``decode_step`` but
        every batch row carries its *own* position.  tokens: (N, 1) int32;
        positions: (N,) int32.  Returns (logits (N, 1, V), new cache).

        Implemented as a vmap of the single-sequence decode over the cache's
        batch axes, so every family's decode path (dense/mla/ssm/hybrid/
        encdec/vlm) is reused unchanged and numerics match the static engine.
        """
        axes = self.cache_batch_axes()

        def one(c, t, pos):
            # vmap strips the mapped batch axis; decode_step wants batch=1.
            c = jax.tree.map(jnp.expand_dims, c, axes)
            logits, nc = self.decode_step(params, c, t[None], pos)
            nc = jax.tree.map(jnp.squeeze, nc, axes)
            return logits[0], nc

        return jax.vmap(one, in_axes=(axes, 0, 0), out_axes=(0, axes))(
            cache, tokens, positions
        )

    # ------------------------------------------------- fused chunk step ---
    def fresh_request_cache(self, max_seq: int):
        """Batch-1 cache tree in the family's *initial* (pre-prompt) state —
        the chunked-prefill entry point.  Zeros everywhere except the mLSTM
        stabilizer m, whose empty value is -1e30 (``mlstm_block``'s
        carry=None init); a zero m would corrupt the first chunk's gating."""
        cache = self.init_cache(1, max_seq)
        if self.cfg.family == "ssm" and self.cfg.ssm.kind == "mlstm":
            cache["layers"][3] = jnp.full_like(cache["layers"][3], -1e30)
        return cache

    def encode_cross_kv(self, params, frames):
        """encdec admission path: run the encoder once and project the
        per-layer cross k/v the decoder's chunked prefill will attend to.
        frames: (B, M_frames, D) -> {'k','v'}: (L, B, M, KV, dh), exactly the
        ``cache['cross']`` layout ``prefill`` produces."""

        memory = self._encode(params, frames)

        def body(_, lp):
            k, v = _project_cross_kv(self.cfg, lp["xattn"], memory)
            return None, (k, v)

        _, (ks, vs) = jax.lax.scan(body, None, params["layers"])
        return {"k": ks, "v": vs}

    def prefill_chunk(self, params, cache, tokens, pos, n_valid, last_only=False):
        """Masked, position-offset multi-token step: process ``tokens``
        (B, C) at absolute positions [pos, pos+n_valid), appending into the
        decode cache at the traced write offset ``pos``.  Lanes >= n_valid
        are don't-care: their cache writes are dropped and recurrent carries
        frozen (see attn_decode_chunk / mlstm_block / mamba2_block).

        With n_valid=1 this is a decode step whose extra lanes are padding;
        with full chunks it streams a prompt into the cache chunk-by-chunk.
        At serve scales (prompt < the conv-fusion / chunked-SSD / chunked-
        attention thresholds) the result is bit-identical to the monolithic
        ``prefill`` followed by ``decode_step``s, which is what keeps greedy
        continuous batching token-identical to the static oracle.

        Returns (logits, new cache).  With ``last_only=False`` logits is
        (B, C, V) and row n_valid-1 is the next-token distribution after the
        chunk; with ``last_only=True`` only that row is projected through
        the LM head — (B, 1, V) — which skips (C-1)/C of the vocab matmul
        on serving ticks (the row gather is bit-identical to slicing the
        full projection, matmul rows being independent).
        """
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = params["embed"]["tok"].astype(dt)[tokens]  # (B, C, D)
        x = shard(x, "batch", None, "embed_act")

        if cfg.family == "hybrid":
            return self._hybrid_chunk(params, cache, x, pos, n_valid, last_only)
        if cfg.family == "vlm":
            return self._vlm_chunk(params, cache, x, pos, n_valid, last_only)
        if cfg.family == "encdec":
            return self._encdec_chunk(params, cache, x, pos, n_valid, last_only)

        def body(x, scanned):
            lp, lcache = scanned
            h = apply_norm(cfg, lp["ln1"], x)
            if cfg.mla is not None:
                y, nc = mla_mod.mla_decode_chunk(cfg, lp["mixer"], lcache, h, pos, n_valid)
            elif cfg.family == "ssm":
                blk = ssm_mod.mlstm_block if cfg.ssm.kind == "mlstm" else ssm_mod.mamba2_block
                carry = tuple(lcache[i] for i in sorted(lcache))
                y, ncarry = blk(cfg, lp["mixer"], h, carry, n_valid=n_valid)
                nc = {i: c for i, c in enumerate(ncarry)}
            else:
                y, nc = attn.attn_decode_chunk(cfg, lp["mixer"], lcache, h, pos, n_valid)
            x = x + y
            if "mlp" in lp:
                h2 = apply_norm(cfg, lp["ln2"], x)
                y = (
                    moe_mod.apply_moe(cfg, lp["mlp"], h2)[0]
                    if cfg.moe
                    else apply_mlp(cfg, lp["mlp"], h2)
                )
                x = x + y
            return x, nc

        x, new_layers = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        logits = _chunk_head(cfg, params, x, n_valid, last_only)
        return logits, {**cache, "layers": new_layers}

    def _hybrid_chunk(self, params, cache, x, pos, n_valid, last_only=False):
        cfg = self.cfg
        g = cfg.n_layers // cfg.attn_every
        layers = self._group_tree(params["layers"], g)
        lcache = self._group_tree(cache["layers"], g)
        shared = params["shared_attn"]

        def group_body(x, scanned):
            gp, gc, skv = scanned
            h = apply_norm(cfg, shared["ln1"], x)
            y, new_skv = attn.attn_decode_chunk(cfg, shared["attn"], skv, h, pos, n_valid)
            x = x + y
            h = apply_norm(cfg, shared["ln2"], x)
            x = x + apply_mlp(cfg, shared["mlp"], h)

            def inner(x2, s2):
                lp, lc = s2
                h2 = apply_norm(cfg, lp["ln1"], x2)
                carry = tuple(lc[i] for i in sorted(lc))
                y2, ncarry = ssm_mod.mamba2_block(cfg, lp["mixer"], h2, carry, n_valid=n_valid)
                return x2 + y2, {i: c for i, c in enumerate(ncarry)}

            x, ncarries = jax.lax.scan(inner, x, (gp, gc))
            return x, (ncarries, new_skv)

        x, (ncar, nskv) = jax.lax.scan(group_body, x, (layers, lcache, cache["shared"]))
        L = cfg.n_layers
        ncar = jax.tree.map(lambda a: a.reshape(L, *a.shape[2:]), ncar)
        logits = _chunk_head(cfg, params, x, n_valid, last_only)
        return logits, {"layers": ncar, "shared": nskv}

    def _vlm_chunk(self, params, cache, x, pos, n_valid, last_only=False):
        cfg = self.cfg
        g = cfg.n_layers // cfg.cross_attn_every
        layers = self._group_tree(params["layers"], g)
        lcache = self._group_tree(cache["layers"], g)
        patches = cache["patches"]

        def group_body(x, scanned):
            gp, xp, gc = scanned
            x = self._xattn_block(xp, x, patches)

            def inner(x2, s2):
                lp, lc = s2
                h = apply_norm(cfg, lp["ln1"], x2)
                y, nc = attn.attn_decode_chunk(cfg, lp["mixer"], lc, h, pos, n_valid)
                x2 = x2 + y
                h2 = apply_norm(cfg, lp["ln2"], x2)
                x2 = x2 + apply_mlp(cfg, lp["mlp"], h2)
                return x2, nc

            x, ngc = jax.lax.scan(inner, x, (gp, gc))
            return x, ngc

        x, nlc = jax.lax.scan(group_body, x, (layers, params["xattn_layers"], lcache))
        nlc = jax.tree.map(lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), nlc)
        logits = _chunk_head(cfg, params, x, n_valid, last_only)
        return logits, {**cache, "layers": nlc}

    def _encdec_chunk(self, params, cache, x, pos, n_valid, last_only=False):
        cfg = self.cfg

        def body(x, scanned):
            lp, lcache, xk, xv = scanned
            h = apply_norm(cfg, lp["ln1"], x)
            y, nc = attn.attn_decode_chunk(cfg, lp["mixer"], lcache, h, pos, n_valid)
            x = x + y
            hx = apply_norm(cfg, lp["ln_x"], x)
            x = x + _cross_attend_cached(cfg, lp["xattn"], hx, xk, xv)
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + apply_mlp(cfg, lp["mlp"], h2)
            return x, nc

        x, new_layers = jax.lax.scan(
            body, x, (params["layers"], cache["layers"], cache["cross"]["k"], cache["cross"]["v"])
        )
        logits = _chunk_head(cfg, params, x, n_valid, last_only)
        return logits, {**cache, "layers": new_layers}

    def fused_step_slots(self, params, cache, tokens, positions, n_valid):
        """Per-slot fused prefill/decode for continuous batching: every slot
        processes its own C-token chunk at its own write offset.  tokens:
        (N, C) int32; positions/n_valid: (N,) int32 (all traced -> a single
        compilation regardless of the prompt-length mix).  Returns (logits
        (N, 1, V) — each slot's next-token row n_valid-1, the only one a
        serving tick consumes — and the new cache).  Decode slots pass their
        one sampled token in lane 0 with n_valid=1; prefill slots pass the
        next prompt chunk.

        Like ``decode_step_slots``, a vmap of the single-sequence step over
        the cache's batch axes, so all seven cache families reuse their
        chunk path unchanged.
        """
        axes = self.cache_batch_axes()

        def one(c, t, pos, nv):
            c = jax.tree.map(jnp.expand_dims, c, axes)
            logits, nc = self.prefill_chunk(params, c, t[None], pos, nv,
                                            last_only=True)
            nc = jax.tree.map(jnp.squeeze, nc, axes)
            return logits[0], nc

        return jax.vmap(one, in_axes=(axes, 0, 0, 0), out_axes=(0, axes))(
            cache, tokens, positions, n_valid
        )

    # ------------------------------------------------- block-paged cache ---
    @property
    def paged_read_path(self) -> str:
        """How the serving tick reads paged KV for this family: 'pallas'
        (dense TPU kernel), 'streamed' (block-tile scan, the CPU/GPU
        default) or 'gathered' (full-stream oracle, baselines only).  The
        engine surfaces this in ``metrics()`` and the bench folds it into
        the workload hash so trajectories don't mix read paths."""
        if self.cfg.mla is not None:
            return mla_mod.mla_paged_read_path(self.cfg)
        return attn.paged_read_path(self.cfg)

    @property
    def supports_paging(self) -> bool:
        """Block-granular KV paging applies to the families whose per-layer
        cache is a full-attention KV (dense/moe/encdec/vlm) or MLA latent
        stream: those grow with the sequence, so HBM scales with worst-case
        length under slab pooling.  SSM/hybrid carries are O(1) state and
        sliding-window configs keep their ring buffer — nothing to page."""
        return self.cfg.sliding_window == 0 and self.cfg.family not in ("ssm", "hybrid")

    def paged_cache_specs(self, num_slots: int, num_blocks: int,
                          block_size: int, max_seq: int, kv_dtype: str = "fp"):
        """Cache specs with the ``layers`` leaves re-laid as shared block
        arenas: the (slot, max_seq) dims of every per-layer KV/latent leaf
        become (num_blocks, block_size), indexed through per-slot block
        tables instead of a batch dim.  Non-sequence leaves (encdec cross KV,
        vlm patches) keep their slot-batched layout.

        ``kv_dtype='int8'`` stores every arena in int8 and adds one
        ``<leaf>_scale`` (L, num_blocks) float32 leaf per arena — the
        per-block dequantization scale, carried *inside* ``layers`` so every
        block-axis operation (COW fork, preemption spill/restore, the fused
        tick's layer scan) moves a block's scale with its payload for free."""
        if not self.supports_paging:
            raise ValueError(f"family {self.cfg.family!r} (sliding_window="
                             f"{self.cfg.sliding_window}) has no pageable KV")
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r}")
        specs = self.cache_specs(num_slots, max_seq)

        def repage(s):
            # every 'layers' leaf here is (L, slot, kv_seq, ...): see
            # cache_logical_axes for the dense/MLA families
            dtype = jnp.int8 if kv_dtype == "int8" else s.dtype
            return jax.ShapeDtypeStruct(
                (s.shape[0], num_blocks, block_size, *s.shape[3:]), dtype
            )

        layers = {k: repage(s) for k, s in specs["layers"].items()}
        if kv_dtype == "int8":
            layers.update({
                f"{k}_scale": jax.ShapeDtypeStruct((v.shape[0], num_blocks),
                                                   jnp.float32)
                for k, v in layers.items()
            })
        return {**specs, "layers": layers}

    def paged_cache_logical_axes(self, kv_dtype: str = "fp"):
        """Logical sharding axes tree parallel to ``paged_cache_specs``.

        The per-layer arenas trade the (slot, kv_seq) dims for (num_blocks,
        block_size): the *block* axis inherits the slot pool's 'batch' rule —
        the serving mesh shards blocks over the same device axis as slots,
        and the pool hands each slot blocks from its own device's range, so
        a sequence's KV stays resident with its slot shard — while the
        intra-block dim is replicated like any other sequence dim.  Non-paged
        leaves (encdec cross KV, vlm patches) keep their slot-batched axes.
        Quantized pools add (layer, block) scale leaves whose block axis
        shards exactly like the arena it scales.
        """
        axes = self.cache_logical_axes()

        def repage(ax):
            # (layers, batch/slot, kv_seq, *rest) -> (layers, blocks, in-block, *rest)
            return (ax[0], "batch", None) + tuple(ax[3:])

        layers = {k: repage(ax) for k, ax in axes["layers"].items()}
        if kv_dtype == "int8":
            layers.update({
                f"{k}_scale": (ax[0], "batch") for k, ax in layers.items()
            })
        return {**axes, "layers": layers}

    def init_paged_cache(self, num_slots: int, num_blocks: int,
                         block_size: int, max_seq: int, kv_dtype: str = "fp"):
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.paged_cache_specs(num_slots, num_blocks, block_size, max_seq,
                                   kv_dtype=kv_dtype),
        )

    def insert_cache_slot_extras(self, pool_cache, request_cache, slot):
        """Slot-insert for the non-paged leaves of a paged pool cache (encdec
        cross KV, vlm patches).  The block arenas under ``layers`` have no
        slot dim — prompts stream into them through the block table — so
        admission only pages the per-request side inputs in."""
        axes = {k: v for k, v in self.cache_batch_axes().items() if k != "layers"}

        def upd(dst, src, ax):
            starts = tuple(slot if i == ax else 0 for i in range(dst.ndim))
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), starts)

        extras = {k: pool_cache[k] for k in axes}
        request = {k: request_cache[k] for k in axes}
        return {**pool_cache, **jax.tree.map(upd, extras, request, axes)}

    def fused_step_slots_paged(self, params, cache, tokens, positions, n_valid,
                               tables, sentinel=False):
        """Block-paged counterpart of ``fused_step_slots``: every slot
        processes its own C-token chunk at its own write offset, but KV lives
        in shared block arenas addressed through per-slot block tables
        instead of per-slot max_seq slabs.  tokens: (N, C) int32;
        positions/n_valid: (N,) int32; tables: (N, max_bt) int32 — all
        traced, so one compilation covers every phase/length/table mix.

        Where the slab path vmaps the single-sequence chunk step over the
        cache's slot axis, the arenas are *shared* across slots (that is the
        memory win), so this path runs the layer stack batched: projections,
        norms and MLPs are row-independent, and the paged attention read
        gathers each slot's logical stream through its table.  n_valid=0
        parks a lane completely (no writes — an inactive slot owns no
        blocks).  Returns (logits (N, 1, V) — each slot's next-token row
        n_valid-1 — and the new cache).

        ``sentinel`` is a static Python bool bound at closure time (never a
        trace key): when True the return gains a third element, a health
        pytree ``{"layers": (L, N, 3) f32, "head": (N,) f32}`` of GN
        sentinel probes (Σp residual / clip fraction / scale sanity per
        layer, σ residual at the head) accumulated on-device — no host
        transfer and no extra compile keys."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = params["embed"]["tok"].astype(dt)[tokens]  # (N, C, D)
        x = shard(x, "batch", None, "embed_act")

        if cfg.family == "vlm":
            return self._vlm_paged(params, cache, x, positions, n_valid,
                                   tables, sentinel)
        if cfg.family == "encdec":
            return self._encdec_paged(params, cache, x, positions, n_valid,
                                      tables, sentinel)

        def body(x, scanned):
            lp, lcache = scanned
            h = apply_norm(cfg, lp["ln1"], x)
            if cfg.mla is not None:
                if "c_kv_scale" in lcache:  # int8 arenas + per-block scales
                    y, (nck, nkr, ncs, nrs), *pr = mla_mod.mla_paged_chunk(
                        cfg, lp["mixer"], lcache["c_kv"], lcache["k_rope"], h,
                        positions, n_valid, tables,
                        scales=(lcache["c_kv_scale"], lcache["k_rope_scale"]),
                        probe=sentinel)
                    nc = {"c_kv": nck, "k_rope": nkr,
                          "c_kv_scale": ncs, "k_rope_scale": nrs}
                else:
                    y, (nck, nkr), *pr = mla_mod.mla_paged_chunk(
                        cfg, lp["mixer"], lcache["c_kv"], lcache["k_rope"], h,
                        positions, n_valid, tables, probe=sentinel)
                    nc = {"c_kv": nck, "k_rope": nkr}
            else:
                if "k_scale" in lcache:  # int8 arenas + per-block scales
                    y, (nk, nv, nks, nvs), *pr = attn.attn_paged_chunk(
                        cfg, lp["mixer"], lcache["k"], lcache["v"], h,
                        positions, n_valid, tables,
                        scales=(lcache["k_scale"], lcache["v_scale"]),
                        probe=sentinel)
                    nc = {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs}
                else:
                    y, (nk, nv), *pr = attn.attn_paged_chunk(
                        cfg, lp["mixer"], lcache["k"], lcache["v"], h,
                        positions, n_valid, tables, probe=sentinel)
                    nc = {"k": nk, "v": nv}
            x = x + y
            if "mlp" in lp:
                h2 = apply_norm(cfg, lp["ln2"], x)
                y = (
                    moe_mod.apply_moe(cfg, lp["mlp"], h2)[0]
                    if cfg.moe
                    else apply_mlp(cfg, lp["mlp"], h2)
                )
                x = x + y
            return x, ((nc, pr[0]) if sentinel else nc)

        x, ys = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        if sentinel:
            new_layers, probes = ys
            logits, head = self._paged_head(params, x, n_valid, probe=True)
            return (logits, {**cache, "layers": new_layers},
                    {"layers": probes, "head": head})
        return self._paged_head(params, x, n_valid), {**cache, "layers": ys}

    def _paged_head(self, params, x, n_valid, probe=False):
        """Next-token logits per slot: gather row n_valid-1 (clamped for
        parked lanes), then project only that row — per-row matmuls make the
        gather bit-exact vs slicing the full projection.

        With ``probe`` (static bool), also returns a (N,) f32 GN-LayerNorm
        σ-residual sentinel: |mean(x̂²) − 1| of the final-norm output on the
        gathered row (unit gamma — re-running the registry norm fn keeps
        the probe pinned to the same impl the head used), forced to +inf
        when the row or its logits contain nonfinite values, and zeroed for
        parked lanes."""
        n = x.shape[0]
        with jax.named_scope(LM_HEAD):
            idx = jnp.maximum(n_valid - 1, 0)[:, None, None]
            xr = jnp.take_along_axis(x, jnp.broadcast_to(idx, (n, 1, x.shape[-1])), axis=1)
        logits = _lm_head(self.cfg, params, xr)
        if not probe:
            return logits
        with jax.named_scope(GN_PROBE):
            xhat = get_norm(self.cfg.norm_impl)(xr.astype(jnp.float32))
            sig = jnp.abs(jnp.mean(xhat * xhat, axis=-1) - 1.0)[:, 0]
            bad = jnp.any(~jnp.isfinite(logits.astype(jnp.float32)),
                          axis=(1, 2)) | jnp.any(~jnp.isfinite(xr.astype(jnp.float32)),
                                                 axis=(1, 2))
            head = jnp.where(n_valid > 0,
                             jnp.where(bad, jnp.inf, sig),
                             jnp.zeros_like(sig))
        return logits, head

    def _encdec_paged(self, params, cache, x, positions, n_valid, tables,
                      sentinel=False):
        cfg = self.cfg

        def body(x, scanned):
            lp, lcache, xk, xv = scanned
            h = apply_norm(cfg, lp["ln1"], x)
            if "k_scale" in lcache:
                y, (nk, nv, nks, nvs), *pr = attn.attn_paged_chunk(
                    cfg, lp["mixer"], lcache["k"], lcache["v"], h,
                    positions, n_valid, tables,
                    scales=(lcache["k_scale"], lcache["v_scale"]),
                    probe=sentinel)
                nc = {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs}
            else:
                y, (nk, nv), *pr = attn.attn_paged_chunk(
                    cfg, lp["mixer"], lcache["k"], lcache["v"], h,
                    positions, n_valid, tables, probe=sentinel)
                nc = {"k": nk, "v": nv}
            x = x + y
            hx = apply_norm(cfg, lp["ln_x"], x)
            x = x + _cross_attend_cached(cfg, lp["xattn"], hx, xk, xv)
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + apply_mlp(cfg, lp["mlp"], h2)
            return x, ((nc, pr[0]) if sentinel else nc)

        x, ys = jax.lax.scan(
            body, x, (params["layers"], cache["layers"], cache["cross"]["k"], cache["cross"]["v"])
        )
        if sentinel:
            new_layers, probes = ys
            logits, head = self._paged_head(params, x, n_valid, probe=True)
            return (logits, {**cache, "layers": new_layers},
                    {"layers": probes, "head": head})
        return self._paged_head(params, x, n_valid), {**cache, "layers": ys}

    def _vlm_paged(self, params, cache, x, positions, n_valid, tables,
                   sentinel=False):
        cfg = self.cfg
        g = cfg.n_layers // cfg.cross_attn_every
        layers = self._group_tree(params["layers"], g)
        lcache = self._group_tree(cache["layers"], g)
        patches = cache["patches"]

        def group_body(x, scanned):
            gp, xp, gc = scanned
            x = self._xattn_block(xp, x, patches)

            def inner(x2, s2):
                lp, lc = s2
                h = apply_norm(cfg, lp["ln1"], x2)
                if "k_scale" in lc:
                    y, (nk, nv, nks, nvs), *pr = attn.attn_paged_chunk(
                        cfg, lp["mixer"], lc["k"], lc["v"], h,
                        positions, n_valid, tables,
                        scales=(lc["k_scale"], lc["v_scale"]),
                        probe=sentinel)
                    nc = {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs}
                else:
                    y, (nk, nv), *pr = attn.attn_paged_chunk(
                        cfg, lp["mixer"], lc["k"], lc["v"], h,
                        positions, n_valid, tables, probe=sentinel)
                    nc = {"k": nk, "v": nv}
                x2 = x2 + y
                h2 = apply_norm(cfg, lp["ln2"], x2)
                x2 = x2 + apply_mlp(cfg, lp["mlp"], h2)
                return x2, ((nc, pr[0]) if sentinel else nc)

            x, ys2 = jax.lax.scan(inner, x, (gp, gc))
            return x, ys2

        x, ys = jax.lax.scan(group_body, x, (layers, params["xattn_layers"], lcache))
        if sentinel:
            nlc, probes = ys
            nlc = jax.tree.map(lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), nlc)
            probes = probes.reshape(cfg.n_layers, *probes.shape[2:])
            logits, head = self._paged_head(params, x, n_valid, probe=True)
            return (logits, {**cache, "layers": nlc},
                    {"layers": probes, "head": head})
        nlc = jax.tree.map(lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), ys)
        return self._paged_head(params, x, n_valid), {**cache, "layers": nlc}

    # ----------------------------------------------------------- prefill ---
    def prefill(self, params, batch: dict, max_seq: int | None = None):
        """Prompt pass.  Returns (full-seq logits, decode-ready cache)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        tokens = batch["tokens"]
        b, s = tokens.shape
        max_seq = max_seq or s
        x = params["embed"]["tok"].astype(dt)[tokens]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        def fill_kv(kv):  # (B,S,KV,dh) -> (B,T,KV,dh) at positions [0, s)
            win = cfg.sliding_window or 0
            slots = min(max_seq, win) if win else max_seq
            out = jnp.zeros((b, slots, *kv.shape[2:]), kv.dtype)
            if win and s > win:
                kv = kv[:, -win:]
                out = jax.lax.dynamic_update_slice(out, kv, (0, 0, 0, 0))
                return jnp.roll(out, shift=s % win, axis=1) if win != slots else jnp.roll(out, shift=s % win, axis=1)
            return jax.lax.dynamic_update_slice(out, kv, (0, 0, 0, 0))

        memory = None
        if cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
        elif cfg.family == "vlm":
            memory = batch["patches"].astype(dt)

        if cfg.family == "hybrid":
            return self._hybrid_prefill(params, x, positions, max_seq, fill_kv)
        if cfg.family == "vlm":
            return self._vlm_prefill(params, x, positions, memory, fill_kv)

        def body(x, lp):
            h = apply_norm(cfg, lp["ln1"], x)
            if cfg.mla is not None:
                y, kv = mla_mod.mla_prefill(cfg, lp["mixer"], h, positions)
                kv = {
                    k: jax.lax.dynamic_update_slice(
                        jnp.zeros((b, max_seq, v.shape[-1]), v.dtype), v, (0, 0, 0)
                    )
                    for k, v in kv.items()
                }
            elif cfg.family == "ssm":
                blk = ssm_mod.mlstm_block if cfg.ssm.kind == "mlstm" else ssm_mod.mamba2_block
                y, carry = blk(cfg, lp["mixer"], h, exact=True)
                kv = {i: c for i, c in enumerate(carry)}
            else:
                y, kv = attn.attn_prefill(cfg, lp["mixer"], h, positions)
                kv = {k: fill_kv(v) for k, v in kv.items()}
            x = x + y
            if memory is not None and "xattn" in lp:
                hx = apply_norm(cfg, lp["ln_x"], x)
                x = x + attn.cross_attention(cfg, lp["xattn"], hx, memory)
                mk, mv = _project_cross_kv(cfg, lp["xattn"], memory)
                kv = {"k": kv["k"], "v": kv["v"], "xk": mk, "xv": mv}
            if "mlp" in lp:
                h2 = apply_norm(cfg, lp["ln2"], x)
                y = (
                    moe_mod.apply_moe(cfg, lp["mlp"], h2)[0]
                    if cfg.moe
                    else apply_mlp(cfg, lp["mlp"], h2)
                )
                x = x + y
            return x, kv

        x, kvs = jax.lax.scan(body, x, params["layers"])
        logits = _lm_head(cfg, params, x)
        if cfg.family == "encdec":
            cache = {
                "layers": {"k": kvs["k"], "v": kvs["v"]},
                "cross": {"k": kvs["xk"], "v": kvs["xv"]},
            }
        else:
            cache = {"layers": kvs}
        return logits, cache

    def _hybrid_prefill(self, params, x, positions, max_seq, fill_kv):
        cfg = self.cfg
        g = cfg.n_layers // cfg.attn_every
        layers = self._group_tree(params["layers"], g)
        shared = params["shared_attn"]
        b = x.shape[0]

        def group_body(x, gp):
            h = apply_norm(cfg, shared["ln1"], x)
            y, kv = attn.attn_prefill(cfg, shared["attn"], h, positions)
            x = x + y
            h = apply_norm(cfg, shared["ln2"], x)
            x = x + apply_mlp(cfg, shared["mlp"], h)
            kv = {k: fill_kv(v) for k, v in kv.items()}

            def inner(x2, lp):
                h2 = apply_norm(cfg, lp["ln1"], x2)
                y2, carry = ssm_mod.mamba2_block(cfg, lp["mixer"], h2, exact=True)
                return x2 + y2, {i: c for i, c in enumerate(carry)}

            x, carries = jax.lax.scan(inner, x, gp)
            return x, (kv, carries)

        x, (shared_kv, carries) = jax.lax.scan(group_body, x, layers)
        logits = _lm_head(cfg, params, x)
        L = cfg.n_layers
        carries = jax.tree.map(lambda a: a.reshape(L, *a.shape[2:]), carries)
        return logits, {"layers": carries, "shared": shared_kv}

    def _vlm_prefill(self, params, x, positions, patches, fill_kv):
        cfg = self.cfg
        g = cfg.n_layers // cfg.cross_attn_every
        layers = self._group_tree(params["layers"], g)

        def group_body(x, scanned):
            gp, xp = scanned
            x = self._xattn_block(xp, x, patches)

            def inner(x2, lp):
                h = apply_norm(cfg, lp["ln1"], x2)
                y, kv = attn.attn_prefill(cfg, lp["mixer"], h, positions)
                x2 = x2 + y
                h2 = apply_norm(cfg, lp["ln2"], x2)
                x2 = x2 + apply_mlp(cfg, lp["mlp"], h2)
                return x2, {k: fill_kv(v) for k, v in kv.items()}

            x, kvs = jax.lax.scan(inner, x, gp)
            return x, kvs

        x, kvs = jax.lax.scan(group_body, x, (layers, params["xattn_layers"]))
        logits = _lm_head(cfg, params, x)
        L = cfg.n_layers
        kvs = jax.tree.map(lambda a: a.reshape(L, *a.shape[2:]), kvs)
        return logits, {"layers": kvs, "patches": patches}

    # ------------------------------------------------------------ decode ---
    def decode_step(self, params, cache, token, pos):
        """token: (B, 1) int32; pos: scalar int32.  Returns (logits, cache)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = params["embed"]["tok"].astype(dt)[token]  # (B, 1, D)
        x = shard(x, "batch", None, "embed_act")

        if cfg.family == "hybrid":
            return self._hybrid_decode(params, cache, x, pos)
        if cfg.family == "vlm":
            return self._vlm_decode(params, cache, x, pos)
        if cfg.family == "encdec":
            return self._encdec_decode(params, cache, x, pos)

        def body(x, scanned):
            lp, lcache = scanned
            h = apply_norm(cfg, lp["ln1"], x)
            if cfg.mla is not None:
                y, nc = mla_mod.mla_decode_step(cfg, lp["mixer"], lcache, h, pos)
            elif cfg.family == "ssm":
                blk = ssm_mod.mlstm_block if cfg.ssm.kind == "mlstm" else ssm_mod.mamba2_block
                carry = tuple(lcache[i] for i in sorted(lcache))
                y, ncarry = blk(cfg, lp["mixer"], h, carry)
                nc = {i: c for i, c in enumerate(ncarry)}
            else:
                y, nc = attn.attn_decode_step(cfg, lp["mixer"], lcache, h, pos)
            x = x + y
            if "mlp" in lp:
                h2 = apply_norm(cfg, lp["ln2"], x)
                y = (
                    moe_mod.apply_moe(cfg, lp["mlp"], h2)[0]
                    if cfg.moe
                    else apply_mlp(cfg, lp["mlp"], h2)
                )
                x = x + y
            return x, nc

        x, new_layers = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        logits = _lm_head(cfg, params, x)
        return logits, {**cache, "layers": new_layers}

    def _hybrid_decode(self, params, cache, x, pos):
        cfg = self.cfg
        g = cfg.n_layers // cfg.attn_every
        layers = self._group_tree(params["layers"], g)
        lcache = self._group_tree(cache["layers"], g)
        shared = params["shared_attn"]

        def group_body(x, scanned):
            gp, gc, skv = scanned
            h = apply_norm(cfg, shared["ln1"], x)
            y, new_skv = attn.attn_decode_step(cfg, shared["attn"], skv, h, pos)
            x = x + y
            h = apply_norm(cfg, shared["ln2"], x)
            x = x + apply_mlp(cfg, shared["mlp"], h)

            def inner(x2, s2):
                lp, lc = s2
                h2 = apply_norm(cfg, lp["ln1"], x2)
                carry = tuple(lc[i] for i in sorted(lc))
                y2, ncarry = ssm_mod.mamba2_block(cfg, lp["mixer"], h2, carry)
                return x2 + y2, {i: c for i, c in enumerate(ncarry)}

            x, ncarries = jax.lax.scan(inner, x, (gp, gc))
            return x, (ncarries, new_skv)

        x, (ncar, nskv) = jax.lax.scan(group_body, x, (layers, lcache, cache["shared"]))
        L = cfg.n_layers
        ncar = jax.tree.map(lambda a: a.reshape(L, *a.shape[2:]), ncar)
        logits = _lm_head(cfg, params, x)
        return logits, {"layers": ncar, "shared": nskv}

    def _vlm_decode(self, params, cache, x, pos):
        cfg = self.cfg
        g = cfg.n_layers // cfg.cross_attn_every
        layers = self._group_tree(params["layers"], g)
        lcache = self._group_tree(cache["layers"], g)
        patches = cache["patches"]

        def group_body(x, scanned):
            gp, xp, gc = scanned
            x = self._xattn_block(xp, x, patches)

            def inner(x2, s2):
                lp, lc = s2
                h = apply_norm(cfg, lp["ln1"], x2)
                y, nc = attn.attn_decode_step(cfg, lp["mixer"], lc, h, pos)
                x2 = x2 + y
                h2 = apply_norm(cfg, lp["ln2"], x2)
                x2 = x2 + apply_mlp(cfg, lp["mlp"], h2)
                return x2, nc

            x, ngc = jax.lax.scan(inner, x, (gp, gc))
            return x, ngc

        x, nlc = jax.lax.scan(group_body, x, (layers, params["xattn_layers"], lcache))
        nlc = jax.tree.map(lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), nlc)
        logits = _lm_head(cfg, params, x)
        return logits, {**cache, "layers": nlc}

    def _encdec_decode(self, params, cache, x, pos):
        cfg = self.cfg

        def body(x, scanned):
            lp, lcache, xk, xv = scanned
            h = apply_norm(cfg, lp["ln1"], x)
            y, nc = attn.attn_decode_step(cfg, lp["mixer"], lcache, h, pos)
            x = x + y
            hx = apply_norm(cfg, lp["ln_x"], x)
            x = x + _cross_attend_cached(cfg, lp["xattn"], hx, xk, xv)
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + apply_mlp(cfg, lp["mlp"], h2)
            return x, nc

        x, new_layers = jax.lax.scan(
            body, x, (params["layers"], cache["layers"], cache["cross"]["k"], cache["cross"]["v"])
        )
        logits = _lm_head(cfg, params, x)
        return logits, {**cache, "layers": new_layers}


def _project_cross_kv(cfg: ModelConfig, p: dict, memory):
    dt = memory.dtype
    b, m, _ = memory.shape
    k = jnp.einsum("bmd,df->bmf", memory, p["wk"].astype(dt)).reshape(
        b, m, cfg.n_kv_heads, cfg.head_dim
    )
    v = jnp.einsum("bmd,df->bmf", memory, p["wv"].astype(dt)).reshape(
        b, m, cfg.n_kv_heads, cfg.head_dim
    )
    return k, v


def _cross_attend_cached(cfg: ModelConfig, p: dict, x, k, v):
    """Cross-attn with precomputed memory kv.  x: (B,S,D); k/v: (B,M,KV,dh)."""
    dt = x.dtype
    b, s, _ = x.shape
    q = jnp.einsum("bsd,df->bsf", x, p["wq"].astype(dt)).reshape(
        b, s, cfg.n_heads, cfg.head_dim
    )
    kvh = cfg.n_kv_heads
    group = cfg.n_heads // kvh
    qg = q.reshape(b, s, kvh, group, cfg.head_dim)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) * (cfg.head_dim**-0.5)
    from repro.core import get_softmax

    pmat = get_softmax(cfg.softmax_impl)(scores.astype(jnp.float32)).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", pmat, v).reshape(b, s, cfg.q_features)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(dt))


def make_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
