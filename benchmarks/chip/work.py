"""The work a serving tick needs, counted from the configuration's shapes.

These are the least operations and bytes any implementation of the
configuration has to spend, so that a share of the roofline built on them
cannot pass 100%:

* Weights are read once per tick, at the compute dtype (bfloat16, 2 bytes),
  whatever dtype they are stored in.  The embedding table is not read
  whole: only the rows of the tick's tokens.
* Every live slot reads its own context's keys and values once, at the
  pool's dtype, and writes those of its new tokens.  For MLA that is the
  latent (kv_lora_rank + qk_rope_head_dim values per token and layer).
* Matmul operations are 2 per parameter per token.  The head projects one
  row per live slot (the next-token row), not every prompt token.
* Attention operations are 2 * (qk width + v width) per head per (query,
  key) pair over the causal context.  For MLA those widths are
  qk_nope + qk_rope and v_head_dim: the expansion of cached latents through
  wkv_b is work an implementation may avoid (by absorbing wkv_b into the
  query, or by caching the expansion), so it is not counted.

Model flops (``model_flops``) are the same sums: what the step computes for
the tokens it processes, not what an implementation recomputes.
"""
from __future__ import annotations

COMPUTE_BYTES = 2   # bfloat16
KV_BYTES = 2        # the pool's bfloat16 blocks


def _mla(cfg: dict) -> bool:
    return "kv_lora_rank" in cfg


def head_dim(cfg: dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def layer_matmul_params(cfg: dict) -> int:
    """Parameters of one layer's matrices (norm gains left out)."""
    d, f, h = int(cfg["hidden_size"]), int(cfg["intermediate_size"]), int(cfg["num_attention_heads"])
    mlp = 3 * d * f  # SwiGLU: gate, up, down
    if _mla(cfg):
        qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
        dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        attn = (d * qr + qr * h * (dn + dr) + d * (kr + dr)
                + kr * h * (dn + dv) + h * dv * d)
    else:
        kv, dh = int(cfg["num_key_value_heads"]), head_dim(cfg)
        attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    return attn + mlp


def body_matmul_params(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) * layer_matmul_params(cfg)


def head_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def weight_bytes(cfg: dict) -> int:
    """Bytes of the matrices a tick reads once: body and head."""
    return (body_matmul_params(cfg) + head_params(cfg)) * COMPUTE_BYTES


def kv_bytes_per_token(cfg: dict) -> int:
    """Pool bytes per token over all layers."""
    layers = int(cfg["num_hidden_layers"])
    if _mla(cfg):
        per = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    else:
        per = 2 * int(cfg["num_key_value_heads"]) * head_dim(cfg)
    return layers * per * KV_BYTES


def attn_flops_per_pair(cfg: dict) -> int:
    """Operations per (query, key) pair over all heads and layers."""
    h, layers = int(cfg["num_attention_heads"]), int(cfg["num_hidden_layers"])
    if _mla(cfg):
        qk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        v = int(cfg["v_head_dim"])
    else:
        qk = v = head_dim(cfg)
    return 2 * (qk + v) * h * layers


def causal_pairs(tokens: int, start: int) -> int:
    """(query, key) pairs of ``tokens`` queries at positions start.. that
    each attend every key up to and including their own position."""
    return tokens * start + tokens * (tokens + 1) // 2


def tick_work(cfg: dict, lanes) -> tuple[float, float]:
    """(operations, bytes) of one tick; ``lanes`` lists (tokens, context
    start) for each live slot.  A tick with no live slot needs nothing."""
    if not lanes:
        return 0.0, 0.0
    d = int(cfg["hidden_size"])
    toks = sum(t for t, _ in lanes)
    pairs = sum(causal_pairs(t, s) for t, s in lanes)
    flops = (2 * body_matmul_params(cfg) * toks + 2 * head_params(cfg) * len(lanes)
             + attn_flops_per_pair(cfg) * pairs)
    kv = kv_bytes_per_token(cfg)
    nbytes = (weight_bytes(cfg) + toks * d * COMPUTE_BYTES
              + sum(s * kv for _, s in lanes) + toks * kv)
    return float(flops), float(nbytes)


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc = flops / float(peaks["bf16_flops_per_s"])
    tm = nbytes / float(peaks["hbm_bytes_per_s"])
    return (tc, "compute") if tc >= tm else (tm, "memory")
