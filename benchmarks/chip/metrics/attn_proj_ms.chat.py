"""Mean device time per decode tick inside ``attn.proj``: the attention projections (q/k/v, RoPE, wo)."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "decode", "attn.proj")
