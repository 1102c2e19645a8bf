"""Mean device time per fused tick inside ``attn.kv_write``: the KV write into the block arenas."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "fused", "attn.kv_write")
