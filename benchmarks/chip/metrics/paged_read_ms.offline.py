"""Mean device time per fused tick inside ``attn.paged_read``: the paged read (tile stream, scores, softmax, P.V)."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "fused", "attn.paged_read")
