"""Mean device time per decode tick inside ``lm_head``: the head (row gather and final projection)."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "decode", "lm_head")
