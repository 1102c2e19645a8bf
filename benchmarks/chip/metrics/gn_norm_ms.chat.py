"""Mean device time per decode tick inside ``gn.norm``: the GN norms."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "decode", "gn.norm")
