"""Mean device time per fused tick inside ``gn.softmax``: the GN softmax."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "fused", "gn.softmax")
