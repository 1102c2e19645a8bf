"""Mean device time per fused tick inside ``gn.probe``: the GN sentinel probes."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "fused", "gn.probe")
