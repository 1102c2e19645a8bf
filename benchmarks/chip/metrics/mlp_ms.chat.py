"""Mean device time per decode tick inside ``mlp``: the MLP."""
from scopes import scope_ms as _r


def read(ctx):
    return _r(ctx, "decode", "mlp")
