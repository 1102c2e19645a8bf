"""Mean device time of one fused (prefill and decode) tick."""
from readers import tick_ms as _r


def read(ctx):
    return _r(ctx, "fused")
