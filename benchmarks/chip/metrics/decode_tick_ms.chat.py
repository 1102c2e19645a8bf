"""Mean device time of one decode-only tick."""
from readers import tick_ms as _r


def read(ctx):
    return _r(ctx, "decode")
