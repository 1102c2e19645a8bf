"""Share of the traced window in which no operation ran on the chip."""
from readers import idle_share as _r


def read(ctx):
    return _r(ctx)
