"""Model operations of the traced ticks over the traced window at the chips' peak."""
from readers import step_mfu as _r


def read(ctx):
    return _r(ctx)
