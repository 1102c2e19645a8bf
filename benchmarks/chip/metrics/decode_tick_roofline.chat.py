"""Least time of the traced decode ticks (work.py) over their device time."""
from readers import roofline as _r


def read(ctx):
    return _r(ctx, "decode")
