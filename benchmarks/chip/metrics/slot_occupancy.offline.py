"""Mean share of slots live per tick inside the window."""
from readers import slot_occupancy as _r


def read(ctx):
    return _r(ctx)
