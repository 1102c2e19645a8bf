"""Median host time of a tick: its ``engine.step`` span less the wait for its tokens."""
from scopes import host_tick_ms as _r


def read(ctx):
    return _r(ctx)
