"""Median wait from a request's due time to its admission, over the requests due in the window."""
from readers import queue_wait_ms as _r


def read(ctx):
    return _r(ctx)
