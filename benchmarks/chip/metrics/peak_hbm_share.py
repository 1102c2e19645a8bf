"""Peak device memory in use over the chip's HBM."""
from readers import peak_hbm_share as _r


def read(ctx):
    return _r(ctx)
