"""The names the serving tick is traced under, and the host phase timer.

Device scopes: ``jax.named_scope`` names the layers of the paged tick in
each HLO instruction's ``op_name`` metadata, so a profiler trace credits
every device operation to the scopes on its path (a fusion carries the
path of its root instruction).

Host phases: ``phase`` opens a ``jax.profiler.TraceAnnotation`` over one
part of ``ContinuousEngine.step`` and adds its wall time to the engine's
cumulative ``host_phase_s`` counters, so one mechanism gives both the
trace spans and the counters.  ``trace_gc`` writes each pass of Python's
collector as a ``python.gc`` span.

A benchmark that drives the engine wraps each ``step()`` call in a span of
its own; the names in ``RESERVED`` are that caller's and are never used
here.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax

# device scopes of the paged tick, one per layer
SCOPES = (
    ATTN_PROJ,    # q/k/v projections, RoPE and the wo projection
    KV_WRITE,     # the scatter of the chunk's K/V into the block arenas
    PAGED_READ,   # the tile stream over the block table, scores, softmax, P.V
    GN_SOFTMAX,   # the GN softmax, nested inside PAGED_READ
    GN_NORM,      # every apply_norm: ln1, ln2 and the final norm
    MLP,          # apply_mlp with its weight casts
    LM_HEAD,      # row gather and final projection of the paged head
    GN_PROBE,     # the GN sentinel probes: Σp residual, health word, σ
) = ("attn.proj", "attn.kv_write", "attn.paged_read", "gn.softmax",
     "gn.norm", "mlp", "lm_head", "gn.probe")

# host phases of ContinuousEngine.step, in the order it runs them
PHASES = (
    "engine.check_tables",    # block-table redundancy check
    "engine.admit",           # admission of queued requests
    "engine.pool_ensure",     # block allocation, write barrier, table mirror, horizon
    "engine.upload",          # lane mirrors, chunk tokens, n_valid, prefill flags
    "engine.dispatch",        # the tick's jitted call
    "engine.device_get",      # the wait for the tick's tokens and health word
    "engine.sentinel_scan",   # host check of the GN health word
    "engine.commit",          # pool.advance, token append, finishing requests
)
GC_SPAN = "python.gc"
RESERVED = ("engine.step", "harness.window", "harness.submit",
            "harness.wait_arrival")


@contextlib.contextmanager
def phase(name: str, seconds: dict):
    """Span ``name`` on the profiler trace and add its wall time to
    ``seconds[name]``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        seconds[name] = seconds.get(name, 0.0) + (time.perf_counter() - t0)


_gc_open: list = []  # the span of the collector pass in progress


def _on_gc(stage: str, info: dict) -> None:
    if stage == "start":
        span = jax.profiler.TraceAnnotation(GC_SPAN)
        span.__enter__()
        _gc_open.append(span)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def trace_gc() -> None:
    """Write every collector pass as a ``python.gc`` span (once per
    process, however often it is called)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
