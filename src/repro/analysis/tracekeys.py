"""Static enumeration of the engine's (step-kind × horizon-bucket) trace keys.

The engine promises compile-once ticks: every jitted step specializes only
on the step kind (fused vs decode) and, for paged pools, on the horizon
bucket (the power-of-two number of block-table columns the tick reads).
This module derives that trace-key space *from configuration alone* — no
engine, no tracing — so the compile-count pins in
``tests/_serve_helpers.assert_exact_compile_counters`` and the Pass A
``A-TRACEKEY`` audit share one source of truth instead of an empirical
constant.
"""
from __future__ import annotations

from typing import Iterable, Optional

STEP_KINDS = ("fused", "decode")


def horizon_bucket_grid(max_seq: int, block_size: int,
                        read_path: str = "streamed") -> list[int]:
    """Horizon buckets for a paged pool, as ``ContinuousEngine.__init__``
    builds them.

    Buckets double from 1 up to the per-slot block capacity, which is
    always the final bucket (so the full-horizon read is representable even
    when capacity is not a power of two).  The Pallas read (``read_path``
    'pallas') skips each slot's blocks past its context whatever the
    table's width, so its grid is that final bucket alone.
    """
    if max_seq <= 0 or block_size <= 0:
        raise ValueError(f"max_seq={max_seq}, block_size={block_size} must be positive")
    max_blocks_per_slot = -(-max_seq // block_size)
    if read_path == "pallas":
        return [max_blocks_per_slot]
    grid: list[int] = []
    b = 1
    while b < max_blocks_per_slot:
        grid.append(b)
        b *= 2
    grid.append(max_blocks_per_slot)
    return grid


def trace_key_space(
    *,
    paged: bool,
    max_seq: Optional[int] = None,
    block_size: Optional[int] = None,
    grid: Optional[Iterable[int]] = None,
) -> set[tuple[str, Optional[int]]]:
    """All (step_kind, bucket) keys a compliant engine may ever trace.

    Slab pools have no horizon dimension: the key space is
    ``{(fused, None), (decode, None)}``.  Paged pools cross the step kinds
    with the bucket grid (pass ``grid`` explicitly, or ``max_seq`` +
    ``block_size`` to derive it).
    """
    if not paged:
        return {(kind, None) for kind in STEP_KINDS}
    if grid is None:
        if max_seq is None or block_size is None:
            raise ValueError("paged trace_key_space needs grid or max_seq+block_size")
        grid = horizon_bucket_grid(max_seq, block_size)
    return {(kind, int(b)) for kind in STEP_KINDS for b in grid}


def compile_bound(
    *,
    paged: bool,
    max_seq: Optional[int] = None,
    block_size: Optional[int] = None,
    grid: Optional[Iterable[int]] = None,
) -> dict[str, int]:
    """Max compilations per step kind implied by the trace-key space."""
    keys = trace_key_space(paged=paged, max_seq=max_seq, block_size=block_size, grid=grid)
    return {kind: sum(1 for k, _ in keys if k == kind) for kind in STEP_KINDS}


def seen_trace_keys(metrics: dict) -> set[tuple[str, Optional[int]]]:
    """Trace keys an engine actually compiled, from ``engine.metrics()``."""
    if "horizon_bucket_grid" in metrics:
        return {("fused", int(b)) for b in metrics.get("fused_buckets", [])} | {
            ("decode", int(b)) for b in metrics.get("decode_buckets", [])
        }
    seen: set[tuple[str, Optional[int]]] = set()
    if metrics.get("fused_step_compilations", 0):
        seen.add(("fused", None))
    if metrics.get("decode_compilations", 0):
        seen.add(("decode", None))
    return seen


def format_trace_key_diff(
    expected: set[tuple[str, Optional[int]]],
    seen: set[tuple[str, Optional[int]]],
    counts: Optional[dict[str, int]] = None,
) -> str:
    """Human-readable expected-vs-seen trace-key table for assert messages."""

    def _fmt(keys: set[tuple[str, Optional[int]]]) -> str:
        if not keys:
            return "(none)"
        return ", ".join(
            f"({kind}, bucket={bucket})" if bucket is not None else f"({kind},)"
            for kind, bucket in sorted(keys, key=lambda k: (k[0], -1 if k[1] is None else k[1]))
        )

    lines = [
        "trace-key space (step kind, horizon bucket):",
        f"  allowed : {_fmt(expected)}",
        f"  seen    : {_fmt(seen)}",
    ]
    extra = seen - expected
    if extra:
        lines.append(f"  EXTRA (recompile hazard!): {_fmt(extra)}")
    if counts:
        lines.append(
            "  compilations: "
            + ", ".join(f"{kind}={n}" for kind, n in sorted(counts.items()))
        )
    return "\n".join(lines)
