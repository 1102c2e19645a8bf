"""Known-bad / known-good fixtures for the Pass A audit rules.

Each audit rule gets a compact synthetic program that violates exactly one
invariant, fed through the *real* audit machinery (``audit_entry_point`` /
``check_trace_keys``) — plus a good twin that passes clean.  These back
``--self-check`` (every rule still catches its fixture) and
``--break-invariant RULE`` (non-zero exit with the responsible rule id,
the acceptance-criteria drill).

Lint-rule fixtures are source snippets and live on the rules themselves
(``rules.LINT_RULES``); this module covers the rules that need traced
programs rather than source text.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.analysis.audit import EntryPoint, audit_entry_point, check_trace_keys
from repro.analysis.findings import Finding

# A tiny synthetic paged arena: (L=1, nb=8, bs=4, d=6), 2 slots, bucket 4.
# The (L, nb) entry mirrors a quantized pool's per-block scale leaf — the
# shape matchers must skip it (it is not an arena).
_L, _NB, _BS, _D = 1, 8, 4, 6
_N, _BUCKET = 2, 4
_LEAF_SHAPES = [(_L, _NB, _BS, _D), (_L, _NB)]
_ARENA = jax.ShapeDtypeStruct((_NB, _BS, _D), jnp.float32)
_ARENA_I8 = jax.ShapeDtypeStruct((_NB, _BS, _D), jnp.int8)
_SCALE = jax.ShapeDtypeStruct((_NB,), jnp.float32)
_TABLES = jax.ShapeDtypeStruct((_N, _BUCKET), jnp.int32)


def _gathered_read(arena, tables):
    # materializes the whole bucketed stream in one arena gather
    stream = arena[tables]                      # (N, BUCKET, BS, D)
    return stream.reshape(_N, _BUCKET * _BS, _D).sum(axis=1)


def _streamed_read(arena, tables):
    # one tile at a time: no gather output ever exceeds a single block
    def body(acc, tbl_col):
        tile = arena[tbl_col]                   # (N, BS, D)
        return acc + tile.sum(axis=1), None
    init = jnp.zeros((_N, _D), jnp.float32)
    acc, _ = jax.lax.scan(body, init, tables.T)
    return acc


def _entry(name, fn, avals, *, donate=(), budget=None, bucket=None,
           quantized=False, sentinel_outputs=0):
    return EntryPoint(
        name=name, jitfn=jax.jit(fn, donate_argnums=donate), avals=avals,
        donate=donate, gather_budget=budget, bucket=bucket,
        quantized=quantized, sentinel_outputs=sentinel_outputs,
    )


def _audit(ep) -> list[Finding]:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the dropped-donation UserWarning
        return audit_entry_point(
            ep, f"fixture:{ep.name}",
            layer_leaf_shapes=_LEAF_SHAPES, num_slots=_N,
        )


# --------------------------------------------------------- per-rule pairs --
def _gather_bad():
    return _audit(_entry("gathered_read_as_streamed", _gathered_read,
                         (_ARENA, _TABLES), budget=0, bucket=_BUCKET))


def _gather_good():
    return _audit(_entry("streamed_read", _streamed_read,
                         (_ARENA, _TABLES), budget=0, bucket=_BUCKET))


def _donate_bad():
    # cache donated but never used -> jax drops the donation silently
    def f(cache, x):
        return x * 2.0
    return _audit(_entry("donated_unused", f, (_ARENA, _ARENA), donate=(0,)))


def _donate_good():
    def f(cache, x):
        return cache + x
    return _audit(_entry("donated_in_place", f, (_ARENA, _ARENA), donate=(0,)))


def _f64_bad():
    def f(x):
        return jnp.cumsum(x) * 2.0
    aval = jax.ShapeDtypeStruct((8,), jnp.float64)
    with jax.enable_x64(True):
        return _audit(_entry("f64_tick", f, (aval,)))


def _f64_good():
    def f(x):
        return jnp.cumsum(x) * 2.0
    return _audit(_entry("f32_tick", f, (jax.ShapeDtypeStruct((8,), jnp.float32),)))


def _transfer_bad():
    def f(x):
        jax.debug.print("tick {}", x.sum())
        return x * 2.0
    return _audit(_entry("callback_in_tick", f,
                         (jax.ShapeDtypeStruct((8,), jnp.float32),)))


def _transfer_good():
    def f(x):
        return x * 2.0
    return _audit(_entry("pure_tick", f,
                         (jax.ShapeDtypeStruct((8,), jnp.float32),)))


def _metrics(fused_buckets, decode_buckets, grid, extra_fused=0):
    return {
        "horizon_bucket_grid": list(grid),
        "fused_buckets": list(fused_buckets),
        "decode_buckets": list(decode_buckets),
        "fused_step_compilations": len(fused_buckets) + extra_fused,
        "decode_compilations": len(decode_buckets),
        "prefill_compilations": 0,
        "fused_ticks": 1,
        "kv_paged": True,
    }


def _tracekey_bad():
    # one more fused compilation than buckets seen: an off-grid retrace
    m = _metrics([1, 2], [1], grid=[1, 2, 4], extra_fused=1)
    return check_trace_keys(m, "fixture:tracekey_extra_compile",
                            paged=True, max_seq=16, block_size=4,
                            engine_grid=[1, 2, 4])


def _quant_bad():
    # a quantized-mode tick reading a FLOAT arena: the fp stream exists in
    # HBM and the gather upcasts nothing — it was never int8 to begin with
    return _audit(_entry("fp_arena_in_quant_mode", _gathered_read,
                         (_ARENA, _TABLES), quantized=True))


def _quant_good():
    # int8 arena + per-block scales, dequant per streamed tile AFTER the
    # table read — no fp value ever has the arena's shape
    def quant_streamed(arena, scale, tables):
        def body(acc, tbl_col):
            tile = arena[tbl_col].astype(jnp.float32)   # (N, BS, D)
            tile = tile * scale[tbl_col][:, None, None]
            return acc + tile.sum(axis=1), None
        init = jnp.zeros((_N, _D), jnp.float32)
        acc, _ = jax.lax.scan(body, init, tables.T)
        return acc
    return _audit(_entry("int8_streamed_dequant", quant_streamed,
                         (_ARENA_I8, _SCALE, _TABLES), quantized=True))


def _sentinel_bad():
    # the probe was "optimized away": the tick emits a constant healthy
    # word regardless of what flows through the attention read
    def tick(arena, tables):
        out = _streamed_read(arena, tables)
        health = jnp.zeros((_N,), jnp.float32)          # disconnected
        return out, health
    return _audit(_entry("constant_health_tick", tick, (_ARENA, _TABLES),
                         sentinel_outputs=1))


def _sentinel_good():
    # health derived from the read itself: Σ-residual style reduction
    def tick(arena, tables):
        out = _streamed_read(arena, tables)
        health = jnp.abs(out.sum(axis=-1) - 1.0)
        return out, health
    return _audit(_entry("probed_tick", tick, (_ARENA, _TABLES),
                         sentinel_outputs=1))


def _tracekey_good():
    m = _metrics([1, 2], [1], grid=[1, 2, 4])
    return check_trace_keys(m, "fixture:tracekey_exact",
                            paged=True, max_seq=16, block_size=4,
                            engine_grid=[1, 2, 4])


AUDIT_FIXTURES = {
    "A-GATHER": (_gather_bad, _gather_good),
    "A-DONATE": (_donate_bad, _donate_good),
    "A-F64": (_f64_bad, _f64_good),
    "A-TRANSFER": (_transfer_bad, _transfer_good),
    "A-TRACEKEY": (_tracekey_bad, _tracekey_good),
    "A-QUANT": (_quant_bad, _quant_good),
    "A-SENTINEL": (_sentinel_bad, _sentinel_good),
}


def run_fixture(rule_id: str, which: str = "bad") -> list[Finding]:
    """Run one audit fixture through the real pipeline; returns findings."""
    bad, good = AUDIT_FIXTURES[rule_id]
    return bad() if which == "bad" else good()
