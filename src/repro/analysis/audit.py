"""Pass A: the jaxpr/HLO invariant auditor.

For each registry arch, build a smoke-scale engine (the same
``reduce_config`` shapes the serve tests pin), run a small mixed-length
workload so the ``CountingJit`` entry points capture their real call
signatures (as ShapeDtypeStructs — donated buffers are never held), then
re-trace every jitted serving entry point and assert the structural
invariants:

* **A-GATHER** — paged tick jaxprs contain no stream-materializing arena
  gather beyond the read path's budget (streamed dense KV: exactly the
  one bucketed V read; streamed MLA: zero — both latent tiles stream;
  pallas: zero outside the kernel; the gathered oracle: its two
  full-stream reads, and no more).
* **A-DONATE** — every ``donate_argnums`` leaf produces an input-output
  aliasing mark in the lowered module (``tf.aliasing_output``) and, for
  the tick entry points, an ``input_output_alias`` entry in the compiled
  executable.  Catches silently-dropped donation that doubles KV HBM.
* **A-F64** — no float64/complex128 value anywhere in a tick jaxpr (the
  classic silent-upcast hazard on CPU hosts with x64 enabled).
* **A-TRANSFER** — no host-transfer/callback primitive inside a tick
  body (the runtime twin is the ``jax.transfer_guard`` around tick
  dispatch in ``engine.step``).
* **A-TRACEKEY** — the statically enumerated (step kind × horizon
  bucket) trace-key space (``tracekeys``) contains every key the run
  actually traced, and the CountingJit totals equal the per-kind seen
  counts, bounded by the derived grid — the same single-source bound
  ``tests/_serve_helpers.assert_exact_compile_counters`` asserts.
* **A-QUANT** — quantized-mode (kv_dtype=int8) programs never hold a
  floating-typed value at a full KV arena shape: the int8 arena is the
  only arena, dequant happens strictly per gathered tile (after the
  block-table read), and in particular no upcast-then-gather — a float
  gather operand at arena shape means the whole fp stream was
  materialized before the table was consulted, which is exactly the
  HBM-doubling rewrite the quantized path exists to avoid.
* **A-SENTINEL** — when GN runtime sentinels are enabled, the tick's
  trailing health outputs are data-dependent on the tick's inputs
  (backward-reachable to the jaxpr invars).  A constant-foldable health
  word means the probes were disconnected — the engine would read
  "healthy" forever while corruption flows through undetected.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import tracekeys
from repro.analysis.findings import Finding
from repro.analysis.jaxpr_walk import (
    eqns_by_name,
    iter_eqns,
    out_dtypes,
    primitive_names,
)
from repro.configs.registry import get_config, list_archs, reduce_config
from repro.models import attention
from repro.models.transformer import make_model
from repro.serve import kv_cache
from repro.serve.engine import ContinuousEngine, ServeConfig
from repro.serve.workload import required_max_seq, staggered_requests

# Primitives that move data across the host boundary (or call back into
# python) — none may appear inside a tick body.  device_put is checked
# separately: jnp.asarray on a traced value lowers to a no-op aliasing
# device_put (devices=[None]) that XLA elides; only an explicit target
# device or memory kind is a real transfer.
TRANSFER_PRIMITIVES = frozenset({
    "infeed", "outfeed",
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
})

# Float dtypes a tick may produce.  Everything else (notably float64 /
# complex128) is an upcast bug: the serving stack computes in the model
# dtype and accumulates in float32, never wider.
ALLOWED_FLOAT_DTYPES = {"bfloat16", "float16", "float32"}

# Per-read-path stream-gather budgets for dense-KV / MLA paged ticks.
GATHER_BUDGETS = {
    ("streamed", False): 1,   # the bucketed V read; K streams tile-by-tile
    ("streamed", True): 0,    # MLA: latent + rope tiles both stream
    ("pallas", False): 0,     # the kernel IS the read; nothing outside it
    ("gathered", False): 2,   # the oracle's full K and V streams
    ("gathered", True): 2,    # the MLA oracle's latent + rope streams
}


@dataclasses.dataclass
class EntryPoint:
    """One jitted serving entry point, described abstractly."""

    name: str
    jitfn: object                      # has .trace(*avals)
    avals: tuple                       # ShapeDtypeStruct pytree per arg
    donate: tuple = ()
    gather_budget: Optional[int] = None  # None: skip the gather audit
    bucket: Optional[int] = None       # horizon bucket of this signature
    compile_donation: bool = False     # verify aliasing in the executable
    quantized: bool = False            # run the A-QUANT no-fp-arena check
    sentinel_outputs: int = 0          # trailing flat outputs = health words


def read_path_for(cfg) -> str:
    from repro.models.mla import mla_paged_read_path

    return (mla_paged_read_path(cfg) if cfg.mla is not None
            else attention.paged_read_path(cfg))


def build_engine(arch: str, *, num_slots: int = 2, chunk: int = 4,
                 block_size: int = 4, kv_dtype: str = "fp"):
    """Smoke-scale engine + its workload for one registry arch.  The audit
    runs on the CPU, so a forced 'pallas' read runs interpreted."""
    cfg = dataclasses.replace(reduce_config(get_config(arch)),
                              pallas_interpret=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = staggered_requests(cfg, n_requests=4, base_len=12,
                              max_new_tokens=4, stagger=1)
    kw = dict(num_slots=num_slots, max_seq=required_max_seq(reqs),
              cfg=ServeConfig(), chunk=chunk)
    if model.supports_paging:
        kw["block_size"] = block_size
        kw["kv_dtype"] = kv_dtype
    engine = ContinuousEngine(model, params, **kw)
    return engine, reqs


def _to_avals(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _captured_signature(cjit, *, largest_bucket: bool):
    """Pick one captured aval signature from a CountingJit.

    Paged tick signatures differ only in the block-table width (the
    horizon bucket, the trailing arg's second dim); the gather audit needs
    the widest one so tile-sized and stream-sized reads are
    distinguishable (they coincide at bucket 1)."""
    sigs = cjit.capture_avals or {}
    if not sigs:
        return None, None
    if not largest_bucket:
        return next(iter(sigs.values())), None

    def bucket_of(avals):
        tables = jax.tree.leaves(avals[-1])
        return tables[0].shape[1] if tables and len(tables[0].shape) == 2 else 0

    best = max(sigs.values(), key=bucket_of)
    return best, bucket_of(best)


def collect_entry_points(engine, *, paged_budget_path: Optional[str] = None,
                         compile_donation: bool = True) -> list[EntryPoint]:
    """Every jitted serving entry point the engine/pool can dispatch, with
    the aval signatures a real workload produced (ticks) or the pool's
    state implies (fork/spill/insert)."""
    eps: list[EntryPoint] = []
    paged = engine.paged
    cfg = engine.model.cfg
    if paged_budget_path is None and paged:
        paged_budget_path = read_path_for(cfg)
    budget = (GATHER_BUDGETS.get((paged_budget_path, cfg.mla is not None))
              if paged else None)

    # sentinel-enabled ticks append the health pytree {'head', 'layers'} —
    # two trailing flat outputs that A-SENTINEL pins to the tick's inputs
    n_sentinel = 2 if getattr(engine, "sentinels", False) else 0
    for name, cjit in (("fused_tick", engine._fused),
                       ("decode_tick", engine._decode)):
        avals, bucket = _captured_signature(cjit, largest_bucket=paged)
        if avals is None:
            continue
        eps.append(EntryPoint(
            name=name, jitfn=cjit, avals=avals,
            donate=cjit.donate_argnums,
            gather_budget=budget, bucket=bucket,
            compile_donation=compile_donation,
            sentinel_outputs=n_sentinel,
        ))

    cache_avals = _to_avals(engine.pool.cache)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    if paged:
        pool = engine.pool
        npad = pool.max_blocks_per_slot
        ix = jax.ShapeDtypeStruct((npad,), jnp.int32)
        layers_avals = _to_avals(pool.cache["layers"])
        host_avals = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((a.shape[0], npad) + a.shape[2:],
                                           a.dtype),
            layers_avals,
        )
        eps.append(EntryPoint(
            name="prefix_cow_fork",
            jitfn=jax.jit(kv_cache.fork_block, donate_argnums=(0,)),
            avals=(cache_avals, i32, i32), donate=(0,),
        ))
        eps.append(EntryPoint(
            name="spill_gather",
            jitfn=jax.jit(kv_cache.spill_gather),
            avals=(layers_avals, ix), donate=(),
        ))
        eps.append(EntryPoint(
            name="spill_restore",
            jitfn=jax.jit(kv_cache.spill_scatter, donate_argnums=(0,)),
            avals=(cache_avals, host_avals, ix), donate=(0,),
        ))
    else:
        request_avals = jax.eval_shape(
            lambda: engine.model.init_cache(1, engine.max_seq)
        )
        eps.append(EntryPoint(
            name="slot_insert",
            jitfn=jax.jit(engine.model.insert_cache_slot, donate_argnums=(0,)),
            avals=(cache_avals, request_avals, i32), donate=(0,),
        ))
    return eps


# ------------------------------------------------------------- checks ---
def _arena_block_elems(shape, layer_leaf_shapes) -> Optional[int]:
    """If ``shape`` is a paged arena leaf (possibly layer-stripped or
    block-flattened), return the element count of ONE block; else None."""
    for leaf in layer_leaf_shapes:
        if len(leaf) < 3:
            continue  # per-block scale leaves (L, nb) are not arenas
        L, nb, bs, *rest = leaf
        rest = tuple(rest)
        block = bs * int(np.prod(rest, dtype=np.int64)) if rest else bs
        if shape in ((L, nb, bs) + rest, (nb, bs) + rest, (nb * bs,) + rest):
            return block
    return None


def stream_gather_hits(jaxpr, layer_leaf_shapes, num_slots: int,
                       bucket: int) -> list[str]:
    """Gather equations whose operand is a paged arena and whose output
    materializes at least the full bucketed stream (num_slots × bucket
    blocks) — the reads the streamed/pallas paths exist to eliminate."""
    hits = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "gather":
            continue
        op = eqn.invars[0].aval
        block = _arena_block_elems(tuple(op.shape), layer_leaf_shapes)
        if block is None:
            continue
        out = eqn.outvars[0].aval
        if out.size >= num_slots * bucket * block:
            hits.append(f"{tuple(op.shape)} -> {tuple(out.shape)}")
    return hits


def _is_fp_arena(aval, layer_leaf_shapes) -> bool:
    try:
        fp = (np.issubdtype(aval.dtype, np.floating)
              or np.issubdtype(aval.dtype, np.complexfloating))
    except TypeError:
        return False
    return (fp and _arena_block_elems(tuple(aval.shape), layer_leaf_shapes)
            is not None)


def quantized_fp_arena_hits(jaxpr, layer_leaf_shapes) -> list[str]:
    """Floating-typed values at a full KV arena shape in a quantized-mode
    program.  The int8 contract: the arena leaves stay int8 end to end and
    dequant is per gathered tile (strictly after the block-table read) —
    so ANY fp value the size of the whole arena means the fp stream was
    materialized.  The gather case is called out separately: a float
    arena-shaped gather operand is the silent upcast-then-gather rewrite
    (dequantize everything, then read), which doubles arena HBM."""
    hits = []
    for eqn in iter_eqns(jaxpr):
        if (eqn.primitive.name == "gather" and eqn.invars
                and _is_fp_arena(eqn.invars[0].aval, layer_leaf_shapes)):
            op = eqn.invars[0].aval
            hits.append(
                f"upcast-then-gather: gather over fp arena "
                f"{tuple(op.shape)} {op.dtype}"
            )
        for v in eqn.outvars:
            if _is_fp_arena(v.aval, layer_leaf_shapes):
                hits.append(
                    f"{eqn.primitive.name} -> fp arena-shaped "
                    f"{tuple(v.aval.shape)} {v.aval.dtype}"
                )
    return hits


def sentinel_constant_outputs(jaxpr, n_outputs: int) -> list[str]:
    """The trailing ``n_outputs`` outvars of ``jaxpr`` that are NOT
    data-dependent on any jaxpr input — backward reachability over the
    top-level equations (sub-jaxpr bodies need not be entered: a scan/cond
    whose *equation* consumes an input makes its outputs dependent).  A
    health output that only reaches literals/constants is a disconnected
    probe: it would fold to the same 'healthy' word for every tick."""
    from jax._src.core import Literal

    core = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    producers = {v: eqn for eqn in core.eqns for v in eqn.outvars}
    invars = set(core.invars)
    hits = []
    for i, out in enumerate(core.outvars[-n_outputs:]):
        if isinstance(out, Literal):
            hits.append(f"output[-{n_outputs - i}]: literal {out.val!r}")
            continue
        stack, seen, dependent = [out], set(), False
        while stack:
            v = stack.pop()
            if v in invars:
                dependent = True
                break
            if id(v) in seen:
                continue
            seen.add(id(v))
            eqn = producers.get(v)
            if eqn is None:
                continue  # constvar: constant-folded, keep scanning others
            stack.extend(iv for iv in eqn.invars
                         if not isinstance(iv, Literal))
        if not dependent:
            hits.append(
                f"output[-{n_outputs - i}] {out.aval}: no data path to any "
                "tick input"
            )
    return hits


def audit_entry_point(ep: EntryPoint, where: str, *,
                      layer_leaf_shapes=(), num_slots: int = 1) -> list[Finding]:
    findings: list[Finding] = []
    traced = ep.jitfn.trace(*ep.avals)
    jaxpr = traced.jaxpr

    # A-SENTINEL
    if ep.sentinel_outputs:
        hits = sentinel_constant_outputs(jaxpr, ep.sentinel_outputs)
        if hits:
            findings.append(Finding(
                "A-SENTINEL", "error", where,
                f"{len(hits)} sentinel health output(s) not data-dependent "
                f"on the tick inputs (probe disconnected — corruption would "
                f"read as healthy): {hits}",
            ))

    # A-QUANT
    if ep.quantized and layer_leaf_shapes:
        hits = quantized_fp_arena_hits(jaxpr, layer_leaf_shapes)
        if hits:
            findings.append(Finding(
                "A-QUANT", "error", where,
                f"{len(hits)} fp-typed KV arena value(s) in a quantized-mode "
                f"program (int8 arenas must never materialize the fp "
                f"stream): {hits}",
            ))

    # A-GATHER
    if ep.gather_budget is not None and ep.bucket and ep.bucket > 1:
        hits = stream_gather_hits(jaxpr, layer_leaf_shapes, num_slots,
                                  ep.bucket)
        if len(hits) > ep.gather_budget:
            findings.append(Finding(
                "A-GATHER", "error", where,
                f"{len(hits)} stream-materializing arena gathers, budget "
                f"{ep.gather_budget} (bucket={ep.bucket}): {hits}",
            ))

    # A-DONATE
    if ep.donate:
        expected = sum(len(jax.tree.leaves(ep.avals[i])) for i in ep.donate)
        lowered = traced.lower()
        marks = lowered.as_text().count("tf.aliasing_output")
        if marks != expected:
            findings.append(Finding(
                "A-DONATE", "error", where,
                f"donate_argnums={ep.donate}: {expected} donated leaves but "
                f"{marks} aliasing marks in the lowered module — donation "
                "dropped (the buffer will be copied, not reused)",
            ))
        elif ep.compile_donation:
            txt = lowered.compile().as_text()
            aliased = txt.count("may-alias") + txt.count("must-alias")
            if aliased != expected:
                findings.append(Finding(
                    "A-DONATE", "error", where,
                    f"compiled executable aliases {aliased} buffers, expected "
                    f"{expected} (input_output_alias dropped by the backend)",
                ))

    # A-F64
    def _wide_float(dt) -> bool:
        try:
            return (np.issubdtype(dt, np.floating)
                    or np.issubdtype(dt, np.complexfloating))
        except TypeError:
            return False  # extended dtypes (PRNG keys) aren't numpy dtypes
    bad = sorted(
        str(dt) for dt in out_dtypes(jaxpr)
        if _wide_float(dt) and str(dt) not in ALLOWED_FLOAT_DTYPES
    )
    if bad:
        findings.append(Finding(
            "A-F64", "error", where,
            f"wide float dtypes in traced program: {bad} (allowed: "
            f"{sorted(ALLOWED_FLOAT_DTYPES)})",
        ))

    # A-TRANSFER
    present = primitive_names(jaxpr) & TRANSFER_PRIMITIVES
    if present:
        findings.append(Finding(
            "A-TRANSFER", "error", where,
            f"host-transfer/callback primitives inside the body: {sorted(present)}",
        ))
    placed = [
        eqn.params for eqn in eqns_by_name(jaxpr, "device_put")
        if any(d is not None for d in eqn.params.get("devices", []))
        or any(s is not None for s in eqn.params.get("srcs", []))
    ]
    if placed:
        findings.append(Finding(
            "A-TRANSFER", "error", where,
            f"device_put with an explicit placement inside the body "
            f"(forces a transfer): {placed}",
        ))
    return findings


def check_trace_keys(metrics: dict, where: str, *, paged: bool,
                     max_seq: int = 0, block_size: int = 0,
                     engine_grid=None) -> list[Finding]:
    """Engine-independent core of the A-TRACEKEY audit (fixture-drivable):
    derive the grid from config, compare it to what the engine/metrics
    carry, and pin the CountingJit totals to the seen-key counts."""
    findings: list[Finding] = []
    if paged:
        derived = tracekeys.horizon_bucket_grid(max_seq, block_size)
        for label, grid in (("engine", engine_grid),
                            ("metrics", metrics.get("horizon_bucket_grid"))):
            if grid is not None and list(grid) != derived:
                findings.append(Finding(
                    "A-TRACEKEY", "error", where,
                    f"{label} grid {list(grid)} != derived grid {derived} "
                    f"(max_seq={max_seq}, block_size={block_size})",
                ))
                return findings
        expected = tracekeys.trace_key_space(paged=True, grid=derived)
        bound = tracekeys.compile_bound(paged=True, grid=derived)
    else:
        expected = tracekeys.trace_key_space(paged=False)
        bound = tracekeys.compile_bound(paged=False)
    seen = tracekeys.seen_trace_keys(metrics)
    counts = {"fused": metrics["fused_step_compilations"],
              "decode": metrics["decode_compilations"]}
    diff = tracekeys.format_trace_key_diff(expected, seen, counts)
    if not seen <= expected:
        findings.append(Finding(
            "A-TRACEKEY", "error", where,
            "traced keys outside the enumerated space\n" + diff,
        ))
    if paged:
        exact = {k: sum(1 for kind, _ in seen if kind == k)
                 for k in tracekeys.STEP_KINDS}
    else:
        exact = {"fused": min(1, metrics.get("fused_ticks", 1)),
                 "decode": counts["decode"]}  # decode tick is workload-dependent
    for kind in tracekeys.STEP_KINDS:
        if counts[kind] != exact[kind] or counts[kind] > bound[kind]:
            findings.append(Finding(
                "A-TRACEKEY", "error", where,
                f"{kind} compilations {counts[kind]} != seen-key count "
                f"{exact[kind]} (bound {bound[kind]})\n" + diff,
            ))
    if metrics.get("prefill_compilations", 0) != 0:
        findings.append(Finding(
            "A-TRACEKEY", "error", where,
            f"prefill_compilations={metrics['prefill_compilations']} — "
            "per-prompt-length tracing reintroduced",
        ))
    return findings


def audit_trace_keys(engine, metrics: dict, where: str) -> list[Finding]:
    return check_trace_keys(
        metrics, where, paged=engine.paged,
        max_seq=engine.max_seq,
        block_size=engine.pool.block_size if engine.paged else 0,
        engine_grid=engine.horizon_bucket_grid if engine.paged else None,
    )


# -------------------------------------------------------------- driver ---
def audit_arch(arch: str, *, tier: str = "full",
               compile_donation: bool = True) -> list[Finding]:
    """Run the full Pass A audit for one arch.  ``tier='full'`` adds the
    forced gathered-oracle and (dense-KV) pallas read-path variants."""
    findings: list[Finding] = []
    engine, reqs = build_engine(arch)
    engine._fused.capture_avals = {}
    engine._decode.capture_avals = {}
    engine.run(reqs)
    metrics = engine.metrics()
    findings.extend(audit_trace_keys(engine, metrics, f"{arch}:trace_keys"))
    leaf_shapes = ([tuple(l.shape)
                    for l in jax.tree.leaves(engine.pool.cache["layers"])]
                   if engine.paged else ())
    for ep in collect_entry_points(engine, compile_donation=compile_donation):
        findings.extend(audit_entry_point(
            ep, f"{arch}:{ep.name}",
            layer_leaf_shapes=leaf_shapes, num_slots=engine.num_slots,
        ))

    if tier == "full" and engine.paged:
        # Re-trace the tick under each forced read path: the gathered
        # oracle must stay within ITS budget (2 full-stream reads), and the
        # pallas path must route through the kernel with zero XLA-level
        # stream gathers.  Trace-only — no run, no compile.
        variants = ["gathered"]
        if engine.model.cfg.mla is None:
            variants.append("pallas")
        base_sig, bucket = _captured_signature(engine._fused,
                                              largest_bucket=True)
        for path in variants:
            prev = attention.FORCE_PAGED_READ
            attention.FORCE_PAGED_READ = path
            try:
                v_engine, _ = build_engine(arch)
                ep = EntryPoint(
                    name=f"fused_tick[{path}]", jitfn=v_engine._fused,
                    avals=base_sig, donate=v_engine._fused.donate_argnums,
                    gather_budget=GATHER_BUDGETS[
                        (path, engine.model.cfg.mla is not None)],
                    bucket=bucket, compile_donation=False,
                )
                findings.extend(audit_entry_point(
                    ep, f"{arch}:{ep.name}",
                    layer_leaf_shapes=leaf_shapes,
                    num_slots=v_engine.num_slots,
                ))
                if path == "pallas":
                    traced = ep.jitfn.trace(*ep.avals)
                    if "pallas_call" not in primitive_names(traced.jaxpr):
                        findings.append(Finding(
                            "A-GATHER", "error", f"{arch}:{ep.name}",
                            "forced pallas read path traced without a "
                            "pallas_call — the kernel is not wired in",
                        ))
            finally:
                attention.FORCE_PAGED_READ = prev

        # Quantized-mode variant: run the same smoke workload with int8
        # arenas, then audit every entry point with the A-QUANT no-fp-arena
        # check active and re-pin the trace-key bounds — kv_dtype must not
        # add compile keys (the bucket grid is dtype-independent).
        q_engine, q_reqs = build_engine(arch, kv_dtype="int8")
        q_engine._fused.capture_avals = {}
        q_engine._decode.capture_avals = {}
        q_engine.run(q_reqs)
        q_metrics = q_engine.metrics()
        findings.extend(audit_trace_keys(
            q_engine, q_metrics, f"{arch}:int8:trace_keys"))
        q_leaf_shapes = [tuple(l.shape)
                         for l in jax.tree.leaves(q_engine.pool.cache["layers"])]
        for ep in collect_entry_points(q_engine, compile_donation=False):
            ep.quantized = True
            findings.extend(audit_entry_point(
                ep, f"{arch}:int8:{ep.name}",
                layer_leaf_shapes=q_leaf_shapes,
                num_slots=q_engine.num_slots,
            ))
    return findings


def run_audit(archs: Optional[list[str]] = None, *, tier: str = "full",
              compile_donation: bool = True,
              log=lambda msg: None) -> tuple[list[Finding], list[str]]:
    archs = list(archs) if archs else list_archs()
    findings: list[Finding] = []
    for arch in archs:
        log(f"audit: {arch}")
        findings.extend(
            audit_arch(arch, tier=tier, compile_donation=compile_donation)
        )
    return findings, archs
