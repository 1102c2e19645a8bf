"""Serving engines for the GN non-GEMM datapath.

Two paths share the per-family caches from ``models/transformer.py``:

* ``generate`` — the original *static* batch engine (every request in the
  batch shares a prompt length, everyone decodes to ``max_new_tokens``).
  It stays as the correctness oracle: greedy continuous batching must be
  token-identical to it.  Decode writes into a preallocated output buffer
  (O(n) — the old per-token ``jnp.concatenate`` re-copied the whole buffer
  every step).

* ``ContinuousEngine`` — continuous batching with chunked prefill fused
  into the per-tick step.  Admission pages an empty slot in; the fused step
  (jitted ONCE over the fixed (num_slots, chunk) token budget) then drains
  the prompt chunk-by-chunk through otherwise-idle lanes while other slots
  keep decoding.  Per-slot positions, valid counts, phases, temperatures,
  the active mask — and, under paging, the block tables — are all traced
  arrays, so requests joining/leaving/prefilling never trigger
  recompilation, and there is no per-prompt-length prefill jit at all
  (prompts are bucketed to the chunk grid at intake, see
  serve/scheduler.pad_to_grid).

  KV residency is block-granular wherever the family's cache is pageable
  (``BlockPagedKVPool``: dense/moe/encdec/vlm full-attention KV, MLA
  latents — HBM scales with live tokens, admission gates on free blocks);
  SSM/hybrid carries and sliding-window rings keep the slot-monolithic
  ``SlotKVPool``.  Paged reads are gather-free and *horizon-bucketed*:
  each tick slices the traced block tables to the smallest power-of-two
  bucket covering the live block horizon, so attention work scales with
  live context while compile counts stay pinned to one trace per (step
  kind, bucket) — see docs/serving.md §Paged read paths.

  ``devices=N`` shards the slot pool over an N-device mesh along the
  slot/batch axis (slot-axis NamedSharding from parallel/sharding.py's
  rules; the GN guarantees are layout-independent, so per-device slot
  shards change placement, never values): both compile-once jits run SPMD,
  admission places the FCFS head on the least-loaded device's slot range,
  and ``metrics()`` reports num_devices / per_device_slots / shard_balance.
  ``devices=1`` (default) builds no mesh and is bit-identical to the
  single-device engine.

Layering: scheduler (admission + chunk-grid bucketing) -> kv_cache (slot/
block residency, block tables, device placement + per-device ranges,
offset-ranged positions) -> engine (this file: the fused step, sampling,
phase state machine, least-loaded placement, stop conditions, metrics).
See docs/serving.md for the full architecture and docs/benchmarks.md for
how ``metrics()`` feeds the BENCH_serve.json schema.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.tracekeys import horizon_bucket_grid
from repro.checkpoint import store as ckpt_store
from repro.models.attention import SCALE_SANITY_MAX
from repro.models.transformer import Model
from repro.parallel.sharding import make_slot_mesh
from repro.serve.kv_cache import BlockPagedKVPool, SlotKVPool
from repro.serve.scheduler import (
    Completion, FCFSScheduler, PriorityScheduler, Request, pad_to_grid,
)
from repro.serve.prefix_cache import PrefixCache
from repro.serve import tracing

# --- GN sentinel thresholds (docs/serving.md §Fault tolerance) -------------
# Σp residual: the paper's analytic bound for a t-term GN softmax sum is
# (t+1)·ε with ε the softmax compute dtype's machine epsilon — (t+1)·2⁻²³
# in f32 (pinned empirically in examples/norm_error_study.py), (t+1)·2⁻⁸
# when the model runs in bf16 (Σp is exact in the kernel's own arithmetic;
# the probe re-reads the ε-quantized probabilities and re-sums in f32, so
# it sees up to one ulp per term).  The trip wire sits a small constant
# above the analytic bound.  Real corruption lands orders of magnitude
# past either bound (nonfinite, or O(1) deviations), so the slack costs
# no detection.
SENTINEL_SUM_SLACK = 4.0
# GN/exact norm σ residual |mean(x̂²) − 1|, measured in f32 on the f32-cast
# pre-head activations: exact impls land at f32 rounding (~1e-7); the gn_*
# impls guarantee normalization to their grid precision (~2⁻¹¹, observed
# ~1e-5).  1e-3 keeps two orders of headroom over the guarantee while still
# flagging the O(1) deviations corruption produces.  Approximate norm impls
# (integer/lut) are only checked for nonfinite values.
SENTINEL_SIGMA_BOUND = 1e-3


class CountingJit:
    """``jax.jit`` plus an explicit compilation counter.

    The wrapped python function body runs exactly once per trace — i.e. once
    per compilation — so ``compilations`` is always an int.  (The previous
    probe poked jax's private ``_cache_size`` and silently degraded to
    ``None`` on versions without it, writing nulls into ``metrics()`` /
    BENCH_serve.json and blinding the bench's compile-count trajectory.)
    """

    def __init__(self, fn, **jit_kwargs):
        self._count = 0
        self._fn = fn
        self._jit_kwargs = dict(jit_kwargs)
        # repro.analysis hook: when set (to a dict), every distinct call
        # signature records one ShapeDtypeStruct tree of its args, so the
        # auditor can re-trace the exact entry points a workload exercised
        # without holding (donated!) buffer references.
        self.capture_avals = None

        @functools.wraps(fn)
        def counted(*args, **kwargs):
            self._count += 1
            return fn(*args, **kwargs)

        self._jitted = jax.jit(counted, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        if self.capture_avals is not None and not kwargs:
            avals = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                               if not hasattr(x, "dtype") else x.dtype),
                args,
            )
            key = tuple(
                (leaf.shape, str(leaf.dtype)) for leaf in jax.tree.leaves(avals)
            )
            self.capture_avals.setdefault(key, avals)
        return self._jitted(*args, **kwargs)

    @property
    def compilations(self) -> int:
        return self._count

    @property
    def jitted(self):
        """The underlying ``jax.jit`` object (AOT trace/lower access)."""
        return self._jitted

    @property
    def donate_argnums(self) -> tuple:
        return tuple(self._jit_kwargs.get("donate_argnums", ()))

    def trace(self, *args, **kwargs):
        return self._jitted.trace(*args, **kwargs)


def round_slots_to_devices(num_slots: int, devices: int) -> int:
    """Smallest slot count >= ``num_slots`` that shards evenly over
    ``devices`` — the engine requires exact divisibility (per-device slot
    shards), so CLIs round their requested pool size up through this."""
    return -(-int(num_slots) // int(devices)) * int(devices)


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
    seed: int = 0


# ---------------------------------------------------------------- static ---
def _static_jits(model: Model, max_seq: int):
    """Per-model cache of the static path's jitted prefill/decode, so repeated
    ``generate`` calls (benchmarks, the static oracle) don't re-trace."""
    cache = model.__dict__.setdefault("_serve_jits", {})
    if "decode" not in cache:
        cache["decode"] = jax.jit(model.decode_step)
    key = ("prefill", max_seq)
    if key not in cache:
        cache[key] = jax.jit(lambda p, bt: model.prefill(p, bt, max_seq))
    return cache[key], cache["decode"]


def generate(model: Model, params, batch: dict, cfg: ServeConfig, *,
             forced=None, return_logits: bool = False):
    """batch['tokens']: (B, S_prompt) -> (B, S_prompt + max_new) tokens.

    Prefill once, then ``max_new_tokens`` decode steps under jit (the decode
    step is compiled once; positions are traced scalars).

    ``forced`` (B, S_prompt + max_new) teacher-forces the continuation: step
    i feeds ``forced[:, S_prompt + i]`` in place of the chosen token, so the
    logits are the model's on given tokens.  ``return_logits`` also returns
    the (B, max_new, vocab) f32 logits each new token was chosen from.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    max_seq = s + cfg.max_new_tokens

    prefill, decode = _static_jits(model, max_seq)
    logits, cache = prefill(params, batch)

    key = jax.random.PRNGKey(cfg.seed)
    last_logits = logits[:, -1]
    out = jnp.zeros((b, max_seq), jnp.int32)
    out = jax.lax.dynamic_update_slice(out, tokens.astype(jnp.int32), (0, 0))

    rows = []
    for i in range(cfg.max_new_tokens):
        if return_logits:
            rows.append(last_logits.astype(jnp.float32))
        if forced is not None:
            nxt = jnp.asarray(forced, jnp.int32)[:, s + i]
        elif cfg.temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, last_logits / cfg.temperature, axis=-1)
        else:
            nxt = jnp.argmax(last_logits, axis=-1)
        nxt = nxt[:, None].astype(jnp.int32)
        out = jax.lax.dynamic_update_slice(out, nxt, (0, s + i))
        logits_step, cache = decode(params, cache, nxt, jnp.int32(s + i))
        last_logits = logits_step[:, 0]
    if return_logits:
        return out, jnp.stack(rows, axis=1)
    return out


def perplexity(model: Model, params, batch: dict) -> float:
    """Teacher-forced perplexity over a token batch (score-oriented metric)."""
    logits, _ = jax.jit(model.forward)(params, batch)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = batch["tokens"][:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return float(jnp.exp(jnp.mean(nll)))


def static_reference(model: Model, params, requests: Sequence[Request],
                     cfg: ServeConfig, *, forced: Optional[dict] = None,
                     return_logits: bool = False):
    """Serve ``requests`` through the static engine: group by (prompt_len,
    max_new_tokens) in FCFS order, one ``generate`` call per group.  Returns
    request id -> full (prompt + generated) token array, truncated at a
    request's stop token if it has one (the static engine itself always
    decodes the full budget).  This is both the greedy-identity oracle and
    the static baseline in benchmarks — greedy only, since sampled paths use
    different key streams per engine.

    ``forced`` maps request id -> full token array to teacher-force, and
    ``return_logits`` adds a second dict, request id -> (n_new, vocab) f32
    logits each new token was chosen from (see ``generate``)."""
    if any(r.temperature not in (None, 0, 0.0) for r in requests) or cfg.temperature:
        raise ValueError("static_reference is a greedy oracle (temperature 0 only)")
    groups: dict[tuple, list[Request]] = {}
    for req in requests:
        groups.setdefault((req.prompt_len, req.max_new_tokens), []).append(req)
    out: dict[int, np.ndarray] = {}
    out_logits: dict[int, np.ndarray] = {}
    for (plen, max_new), reqs in groups.items():
        batch = {"tokens": jnp.stack([jnp.asarray(r.tokens, jnp.int32) for r in reqs])}
        for k in reqs[0].extras:
            batch[k] = jnp.stack([jnp.asarray(r.extras[k]) for r in reqs])
        gcfg = dataclasses.replace(cfg, max_new_tokens=max_new)
        teacher = (None if forced is None else
                   np.stack([np.asarray(forced[r.id], np.int32) for r in reqs]))
        res = generate(model, params, batch, gcfg, forced=teacher,
                       return_logits=return_logits)
        toks, logits = res if return_logits else (res, None)
        toks = np.asarray(toks)
        logits = None if logits is None else np.asarray(logits)
        for i, (r, row) in enumerate(zip(reqs, toks)):
            n_new = max_new
            if r.stop_token is not None:
                hits = np.nonzero(row[plen:] == r.stop_token)[0]
                if hits.size:
                    n_new = int(hits[0]) + 1
            out[r.id] = row[: plen + n_new]
            if logits is not None:
                out_logits[r.id] = logits[i, :n_new]
    return (out, out_logits) if return_logits else out


# ------------------------------------------------------------ continuous ---
@dataclasses.dataclass
class _SlotState:
    req: Request
    admit_step: int
    admit_time: float
    generated: list
    phase: str = "decoding"       # 'prefilling' | 'decoding'
    padded: Optional[np.ndarray] = None  # prompt padded to the chunk grid
    written: int = 0              # prefill tokens committed to the cache
    # tokens the prefill phase must commit before the slot flips to
    # decoding.  == req.prompt_len normally; a recompute-resumed request
    # re-prefills prompt + already-generated tokens, so it is longer.
    prefill_len: int = 0
    first_token_step: int = -1
    first_token_time: float = 0.0
    preemptions: int = 0          # times this request has been evicted


@dataclasses.dataclass
class _Suspended:
    """A preempted request's carried state, keyed by request id until the
    scheduler hands the request back to admission.

    ``spill`` is None on the recompute path (resume re-prefills prompt +
    generated-so-far from scratch) and, on the spill path, the host-side
    mirror of everything the slot held: the block-chain payload (paged) or
    the batch-1 slab tree, the pool position, the prefill bookkeeping and
    the held next-token logits row — enough to restore the slot bitwise
    and continue as if the eviction never happened."""

    generated: list
    admit_step: int
    admit_time: float
    first_token_step: int
    first_token_time: float
    preemptions: int
    spill: Optional[dict] = None


class ContinuousEngine:
    """Continuous-batching engine over a fixed slot set, with chunked
    prefill fused into the decode step.

    Admission pages a *fresh* (empty) cache into a free slot — no blocking
    prefill call, no per-prompt-length compilation.  Each engine tick then
    runs ONE jitted step over a fixed (num_slots, chunk) token budget:
    every active slot contributes either its next decode token (phase
    'decoding', one valid lane) or the next chunk of its remaining prompt
    (phase 'prefilling', up to ``chunk`` valid lanes), so prompts stream
    through otherwise-idle lanes instead of stalling the batch.  Per-slot
    positions, valid counts, phases, temperatures and the active mask are
    all traced arrays -> requests joining/leaving/prefilling never trigger
    recompilation.  Ticks where every live slot is decoding take the
    cheaper (num_slots, 1) decode step (also compiled once).

    Greedy outputs are token-identical to the static ``generate`` path for
    every family whose serve shapes stay below the monolithic-path
    thresholds (conv fusion, chunked SSD/mLSTM, chunked attention) — see
    ``Model.prefill_chunk``.  MoE chunked prefill is the one exception:
    GShard capacity dropping depends on the dispatch group, so a chunked
    pass can route borderline tokens differently than a monolithic one.

    ``record_logits=True`` keeps, per request, the held logits each new
    token was chosen from, as ``Completion.logits`` (n_new, vocab): one
    extra device->host copy of the (num_slots, vocab) logits per decoding
    tick.  This is how a numerical check compares the served logits with
    ``static_reference(..., return_logits=True)``.
    """

    def __init__(self, model: Model, params, num_slots: int, max_seq: int,
                 cfg: ServeConfig = ServeConfig(),
                 scheduler: Optional[FCFSScheduler] = None,
                 chunk: int = 8, block_size: int = 0, num_blocks: int = 0,
                 devices: int = 1, paged: Optional[bool] = None,
                 prefix_cache: bool = False,
                 sched: str = "fcfs", preempt: str = "off",
                 aging_steps: int = 64, shed_backlog: int = 0,
                 kv_dtype: str = "fp", sentinels: Optional[bool] = None,
                 fault_retry_budget: int = 3,
                 clip_fallback_frac: float = 0.5, clip_patience: int = 3,
                 device_loss_min_slots: int = 2,
                 record_logits: bool = False):
        self.model, self.cfg = model, cfg
        self.record_logits = bool(record_logits)
        self.num_slots, self.max_seq = int(num_slots), int(max_seq)
        self.chunk = int(chunk)
        win = model.cfg.sliding_window or 0
        limit = min(self.max_seq, win) if win else self.max_seq
        if not 1 <= self.chunk <= limit:
            raise ValueError(
                f"chunk {chunk} must be in [1, {limit}] "
                "(cache ring capacity bounds the per-tick chunk)"
            )
        # SLA control plane: the scheduling policy ('fcfs' | 'priority') is
        # an engine kwarg (not just a scheduler instance) so reset() can
        # rebuild an equivalent scheduler for replay — a bench rep must not
        # silently fall back to FCFS.  Preemption ('off' | 'recompute' |
        # 'spill') requires the priority policy: its victim-eligibility
        # check is the scheduler's rank rule, and under FCFS a requeued
        # victim becomes the head again and admission would thrash.
        if sched not in ("fcfs", "priority"):
            raise ValueError(f"sched must be 'fcfs' or 'priority', got {sched!r}")
        if preempt not in ("off", "recompute", "spill"):
            raise ValueError(
                f"preempt must be 'off', 'recompute' or 'spill', got {preempt!r}"
            )
        self.sched_policy = sched
        self.preempt_mode = preempt
        self.aging_steps = int(aging_steps)
        self.shed_backlog = int(shed_backlog)
        if isinstance(scheduler, PriorityScheduler):
            # adopt the instance's policy so reset() rebuilds an equivalent
            self.sched_policy = "priority"
            self.aging_steps = scheduler.aging_steps
            self.shed_backlog = scheduler.shed_backlog
        if self.preempt_mode != "off" and self.sched_policy != "priority":
            raise ValueError(
                "preempt requires sched='priority' (or a PriorityScheduler "
                "instance): victim eligibility is the priority rank rule"
            )
        # Slot-pool sharding over the batch axis: devices=N builds a 1-D
        # ('data',) mesh, the pools place every cache leaf with a slot-axis
        # NamedSharding and both compile-once jits run SPMD over per-device
        # slot shards.  devices=1 builds no mesh at all — the single-device
        # path is bit-identical to the unsharded engine.
        self.num_devices = int(devices)
        if self.num_devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        if self.num_slots % self.num_devices:
            raise ValueError(
                f"num_slots {num_slots} must divide evenly over "
                f"{devices} devices (per-device slot shards)"
            )
        self.mesh = make_slot_mesh(self.num_devices) if self.num_devices > 1 else None
        if self.mesh is not None:
            self._sh_slot = NamedSharding(self.mesh, P("data"))       # (N,)
            self._sh_row = NamedSharding(self.mesh, P("data", None))  # (N, ...)
            self._sh_rep = NamedSharding(self.mesh, P())              # replicated
        else:
            self._sh_slot = self._sh_row = self._sh_rep = None
        # Params are placed once, replicated over the mesh: every tick then
        # reads them in place, where params left on one device would be
        # copied (or refused for mixing device sets) at each dispatch.
        self.params = self._put(params, self._sh_rep)
        # Block-paged KV wherever the family's cache is pageable (dense/moe/
        # encdec/vlm full-attention KV, MLA latents): HBM scales with live
        # tokens, admission gates on free blocks.  SSM/hybrid carries and
        # sliding-window rings keep the slot-monolithic pool.  ``paged``
        # overrides the auto-selection (False forces the slab pool for a
        # pageable family — the bench's HBM baseline and the sharded slab
        # test path; True on an unpageable family is an error).
        self.paged = model.supports_paging if paged is None else bool(paged)
        if self.paged and not model.supports_paging:
            raise ValueError(
                f"family {model.cfg.family!r} (sliding_window="
                f"{model.cfg.sliding_window}) has no pageable KV"
            )
        # The paged read every tick of this engine runs, fixed by the
        # backend, the device count and the config (``paged_read_path_on``).
        self.read_path = (model.paged_read_path_on(self.num_devices)
                          if self.paged else "slab")
        # Quantized paged KV: int8 arenas + per-block f32 dequant scales
        # carried as cache leaves alongside the arenas.  Paged-only — the
        # slab pool has no per-block scale granularity to hang scales on.
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r}")
        if kv_dtype == "int8" and not self.paged:
            raise ValueError(
                "kv_dtype='int8' requires the block-paged pool (per-block "
                "scales live in the block tables); the slab pool is fp-only"
            )
        self.kv_dtype = kv_dtype
        # GN runtime sentinels: in-tick Σp/σ-residual probes accumulated on
        # device into a per-slot health word, fetched with the tick's token
        # download and checked against the analytic bound (see
        # docs/serving.md §Fault tolerance).  ``sentinel`` is a static bool
        # closed over by the tick bodies — never a trace key — so enabling
        # them changes neither compile counts nor the tick's input avals.
        # Default on wherever the probe path exists (the paged tick bodies);
        # the slab pool has no probe plumbing, so sentinels=True there is an
        # error rather than a silent no-op.
        self.sentinels = self.paged if sentinels is None else bool(sentinels)
        if self.sentinels and not self.paged:
            raise ValueError(
                "sentinels ride the paged tick bodies; the slab pool has "
                "no probe path (pass sentinels=False or paged=True)"
            )
        self.fault_retry_budget = int(fault_retry_budget)
        self.clip_fallback_frac = float(clip_fallback_frac)
        self.clip_patience = int(clip_patience)
        self.device_loss_min_slots = int(device_loss_min_slots)
        if self.paged:
            self.pool = BlockPagedKVPool(
                model, num_slots, max_seq,
                block_size=block_size or self.chunk, num_blocks=num_blocks,
                mesh=self.mesh, num_devices=self.num_devices,
                kv_dtype=kv_dtype,
            )
            # Horizon-bucket grid: each paged tick slices the traced block
            # tables to the smallest bucket covering the *active block
            # horizon* (max blocks any live slot holds), so attention
            # compute/HBM traffic scales with live tokens while the jit
            # cache stays pinned — one compilation per (step kind, bucket),
            # i.e. fused <= len(grid) and decode <= len(grid) instead of
            # one tick shape per horizon.  Powers of two up to
            # max_blocks_per_slot, which caps the grid at
            # ceil(log2(max_bt)) + 1 entries.  The Pallas read's cost
            # follows each slot's own context (its grid skips the steps
            # past it), so there the grid is the one full-width bucket:
            # two tick programs instead of two per bucket.
            self.horizon_bucket_grid: list[int] = horizon_bucket_grid(
                self.pool.max_seq, self.pool.block_size, self.read_path)
            # Prefix sharing (opt-in): a per-device radix index over finished
            # prompt prefixes.  Admission attaches fully-matched cached
            # blocks read-only (refcount++), COW-forks a partially-matched
            # tail, charges the reservation only for the unshared remainder,
            # and prefill starts at the shared length — cold-TTFT drops to
            # the unshared tail.  Off by default: a retaining cache keeps
            # blocks resident after drain, which the non-sharing pool
            # invariants (blocks_in_use == 0) deliberately forbid.
            self.prefix = (
                PrefixCache(self.pool.block_size, self.num_devices)
                if prefix_cache else None
            )
            if self.prefix is not None:
                self.pool.attach_prefix_cache(self.prefix)
        else:
            if prefix_cache:
                raise ValueError(
                    f"family {model.cfg.family!r} has no pageable KV; "
                    "prefix_cache shares paged blocks"
                )
            self.prefix = None
            if block_size or num_blocks:
                raise ValueError(
                    f"family {model.cfg.family!r} has no pageable KV; "
                    "block_size/num_blocks only apply to paged pools"
                    if not model.supports_paging else
                    "block_size/num_blocks only apply to paged pools "
                    "(paged=False forces the slab pool)"
                )
            self.pool = SlotKVPool(model, num_slots, max_seq,
                                   mesh=self.mesh, num_devices=self.num_devices)

        # Donating the tick-carried state (cache tree, held logits,
        # positions, key) lets XLA update the cache in place instead of
        # copying it every tick (~20% off a smoke-scale decode tick); the
        # engine immediately rebinds each donated input to the returned
        # value, so no stale reference survives.  Block tables are NOT
        # donated — the host mirror stays authoritative.  Paged steps
        # re-trace once per horizon bucket (the tables argument's width):
        # compile counts are bounded by len(horizon_bucket_grid) per step
        # kind, not 1 — CountingJit still reports the exact totals.
        if self.paged:
            self._decode = CountingJit(self._decode_sample_paged,
                                       donate_argnums=(1, 2, 3, 6))
            self._fused = CountingJit(self._fused_step_paged,
                                      donate_argnums=(1, 2, 4, 9))
        else:
            self._decode = CountingJit(self._decode_sample,
                                       donate_argnums=(1, 2, 3, 6))
            self._fused = CountingJit(self._fused_step,
                                      donate_argnums=(1, 2, 4, 9))
        # Per-prompt-length prefill jits.  Chunked prefill leaves this empty
        # by construction; any future fallback that traces a prompt-length-
        # dependent prefill MUST register it here so the metric (and the
        # bench's compile-count trajectory) actually counts it.
        self._length_prefills: dict = {}
        # family-initial batch-1 cache paged in at admission (chunked prefill
        # starts from an empty slot; built once, reused for every request).
        # Replicated under a mesh: admission writes it into any slot shard.
        self._fresh_cache = self._put(model.fresh_request_cache(self.max_seq),
                                      self._sh_rep)
        self._encode_cross = (
            jax.jit(model.encode_cross_kv)
            if model.cfg.family == "encdec" else None
        )
        tracing.trace_gc()
        self.reset(scheduler)

    def _put(self, x, sharding):
        """Commit ``x`` (array or tree) to the serving mesh with ``sharding``;
        identity placement when the engine is single-device (no mesh)."""
        return x if sharding is None else jax.device_put(x, sharding)

    def reset(self, scheduler: Optional[FCFSScheduler] = None) -> None:
        """Clear all serving state but keep compiled functions and the pool
        allocation (benchmarks re-run the same workload without recompiling).
        The pool's slot order is restored too, so a reset run replays a
        workload with identical slot assignment (and, for sampled requests,
        identical per-slot key streams)."""
        self.pool.reset()
        vocab = self.model.cfg.vocab
        # Device-resident per-tick state: held logits, positions, active
        # mask, temps and the PRNG key all live on device and evolve in-jit;
        # the host mirrors (pool.positions, _temps, _slots) are refreshed
        # onto the device only when admission/completion changes lane
        # residency (_lanes_dirty), so a steady-state tick costs exactly one
        # jitted dispatch + one token download.
        self._last_logits = self._put(
            jnp.zeros((self.num_slots, vocab), jnp.float32), self._sh_row
        )
        self._temps = np.zeros(self.num_slots, np.float32)
        self._slots: list[Optional[_SlotState]] = [None] * self.num_slots
        self._pos_dev = self._put(jnp.zeros(self.num_slots, jnp.int32), self._sh_slot)
        self._active_dev = self._put(jnp.zeros(self.num_slots, bool), self._sh_slot)
        self._temps_dev = self._put(
            jnp.zeros(self.num_slots, jnp.float32), self._sh_slot
        )
        self._lanes_dirty = True
        if self.paged:
            self._tables_dev = self._put(jnp.asarray(self.pool.tables), self._sh_row)
            # per-bucket slices of the device tables, rebuilt lazily when
            # residency grows — steady-state ticks reuse the cached slice
            # instead of dispatching a device slice every tick
            self._tables_sliced: dict[int, jax.Array] = {}
            self.pool.tables_dirty = False
        self._key = self._put(jax.random.PRNGKey(self.cfg.seed), self._sh_rep)
        self.step_count = 0
        self.completions: list[Completion] = []
        self._logit_rows: dict[int, list] = {}  # record_logits: id -> rows
        self._active_steps = 0   # sum over decode steps of active-slot count
        self._decode_steps = 0
        self._fused_ticks = 0    # ticks that carried at least one prefill lane
        self._kernel_read_ticks = 0  # ticks whose paged read ran the kernel
        self._prefill_lane_steps = 0  # sum over ticks of prefilling slots
        self._generated = 0
        self.phase_log: list[tuple[int, int]] = []  # (prefill, decode) lanes/tick
        # horizon bucketing (paged): raw active horizon + bucket per tick,
        # the bucket sets each step kind has been traced at (the exact
        # compile-count bound), and the summed attended-token width
        self.horizon_log: list[tuple[int, int]] = []  # (horizon, bucket)/tick
        # ticks dispatched under transfer_guard_host_to_device("disallow")
        # — the serve test helpers assert this equals the tick count,
        # proving no tick ran with an implicit host->device transfer.
        # Host->device only: under a device mesh, jit legitimately
        # reshards args device-to-device at dispatch.
        self._guarded_ticks = 0
        self._buckets_seen: dict[str, set] = {"fused": set(), "decode": set()}
        self._attended_tokens = 0  # sum over ticks of bucket * block_size
        self._device_admits = np.zeros(self.num_devices, np.int64)
        # prefix-sharing telemetry (pool.reset() already cleared the radix
        # index itself, so a reset engine replays identical hit sequences)
        self._prefix_hit_tokens = 0
        self._prefix_prompt_tokens = 0
        self._prefix_hit_requests = 0
        self.request_prefix_hits: dict[int, dict] = {}
        # SLA control-plane state: suspended (preempted, not yet resumed)
        # requests by id, counters, and the deterministic event trace —
        # every admission/resume/preempt/reject/finish lands here with its
        # step stamp, so two same-seed runs can be compared event by event.
        self._suspended: dict[int, _Suspended] = {}
        self._preemptions = 0
        self._resumes = 0
        self._rejections = 0
        self.event_log: list[tuple] = []
        # fault-tolerance state: sentinel telemetry, per-request fault-evict
        # retry counts, per-slot consecutive clip-pressure streaks (int8),
        # and the table-redundancy repair count.  All deterministic under
        # replay — every fault verdict lands in event_log with its step.
        self._sentinel_checks = 0
        self._sentinel_violations = 0
        self._retries = 0
        self._fallbacks = 0
        self._table_repairs = 0
        self._fault_retries: dict[int, int] = {}
        self._clip_streak = np.zeros(self.num_slots, np.int32)
        # cumulative wall seconds of each host phase of step()
        self._phase_s = dict.fromkeys(tracing.PHASES, 0.0)
        self.scheduler = scheduler or self._make_scheduler()

    def _make_scheduler(self) -> FCFSScheduler:
        """The policy-equivalent scheduler reset() rebuilds for replay."""
        if self.sched_policy == "priority":
            return PriorityScheduler(
                chunk_grid=self.chunk, prefix_cache=self.prefix,
                aging_steps=self.aging_steps, shed_backlog=self.shed_backlog,
            )
        return FCFSScheduler(chunk_grid=self.chunk, prefix_cache=self.prefix)

    # ---------------------------------------------------------- jitted step --
    def _pin(self, x, sharding):
        """Sharding constraint inside a jitted step (no-op without a mesh).
        Pinning the per-slot tick state at entry and exit makes both
        compile-once jits SPMD over per-device slot shards — the cache tree
        arrives pre-sharded (committed by the pool), and GSPMD propagates
        the slot axis through the vmapped/batched layer stack between the
        pins."""
        return x if sharding is None else jax.lax.with_sharding_constraint(x, sharding)

    def _pin_state(self, last_logits, positions, active, temps):
        return (
            self._pin(last_logits, self._sh_row),
            self._pin(positions, self._sh_slot),
            self._pin(active, self._sh_slot),
            self._pin(temps, self._sh_slot),
        )

    def _sample_next(self, last_logits, active, is_prefill, temps, key):
        """Next decode token per slot from the held logits.  The key evolves
        inside the step (split traced) so ticks cost no extra host dispatch."""
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(last_logits, axis=-1)
        tsafe = jnp.where(temps > 0, temps, 1.0)
        keys = jax.random.split(sub, self.num_slots)
        sampled = jax.vmap(jax.random.categorical)(keys, last_logits / tsafe[:, None])
        nxt = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
        return jnp.where(active & ~is_prefill, nxt, 0), key

    def _decode_sample(self, params, cache, last_logits, positions, active,
                       temps, key):
        """Sample one token per slot from the held logits, then decode it.
        Everything per-slot is a traced array -> a single compilation.
        Positions advance in-jit; the host mirror tracks them without a
        per-tick transfer."""
        last_logits, positions, active, temps = self._pin_state(
            last_logits, positions, active, temps
        )
        nxt, key = self._sample_next(
            last_logits, active, jnp.zeros_like(active), temps, key
        )
        pos = jnp.where(active, positions, 0)  # clamp dont-care lanes in range
        logits, ncache = self.model.decode_step_slots(params, cache, nxt[:, None], pos)
        new_last = jnp.where(
            active[:, None], logits[:, 0].astype(jnp.float32), last_logits
        )
        new_positions = positions + jnp.where(active, 1, 0).astype(positions.dtype)
        return (self._pin(nxt, self._sh_slot), self._pin(new_last, self._sh_row),
                ncache, self._pin(new_positions, self._sh_slot), key)

    def _fused_step(self, params, cache, last_logits, chunk_tokens, positions,
                    n_valid, is_prefill, active, temps, key):
        """The fused tick: every slot processes a (chunk,)-token lane set —
        decoding slots sample their next token from the held logits into
        lane 0 (n_valid=1), prefilling slots take the staged prompt chunk.
        One compilation covers every phase/length/occupancy mix."""
        last_logits, positions, active, temps = self._pin_state(
            last_logits, positions, active, temps
        )
        chunk_tokens = self._pin(chunk_tokens, self._sh_row)
        n_valid = self._pin(n_valid, self._sh_slot)
        is_prefill = self._pin(is_prefill, self._sh_slot)
        dec, key = self._sample_next(last_logits, active, is_prefill, temps, key)
        lane0 = jnp.zeros_like(chunk_tokens).at[:, 0].set(dec)
        tokens = jnp.where(is_prefill[:, None], chunk_tokens, lane0)
        nv = jnp.where(active & is_prefill, n_valid, 1)
        pos = jnp.where(active, positions, 0)  # clamp dont-care lanes in range
        logits, ncache = self.model.fused_step_slots(params, cache, tokens, pos, nv)
        # fused_step_slots already returns each slot's row n_valid-1 — the
        # next-token distribution after the chunk: for decoders that's lane
        # 0; for prefillers it becomes the first-token logits once the final
        # chunk lands (mid-prompt values are interim, overwritten by later
        # chunks).
        new_last = jnp.where(
            active[:, None], logits[:, 0].astype(jnp.float32), last_logits
        )
        new_positions = positions + jnp.where(active, nv, 0).astype(positions.dtype)
        return (self._pin(dec, self._sh_slot), self._pin(new_last, self._sh_row),
                ncache, self._pin(new_positions, self._sh_slot), key)

    # ------------------------------------------------- paged jitted steps --
    # Same tick contract as the slab steps, but the cache is the shared
    # block-arena tree and every step carries the (traced) block tables.
    # Inactive lanes get n_valid=0 — unlike a slab, a parked lane owns no
    # blocks, so its writes must be *dropped*, not merely aimed at a
    # don't-care slab row.
    #
    # With sentinels enabled (self.sentinels is a closure constant, not an
    # argument) both steps return one extra value: the per-slot health
    # pytree {"layers": (L, N, 3), "head": (N,)} of GN probes, which the
    # engine downloads with the tick's token fetch and checks host-side.
    # Health is output-only (never donated, never re-fed), so it changes
    # neither the donation contract nor the input avals.

    def _decode_sample_paged(self, params, cache, last_logits, positions,
                             active, temps, key, tables):
        last_logits, positions, active, temps = self._pin_state(
            last_logits, positions, active, temps
        )
        tables = self._pin(tables, self._sh_row)
        nxt, key = self._sample_next(
            last_logits, active, jnp.zeros_like(active), temps, key
        )
        pos = jnp.where(active, positions, 0)  # clamp dont-care lanes in range
        nv = jnp.where(active, 1, 0).astype(jnp.int32)
        out = self.model.fused_step_slots_paged(
            params, cache, nxt[:, None], pos, nv, tables,
            sentinel=self.sentinels, read_path=self.read_path,
        )
        logits, ncache = out[0], out[1]
        new_last = jnp.where(
            active[:, None], logits[:, 0].astype(jnp.float32), last_logits
        )
        new_positions = positions + nv.astype(positions.dtype)
        res = (self._pin(nxt, self._sh_slot), self._pin(new_last, self._sh_row),
               ncache, self._pin(new_positions, self._sh_slot), key)
        return res + ((out[2],) if self.sentinels else ())

    def _fused_step_paged(self, params, cache, last_logits, chunk_tokens,
                          positions, n_valid, is_prefill, active, temps, key,
                          tables):
        last_logits, positions, active, temps = self._pin_state(
            last_logits, positions, active, temps
        )
        chunk_tokens = self._pin(chunk_tokens, self._sh_row)
        n_valid = self._pin(n_valid, self._sh_slot)
        is_prefill = self._pin(is_prefill, self._sh_slot)
        tables = self._pin(tables, self._sh_row)
        dec, key = self._sample_next(last_logits, active, is_prefill, temps, key)
        lane0 = jnp.zeros_like(chunk_tokens).at[:, 0].set(dec)
        tokens = jnp.where(is_prefill[:, None], chunk_tokens, lane0)
        nv = jnp.where(active & is_prefill, n_valid, 1)
        nv = jnp.where(active, nv, 0).astype(jnp.int32)
        pos = jnp.where(active, positions, 0)
        out = self.model.fused_step_slots_paged(
            params, cache, tokens, pos, nv, tables, sentinel=self.sentinels,
            read_path=self.read_path,
        )
        logits, ncache = out[0], out[1]
        new_last = jnp.where(
            active[:, None], logits[:, 0].astype(jnp.float32), last_logits
        )
        new_positions = positions + jnp.where(active, nv, 0).astype(positions.dtype)
        res = (self._pin(dec, self._sh_slot), self._pin(new_last, self._sh_row),
               ncache, self._pin(new_positions, self._sh_slot), key)
        return res + ((out[2],) if self.sentinels else ())

    # ------------------------------------------------------------ admission --
    def submit(self, req: Request) -> int:
        return self.scheduler.submit(req)

    def _admit(self) -> list[int]:
        """Page empty cache slots in for ready requests.  No forward pass
        happens here — the fused step drains the prompt chunk-by-chunk —
        so admission cost is one traced-slot insert regardless of prompt
        length, and there is no per-prompt-length prefill compilation.

        Placement is least-loaded-first across the device mesh: the FCFS
        head lands in the slot range of the device with the most free slots
        (paged: whose block range can also cover its whole-footprint
        reservation), so one hot device cannot strand free slots elsewhere.
        With one device this degenerates to the historical global FIFO."""
        admitted = []
        self._tick_admitted: set[int] = set()  # slots filled this pass
        # Backpressure first: under saturation, shed arrived batch backlog
        # beyond the watermark before anyone queues behind it.  FCFS's
        # poll_shed is a no-op; the PriorityScheduler sheds head-ordered.
        live_units, unit_fn = self._shed_signal()
        for req in self.scheduler.poll_shed(self.step_count, live_units, unit_fn):
            self._reject(req)
        while True:
            head = self.scheduler.peek_ready(self.step_count)
            if head is None:
                break
            footprint = head.prompt_len + head.max_new_tokens
            if footprint > self.max_seq:
                raise ValueError(
                    f"request {head.id}: prompt {head.prompt_len} + "
                    f"{head.max_new_tokens} new tokens exceeds max_seq {self.max_seq}"
                )
            if self.paged and (
                self.pool.blocks_for(footprint) > self.pool.max_request_blocks
            ):
                raise ValueError(
                    f"request {head.id}: footprint {footprint} tokens needs "
                    f"{self.pool.blocks_for(footprint)} blocks, a device's "
                    f"arena shard has {self.pool.max_request_blocks} — "
                    "unservable at any occupancy"
                )
            # Prefix lookup before placement: a hit pulls the request toward
            # the device already holding its prefix blocks (chains are
            # device-local), provided that device can still take it; the
            # reservation then charges only the unshared tail.  Misses (and
            # hits whose device is full) fall through to least-loaded.
            # Resuming (previously preempted) requests skip the lookup: the
            # spill path must rebuild the exact chain the payload was
            # gathered from, and the recompute path re-prefills a prompt +
            # generated sequence the prompt-only radix index doesn't cover.
            hit = device = None
            resuming = head.id in self._suspended
            if self.prefix is not None and not resuming:
                # cap at prompt_len - 1: the sampled first token needs the
                # request's own final prompt position to run through prefill
                hit = self.prefix.lookup(head.tokens, cap=head.prompt_len - 1)
                if hit is not None:
                    d = hit.device
                    if (self.pool.free_slots_on(d)
                            and self.pool.can_reserve(footprint, d, prefix=hit)):
                        device = d
                    else:
                        hit = None
            if device is None and self.pool.num_free:
                device = self.pool.pick_device(footprint if self.paged else 0)
            if device is None:
                # no device can take the head: an interactive head may evict
                # a batch victim it outranks; otherwise it waits for
                # recycling (admit gates on free *blocks* under paging)
                if self._try_preempt(head):
                    continue  # retry placement with the victim's resources
                break
            req = self.scheduler.pop_ready(self.step_count)
            sus = self._suspended.pop(req.id, None)
            slot = (
                self.pool.allocate(reserve_tokens=footprint, device=device,
                                   prefix=hit)
                if self.paged else self.pool.allocate(device=device)
            )
            if hit is not None:
                self.pool.attach_prefix(slot, hit)
            self._device_admits[device] += 1
            fresh = self._fresh_cache
            if self._encode_cross is not None:
                frames = jnp.asarray(req.extras["frames"])[None]
                fresh = {**fresh, "cross": self._encode_cross(self.params, frames)}
            if self.model.cfg.family == "vlm":
                dt = jnp.dtype(self.model.cfg.dtype)
                fresh = {**fresh,
                         "patches": jnp.asarray(req.extras["patches"])[None].astype(dt)}
            if sus is not None and sus.spill is not None:
                # --- spill resume: restore the evicted KV bitwise ---------
                sp = sus.spill
                if self.paged:
                    # insert() sets the position and ensures a fresh chain
                    # of exactly the spilled length; the scatter then fills
                    # it with the gathered values (physical ids may differ —
                    # only logical block order matters)
                    self.pool.insert(fresh, slot, position=sp["position"])
                    self.pool.restore_blocks(slot, sp["kv"])
                else:
                    self.pool.insert(sp["kv"], slot, position=sp["position"])
                self._last_logits = self._put(
                    self._last_logits.at[slot].set(jnp.asarray(sp["last_logits"])),
                    self._sh_row,
                )
                padded, written = sp["padded"], sp["written"]
                phase, prefill_len = sp["phase"], sp["prefill_len"]
            elif sus is not None:
                # --- recompute resume: re-prefill prompt + generated ------
                # Chunked prefill is token-identical to the decode path that
                # originally produced these tokens (the PR 2 invariant), so
                # after the re-prefill the held logits row is exactly the
                # next-token distribution the uninterrupted run would hold.
                seq = np.concatenate([
                    np.asarray(req.tokens, np.int32),
                    np.asarray(sus.generated, np.int32),
                ])
                prefill_len = int(seq.shape[0])
                padded = pad_to_grid(seq, self.chunk)
                self.pool.insert(fresh, slot, position=0)
                written, phase = 0, "prefilling"
            else:
                shared = hit.shared_len if hit is not None else 0
                self.pool.insert(fresh, slot, position=shared)
                padded = req.padded_tokens
                if shared:
                    # prefill starts at the shared length, so the chunk
                    # slices run [shared + k*chunk : ... + chunk): re-pad
                    # the prompt to cover the last (possibly overhanging)
                    # slice — grid-aligned padding from intake can be too
                    # short when ``shared`` is not chunk-aligned
                    need = shared + -(-(req.prompt_len - shared) // self.chunk) * self.chunk
                    if padded is None or padded.shape[0] < need:
                        toks = np.asarray(req.tokens, np.int32)
                        padded = np.concatenate(
                            [toks, np.zeros(need - toks.shape[0], np.int32)]
                        )
                    self._prefix_hit_tokens += shared
                    self._prefix_hit_requests += 1
                    self.request_prefix_hits[req.id] = {
                        "tokens": shared,
                        "blocks": len(hit.blocks),
                        "forked": hit.tail_src is not None,
                        "device": hit.device,
                    }
                elif padded is None or padded.shape[0] % self.chunk:
                    padded = pad_to_grid(req.tokens, self.chunk)
                written, phase = shared, "prefilling"
                prefill_len = req.prompt_len
            if self.prefix is not None and not resuming:
                self._prefix_prompt_tokens += req.prompt_len
            temp = self.cfg.temperature if req.temperature is None else req.temperature
            self._temps[slot] = float(temp)
            self._slots[slot] = _SlotState(
                req=req,
                admit_step=sus.admit_step if sus else self.step_count,
                admit_time=sus.admit_time if sus else time.time(),
                generated=sus.generated if sus else [],
                phase=phase, padded=padded, written=written,
                prefill_len=prefill_len,
                first_token_step=sus.first_token_step if sus else -1,
                first_token_time=sus.first_token_time if sus else 0.0,
                preemptions=sus.preemptions if sus else 0,
            )
            self._lanes_dirty = True
            self._clip_streak[slot] = 0
            self._tick_admitted.add(slot)
            if sus is not None:
                self._resumes += 1
                self.event_log.append(
                    ("resume", self.step_count, req.id, slot, device)
                )
            else:
                self.event_log.append(
                    ("admit", self.step_count, req.id, slot, device)
                )
            admitted.append(req.id)
        return admitted

    def _shed_signal(self) -> tuple:
        """(live reservation, per-request footprint fn) in the pool's
        admission units — blocks under paging, slots under a slab — for the
        scheduler's backpressure watermark."""
        if self.paged:
            return (
                self.pool.blocks_reserved,
                lambda r: self.pool.blocks_for(r.prompt_len + r.max_new_tokens),
            )
        return self.pool.num_used, lambda r: 1

    def _try_preempt(self, head: Request) -> bool:
        """Evict one batch victim so ``head`` (an interactive request that
        would otherwise queue) can place.  Victim selection is LIFO over the
        live batch slots the head *outranks under the scheduler's own rank
        rule* — the same step-independent order that decides admission, so
        an aged batch request that would beat the head in the queue can't
        be evicted by it either (no admit/preempt livelock).  LIFO (latest
        admission first) preempts the least sunk cost and mirrors the
        requeue-front resume order: the last victim out is the first back
        in.  Slots admitted this very pass are exempt — a resumed victim
        can't be re-evicted before it runs a single tick."""
        if self.preempt_mode == "off" or head.req_class != "interactive":
            return False
        outranks = getattr(self.scheduler, "outranks", None)
        best = None
        for s, st in enumerate(self._slots):
            if st is None or st.req.req_class != "batch":
                continue
            if s in self._tick_admitted:
                continue
            if outranks is not None and not outranks(
                head.arrival_step, st.req.arrival_step
            ):
                continue  # victim has aged past the head: immune
            if best is None or (st.admit_step, s) > (
                self._slots[best].admit_step, best
            ):
                best = s
        if best is None:
            return False
        self._preempt(best)
        return True

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``'s request and requeue it at the head of its class.

        Spill mode mirrors the slot's KV to host first (block-chain gather
        under paging, batch-1 slab extract otherwise) along with the held
        logits row and the prefill bookkeeping — resume restores all of it
        bitwise.  Recompute mode just drops the chain: resume re-prefills
        prompt + generated-so-far.  Either way the freed blocks are
        recycled *unzeroed* into other requests' chains — the GN guarantee
        (masked scores -> exactly-zero numerators) makes eviction a
        table/length edit, never a memory edit."""
        st = self._slots[slot]
        rid = st.req.id
        spill = None
        if self.preempt_mode == "spill":
            if self.paged:
                kv = self.pool.extract_blocks(slot)
            else:
                kv = jax.tree.map(np.asarray, self.pool.extract(slot))
            spill = {
                "kv": kv,
                "position": int(self.pool.positions[slot]),
                "padded": st.padded,
                "written": st.written,
                "prefill_len": st.prefill_len,
                "phase": st.phase,
                "last_logits": np.asarray(self._last_logits[slot]),
            }
        self._suspended[rid] = _Suspended(
            generated=st.generated,
            admit_step=st.admit_step,
            admit_time=st.admit_time,
            first_token_step=st.first_token_step,
            first_token_time=st.first_token_time,
            preemptions=st.preemptions + 1,
            spill=spill,
        )
        self._slots[slot] = None
        self.pool.free(slot)
        self.scheduler.requeue_front(st.req)
        self._preemptions += 1
        self._lanes_dirty = True
        self.event_log.append(
            ("preempt", self.step_count, rid, self.preempt_mode, slot)
        )

    def _reject(self, req: Request) -> None:
        """Record a shed request as a completion with finish_reason
        'rejected' — the client-visible load-shedding verdict."""
        now = time.time()
        self.completions.append(Completion(
            request_id=req.id,
            prompt_tokens=np.asarray(req.tokens, np.int32),
            new_tokens=np.zeros(0, np.int32),
            finish_reason="rejected",
            arrival_step=req.arrival_step,
            admit_step=-1,
            first_token_step=-1,
            finish_step=self.step_count,
            admit_time=now,
            first_token_time=now,
            finish_time=now,
            req_class=req.req_class,
            preemptions=0,
        ))
        self._rejections += 1
        self.event_log.append(("reject", self.step_count, req.id))

    def _prefix_insert(self, slot: int, up_to: int) -> None:
        """Index ``slot``'s prompt prefix [0, up_to) in the radix cache.
        Called at prefill completion (full prompt blocks — from then on the
        owner writes only at decode positions, in later blocks) and again at
        finish with the partial prompt tail (the owner is gone; the decode
        tokens sharing that block sit beyond every sharer's causal mask, and
        GN maps masked columns to exactly-zero numerators).  Generated
        tokens are never indexed — sharing only prompt-position KV keeps
        greedy identity vs the unshared oracle exact by construction."""
        if up_to <= 0:
            return
        self.prefix.insert(
            np.asarray(self._slots[slot].req.tokens[:up_to], np.int32),
            self.pool.chain_of(slot)[: self.pool.blocks_for(up_to)],
            self.pool.device_of(slot),
        )

    def _recorded_logits(self, req_id: int) -> Optional[np.ndarray]:
        rows = self._logit_rows.pop(req_id, None)
        return np.stack(rows) if rows else None

    def _finish(self, slot: int, reason: str) -> None:
        st = self._slots[slot]
        # written >= prompt_len: a recompute-resumed slot prefilled past the
        # prompt (prompt + generated), but its first blocks_for(prompt_len)
        # chain entries still hold exactly the prompt KV, so the tail insert
        # stays valid
        if self.prefix is not None and st.written >= st.req.prompt_len:
            bs = self.pool.block_size
            if st.req.prompt_len % bs:
                self._prefix_insert(slot, st.req.prompt_len)
        now = time.time()
        self.completions.append(Completion(
            request_id=st.req.id,
            prompt_tokens=np.asarray(st.req.tokens, np.int32),
            new_tokens=np.asarray(st.generated, np.int32),
            finish_reason=reason,
            arrival_step=st.req.arrival_step,
            admit_step=st.admit_step,
            first_token_step=st.first_token_step,
            finish_step=self.step_count,
            admit_time=st.admit_time,
            first_token_time=st.first_token_time,
            finish_time=now,
            req_class=st.req.req_class,
            preemptions=st.preemptions,
            logits=self._recorded_logits(st.req.id),
        ))
        self.event_log.append(("finish", self.step_count, st.req.id, reason))
        self._slots[slot] = None
        self.pool.free(slot)
        self._lanes_dirty = True

    # ------------------------------------------------------ fault tolerance --
    def _check_tables(self) -> None:
        """Host-side block-table redundancy check.  The per-slot chain
        (``_slot_blocks``) is the authoritative allocation record; the flat
        ``tables`` mirror is derived from it.  A divergence (bit-flip, stray
        write) is repaired from the chain, counted, and logged — the bad row
        never reaches the device because this runs before the dirty-mirror
        push in ``step``.  No quarantine or recompute is needed: arena
        contents were never touched."""
        for s, st in enumerate(self._slots):
            if st is None:
                continue
            chain = self.pool.chain_of(s)
            if not chain:
                continue
            want = np.asarray(chain, np.int32)
            if not np.array_equal(self.pool.tables[s, : len(chain)], want):
                self.pool.tables[s, : len(chain)] = want
                self.pool.tables_dirty = True
                self._table_repairs += 1
                self._sentinel_violations += 1
                self.event_log.append(
                    ("fault_table_repair", self.step_count, st.req.id, s)
                )

    def _sentinel_scan(self, health, live) -> None:
        """Check every live slot's health word against the GN bounds and
        contain/recover violations.  Channels (see attention.paged_probe_word
        and Model._paged_head):

        * layers[:, s, 0] — Σp residual, +inf on nonfinite scores/outputs.
          Bound: SENTINEL_SUM_SLACK · (t+1) · ε(compute dtype) with t the
          slot's attended width — (t+1)·2⁻²³ in f32, (t+1)·2⁻⁸ in bf16.
          NaN-safe comparison (``not (x <= bound)``).
        * head[s]        — final-norm σ residual, +inf on nonfinite logits.
        * layers[:, s, 1] — int8 clip fraction: sustained saturation flips
          the request to the full-precision static path (no quarantine —
          clipping is a range problem, not corruption).
        * layers[:, s, 2] — per-block scale sanity (int8): any nonfinite,
          negative, or implausibly large scale in the slot's live horizon.

        Every violating slot is contained uniformly: its chain is
        content-scanned, bad blocks are quarantined AND scrubbed (a NaN
        tile reachable through a stale table entry poisons healthy slots
        via IEEE 0·NaN=NaN — scrubbing closes that channel; healthy blocks
        are never zeroed), and the request is rebuilt token-identically via
        the free-and-recompute resume path under ``fault_retry_budget``.
        When every live slot on a device violates at once (>=
        ``device_loss_min_slots``), the whole device is declared lost."""
        layers = np.asarray(health["layers"], np.float64)  # (L, N, 3)
        head = np.asarray(health["head"], np.float64)      # (N,)
        sigma_certified = self.model.cfg.norm_impl.startswith(("gn", "exact"))
        # ε of the softmax compute dtype: 2⁻²³ (f32) or 2⁻⁸ (bf16)
        eps = float(jnp.finfo(jnp.dtype(self.model.cfg.dtype)).eps)
        violating: dict[int, list] = {}
        for s in live:
            st = self._slots[s]
            if st is None:
                continue
            self._sentinel_checks += 1
            kinds = []
            t = int(self.pool.positions[s])  # attended width incl. this tick
            bound = SENTINEL_SUM_SLACK * (t + 1) * eps
            sumres = layers[:, s, 0]
            worst = float(np.max(sumres))
            if not (worst <= bound):
                ok = sumres <= bound
                kinds.append(("sum", int(np.argmin(ok)), worst))
            h = float(head[s])
            if sigma_certified:
                if not (h <= SENTINEL_SIGMA_BOUND):
                    kinds.append(("sigma", -1, h))
            elif not np.isfinite(h):
                kinds.append(("sigma", -1, h))
            scl = layers[:, s, 2]
            if not (float(np.max(scl)) <= 0.0):
                kinds.append(("scale", int(np.argmax(scl)), float(np.max(scl))))
            if kinds:
                violating[s] = kinds
            elif self.kv_dtype == "int8":
                # clip-pressure channel, only meaningful on a clean tick
                frac = float(np.max(layers[:, s, 1]))
                if frac > self.clip_fallback_frac:
                    self._clip_streak[s] += 1
                    if self._clip_streak[s] >= self.clip_patience:
                        self._int8_fallback(s)
                else:
                    self._clip_streak[s] = 0
        if not violating:
            return
        self._sentinel_violations += len(violating)
        # device-loss aggregation BEFORE eviction mutates residency: a
        # device whose every live slot (>= the floor) tripped at once is
        # flaky hardware, not per-block corruption — retire its whole range
        if self.num_devices > 1:
            pds = self.num_slots // self.num_devices
            for d in range(self.num_devices):
                if d in self.pool._lost_devices:
                    continue
                live_d = [s for s in live if s // pds == d
                          and self._slots[s] is not None]
                viol_d = [s for s in violating if s // pds == d]
                if (len(viol_d) >= self.device_loss_min_slots
                        and len(viol_d) == len(live_d)):
                    self.pool.mark_device_lost(d)
                    self.event_log.append(
                        ("device_lost", self.step_count, d)
                    )
        # content diagnosis: quarantine + scrub the actually-corrupt blocks
        # (a flagged slot with a clean chain is collateral — its table
        # reached a poisoned block through a stale entry — and recovers the
        # same way, but its own blocks recycle normally)
        bad_blocks: set[int] = set()
        for s in violating:
            bad_blocks |= self._diagnose_chain(s)
        for b in sorted(bad_blocks):
            self.pool.quarantine_block(b)
            self.event_log.append(("quarantine", self.step_count, int(b)))
        if bad_blocks:
            self.pool.scrub_blocks(bad_blocks)
        # recovery: uniform free-and-recompute resume under the retry budget
        for s, kinds in violating.items():
            st = self._slots[s]
            rid = st.req.id
            self.event_log.append((
                "fault", self.step_count, rid, s,
                tuple(k for k, _, _ in kinds),
                tuple(lay for _, lay, _ in kinds),
            ))
            n = self._fault_retries.get(rid, 0)
            if n >= self.fault_retry_budget:
                self._finish(s, "failed")
            else:
                self._fault_retries[rid] = n + 1
                self._retries += 1
                self._fault_evict(s)

    def _diagnose_chain(self, slot: int) -> set:
        """Content-scan ``slot``'s block chain and return the physical
        blocks that are actually corrupt: fp arena tiles with nonfinite
        values, or int8 per-block scale entries that are nonfinite,
        negative, or past SCALE_SANITY_MAX.  int8 tiles themselves cannot
        encode NaN/Inf, and a bit-flipped-but-finite fp tile is below the
        GN detection floor by design — Σp = 1 holds exactly over wrong
        finite values — so finiteness is the whole content test."""
        bad: set[int] = set()
        chain = self.pool.chain_of(slot)
        if not chain:
            return bad
        ix = jnp.asarray(chain, jnp.int32)
        pulled = jax.device_get(
            jax.tree.map(lambda l: jnp.take(l, ix, axis=1),
                         self.pool.cache["layers"])
        )
        for name, arr in pulled.items():
            a = np.asarray(arr)
            if name.endswith("_scale"):
                f = a.astype(np.float64)  # (L, n)
                mask = ~np.isfinite(f) | (f < 0.0) | (f > SCALE_SANITY_MAX)
                hit = mask.any(axis=0)
            elif a.dtype == np.int8:
                continue
            else:
                f = a.astype(np.float32).reshape(a.shape[0], a.shape[1], -1)
                hit = ~np.isfinite(f).all(axis=(0, 2))
            for j, b in enumerate(chain):
                if hit[j]:
                    bad.add(int(b))
        return bad

    def _fault_evict(self, slot: int) -> None:
        """Free-and-recompute resume for a fault-flagged slot: identical to
        recompute-mode preemption (drop the chain, requeue at the head,
        re-prefill prompt + generated on resume — token-identical by the
        chunked-prefill invariant) but available under every scheduling
        policy, since the victim chose itself."""
        st = self._slots[slot]
        rid = st.req.id
        self._suspended[rid] = _Suspended(
            generated=st.generated,
            admit_step=st.admit_step,
            admit_time=st.admit_time,
            first_token_step=st.first_token_step,
            first_token_time=st.first_token_time,
            preemptions=st.preemptions + 1,
            spill=None,
        )
        self._slots[slot] = None
        self.pool.free(slot)  # doomed blocks divert to quarantine here
        self.scheduler.requeue_front(st.req)
        self._lanes_dirty = True
        self.event_log.append(("fault_evict", self.step_count, rid, slot))

    def _int8_fallback(self, slot: int) -> None:
        """Sustained int8 scale-overflow clipping: complete the request on
        the full-precision static path.  Clipping is quantizer range
        pressure, not corruption — the request's history is intact, so the
        static engine re-prefills prompt + generated-so-far in fp and
        decodes the remaining budget greedily (sampled requests fall back
        greedily too: the per-slot key stream cannot be replayed off-path).
        """
        st = self._slots[slot]
        req = st.req
        self._fallbacks += 1
        self.event_log.append(("kv_fallback", self.step_count, req.id, slot))
        seq = np.concatenate([
            np.asarray(req.tokens, np.int32),
            np.asarray(st.generated, np.int32),
        ])
        remaining = req.max_new_tokens - len(st.generated)
        reason = "length"
        if remaining > 0:
            batch = {"tokens": jnp.asarray(seq)[None]}
            for k in req.extras:
                batch[k] = jnp.asarray(req.extras[k])[None]
            gcfg = dataclasses.replace(
                self.cfg, max_new_tokens=remaining, temperature=0.0
            )
            row = np.asarray(generate(self.model, self.params, batch, gcfg))[0]
            gen = list(st.generated)
            for tok in row[seq.shape[0]:]:
                gen.append(int(tok))
                if req.stop_token is not None and int(tok) == req.stop_token:
                    reason = "stop"
                    break
            st.generated = gen
        self._finish(slot, reason)

    # ----------------------------------------------------------- main loop --
    def step(self) -> bool:
        """One engine tick.  Returns False once fully drained (no active
        slot, nothing queued).  Each host phase is a profiler span and adds
        its wall time to ``metrics()["host_phase_s"]`` (serve/tracing.py)."""
        phase = functools.partial(tracing.phase, seconds=self._phase_s)
        if self.paged and self.sentinels:
            # block-table redundancy check BEFORE the mirror is pushed to
            # device: the host chain (_slot_blocks) is authoritative, the
            # flat table row is derived — a scribbled entry is repaired in
            # place and the scribble never reaches a device gather.
            with phase("engine.check_tables"):
                self._check_tables()
        with phase("engine.admit"):
            self._admit()
        live = [s for s, st in enumerate(self._slots) if st is not None]
        if not live:
            if self.scheduler.has_pending():
                # Idle fast-forward: no slot is live and every queued
                # request's arrival is in the future, so jump the clock
                # straight to the next arrival.  Replay-identical to
                # burning the ticks one by one — nothing observable (no
                # arrival, admission, shed or decode) can happen on a
                # skipped tick, and the shed scan stops at the first
                # not-yet-arrived request so it could not have fired.
                nxt = self.scheduler.next_ready_step()
                self.step_count = max(
                    self.step_count + 1, nxt if nxt is not None else 0
                )
                return True
            return False

        prefills = [s for s in live if self._slots[s].phase == "prefilling"]
        decoders = [s for s in live if self._slots[s].phase == "decoding"]
        takes: dict[int, int] = {}
        for s in prefills:
            st = self._slots[s]
            takes[s] = min(self.chunk, st.prefill_len - st.written)
        paged_args = ()
        if self.paged:
            with phase("engine.pool_ensure"):
                # allocate blocks for the positions this tick will write,
                # then refresh the device table mirror only if residency grew
                for s in live:
                    self.pool.ensure(s, int(self.pool.positions[s]) + takes.get(s, 1))
                    if self.prefix is not None:
                        # COW assertion: the block this tick's first write
                        # lands in must be privately owned (attach-time
                        # forking makes shared-block writes impossible by
                        # construction)
                        self.pool.write_barrier(s, int(self.pool.positions[s]))
                if self.pool.tables_dirty:
                    self._tables_dev = self._put(
                        jnp.asarray(self.pool.tables), self._sh_row
                    )
                    self._tables_sliced.clear()
                    self.pool.tables_dirty = False
                # Horizon bucketing: slice the traced tables to the smallest
                # grid bucket covering the live block horizon, so the paged
                # reads (streamed tiles / kernel grid) touch only live
                # context.  A new bucket is a new tick shape -> one extra
                # compilation, bounded by len(horizon_bucket_grid) per step
                # kind.
                horizon = self.pool.active_horizon_blocks()
                bucket = next(b for b in self.horizon_bucket_grid if b >= horizon)
                self._buckets_seen["fused" if prefills else "decode"].add(bucket)
                self.horizon_log.append((horizon, bucket))
                self._attended_tokens += bucket * self.pool.block_size
                sliced = self._tables_sliced.get(bucket)
                if sliced is None:
                    sliced = self._tables_sliced[bucket] = self._tables_dev[:, :bucket]
                paged_args = (sliced,)
        # the logits this tick's decoders choose from, fetched before the
        # tick donates them
        held = (np.asarray(self._last_logits)
                if self.record_logits and decoders else None)
        with phase("engine.upload"):
            if self._lanes_dirty:  # residency changed: refresh device mirrors
                self._active_dev = self._put(
                    jnp.asarray(np.array([st is not None for st in self._slots])),
                    self._sh_slot,
                )
                self._temps_dev = self._put(jnp.asarray(self._temps), self._sh_slot)
                self._pos_dev = self._put(jnp.asarray(self.pool.positions), self._sh_slot)
                self._lanes_dirty = False
            if prefills:
                chunk_toks = np.zeros((self.num_slots, self.chunk), np.int32)
                n_valid = np.ones(self.num_slots, np.int32)
                is_pref = np.zeros(self.num_slots, bool)
                for s in prefills:
                    st = self._slots[s]
                    chunk_toks[s] = st.padded[st.written : st.written + self.chunk]
                    n_valid[s] = takes[s]
                    is_pref[s] = True
                # Explicit uploads: every tick operand is a committed device
                # array before dispatch, so the transfer guard below can
                # disallow *implicit* host->device transfers — an accidental
                # numpy arg (a silent per-tick upload) fails loudly instead
                # of slowly.  ``repro.analysis`` audits the same invariant
                # statically (A-TRANSFER).
                chunk_dev = self._put(jnp.asarray(chunk_toks), self._sh_row)
                nv_dev = self._put(jnp.asarray(n_valid), self._sh_slot)
                pref_dev = self._put(jnp.asarray(is_pref), self._sh_slot)
        if prefills:
            with phase("engine.dispatch"):
                with jax.transfer_guard_host_to_device("disallow"):
                    self._guarded_ticks += 1
                    outs = self._fused(
                        self.params, self.pool.cache, self._last_logits, chunk_dev,
                        self._pos_dev, nv_dev, pref_dev, self._active_dev,
                        self._temps_dev, self._key, *paged_args,
                    )
                # rebind the donated operands immediately after the call
                # that invalidated them, per branch — never across the
                # if/else join
                nxt, self._last_logits, self.pool.cache, self._pos_dev, self._key = (
                    outs[:5])
            self._fused_ticks += 1
        else:  # steady state: every live slot decodes -> the (N, 1) step
            with phase("engine.dispatch"):
                with jax.transfer_guard_host_to_device("disallow"):
                    self._guarded_ticks += 1
                    outs = self._decode(
                        self.params, self.pool.cache, self._last_logits,
                        self._pos_dev, self._active_dev, self._temps_dev, self._key,
                        *paged_args,
                    )
                nxt, self._last_logits, self.pool.cache, self._pos_dev, self._key = (
                    outs[:5])
        self._kernel_read_ticks += self.read_path == "pallas"
        with phase("engine.device_get"):
            if self.sentinels:
                # one fetch for token + health: the health word rides the
                # tick's existing device->host download, no extra transfer
                toks, health = jax.device_get((nxt, outs[5]))
            else:
                toks, health = jax.device_get(nxt), None
        with phase("engine.commit"):
            self.pool.advance({s: takes.get(s, 1) for s in live})
            self._active_steps += len(live)
            self._prefill_lane_steps += len(prefills)
            self._decode_steps += 1
            self._generated += len(decoders)
            self.phase_log.append((len(prefills), len(decoders)))

        if health is not None:
            # sentinel scan runs BEFORE token append: a violating slot's
            # tick output is garbage, so its token must never land in
            # st.generated — the slot is evicted (recompute resume) or
            # failed here, and the loops below skip it (st is None).  It
            # reads the advanced positions, so the commit phase is split
            # around it.
            with phase("engine.sentinel_scan"):
                self._sentinel_scan(health, live)

        with phase("engine.commit"):
            self._commit_tokens(prefills, decoders, takes, toks, held)
        self.step_count += 1
        return True

    def _commit_tokens(self, prefills, decoders, takes, toks, held) -> None:
        """Land the tick's prompt chunks and sampled tokens in their slots
        and finish the requests that reached a stop."""
        for slot in prefills:
            st = self._slots[slot]
            if st is None:  # fault-evicted this tick
                continue
            st.written += takes[slot]
            if st.written == st.prefill_len:
                st.phase = "decoding"  # first token samples next tick
                if self.prefix is not None:
                    bs = self.pool.block_size
                    self._prefix_insert(slot, (st.req.prompt_len // bs) * bs)
        for slot in decoders:
            st = self._slots[slot]
            if st is None:  # fault-evicted (or fell back) this tick
                continue
            tok = int(toks[slot])
            st.generated.append(tok)
            if held is not None:
                self._logit_rows.setdefault(st.req.id, []).append(held[slot])
            if len(st.generated) == 1:
                st.first_token_step = self.step_count
                st.first_token_time = time.time()
            reason = None
            if st.req.stop_token is not None and tok == st.req.stop_token:
                reason = "stop"
            elif len(st.generated) >= st.req.max_new_tokens:
                reason = "length"
            if reason:
                self._finish(slot, reason)

    def run(self, requests: Sequence[Request]) -> list[Completion]:
        """Serve a workload to completion; returns completions in finish
        order."""
        for req in requests:
            self.submit(req)
        # 2x per-request work: a preempted request pays (part of) its
        # prefill again on resume; the 10k constant absorbs pathological
        # preemption churn beyond that
        budget = 10_000 + 2 * sum(
            r.arrival_step + r.max_new_tokens + -(-r.prompt_len // self.chunk)
            for r in requests
        )
        while self.step():
            if self.step_count > budget:
                raise RuntimeError("ContinuousEngine failed to drain workload")
        return self.completions

    def compiled_tick_text(self, requests: Sequence[Request]) -> str:
        """Compiled text of the first fused tick that serving ``requests``
        dispatches (reset, replay with signature capture, compile that
        signature): where a caller checks what the tick's program holds,
        such as the Pallas read's ``tpu_custom_call``."""
        self.reset()
        self._fused.capture_avals = {}
        try:
            self.run(requests)
            avals = next(iter(self._fused.capture_avals.values()))
        finally:
            self._fused.capture_avals = None
        return self._fused.jitted.lower(*avals).compile().as_text()

    # ------------------------------------------------------------ snapshots --
    # Crash-consistent engine snapshots over checkpoint/store.py's atomic
    # npz + manifest format.  snapshot() may only be called between ticks —
    # step() boundaries are the engine's only consistent points — and
    # serializes EVERYTHING the next tick reads: arenas/scales (or slabs),
    # block tables and the whole pool ledger (including FIFO free-list
    # ORDER, which replay identity leans on), held logits, the PRNG key,
    # scheduler queues, live-slot and suspended-request state, completions,
    # counters and the event log.  restore() onto a compatibly-constructed
    # engine resumes greedy-token-identically: same values + same order +
    # same key => same tokens (verified by the kill-at-every-tick test).

    def _topology(self) -> dict:
        t = {
            "family": self.model.cfg.family,
            "norm_impl": self.model.cfg.norm_impl,
            "num_slots": self.num_slots,
            "max_seq": self.max_seq,
            "chunk": self.chunk,
            "paged": self.paged,
            "kv_dtype": self.kv_dtype,
            "num_devices": self.num_devices,
            "sched": self.sched_policy,
            "preempt": self.preempt_mode,
            "seed": self.cfg.seed,
            "sentinels": self.sentinels,
        }
        if self.paged:
            t["block_size"] = self.pool.block_size
            t["num_blocks"] = self.pool.num_blocks
        return t

    @staticmethod
    def _req_arrays(req: Request, tree: dict, prefix: str) -> dict:
        tree[f"{prefix}/tokens"] = np.asarray(req.tokens, np.int32)
        if req.padded_tokens is not None:
            tree[f"{prefix}/padded"] = np.asarray(req.padded_tokens, np.int32)
        for k, v in req.extras.items():
            tree[f"{prefix}/extras/{k}"] = np.asarray(v)
        return {
            "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature,
            "stop_token": req.stop_token,
            "arrival_step": req.arrival_step,
            "prefix_hint": req.prefix_hint,
            "req_class": req.req_class,
            "has_padded": req.padded_tokens is not None,
            "extras_keys": sorted(req.extras.keys()),
        }

    def snapshot(self, path) -> "str":
        """Write a crash-consistent snapshot under ``path`` (atomic: a kill
        mid-save never corrupts an existing snapshot).  Returns the
        checkpoint directory.  Unsupported with an attached prefix cache
        (the radix index is not serialized)."""
        if self.prefix is not None:
            raise ValueError(
                "snapshot with an attached prefix cache is not supported"
            )
        tree: dict = {
            "cache": jax.device_get(self.pool.cache),
            "last_logits": np.asarray(self._last_logits),
            "key": np.asarray(self._key),
            "temps": np.asarray(self._temps),
            "positions": np.asarray(self.pool.positions),
            "clip_streak": np.asarray(self._clip_streak),
            "device_admits": np.asarray(self._device_admits),
        }
        requests: dict[int, Request] = {}
        slots_meta = {}
        for s, st in enumerate(self._slots):
            if st is None:
                continue
            requests[st.req.id] = st.req
            if st.padded is not None:
                tree[f"slot/{s}/padded"] = np.asarray(st.padded, np.int32)
            slots_meta[str(s)] = {
                "rid": st.req.id,
                "admit_step": st.admit_step,
                "admit_time": st.admit_time,
                "generated": [int(t) for t in st.generated],
                "phase": st.phase,
                "written": st.written,
                "prefill_len": st.prefill_len,
                "first_token_step": st.first_token_step,
                "first_token_time": st.first_token_time,
                "preemptions": st.preemptions,
                "has_padded": st.padded is not None,
            }
        if self.sched_policy == "priority":
            sched_meta = {
                "queues": {
                    c: [r.id for r in q]
                    for c, q in self.scheduler._queues.items()
                },
                "resumed": sorted(self.scheduler._resumed),
                "shed_count": self.scheduler.shed_count,
            }
            queued = [r for q in self.scheduler._queues.values() for r in q]
        else:
            sched_meta = {"queue": [r.id for r in self.scheduler._queue]}
            queued = list(self.scheduler._queue)
        for r in queued:
            requests[r.id] = r
        sched_meta["next_id"] = self.scheduler._next_id
        sched_meta["pad_tokens"] = self.scheduler._pad_tokens
        req_meta = {
            str(rid): self._req_arrays(r, tree, f"req/{rid}")
            for rid, r in requests.items()
        }
        sus_meta = {}
        for rid, sus in self._suspended.items():
            spill_meta = None
            if sus.spill is not None:
                sp = sus.spill
                spill_meta = {
                    "position": sp["position"],
                    "written": sp["written"],
                    "prefill_len": sp["prefill_len"],
                    "phase": sp["phase"],
                    "has_padded": sp["padded"] is not None,
                }
                tree[f"sus/{rid}/last_logits"] = np.asarray(sp["last_logits"])
                if sp["padded"] is not None:
                    tree[f"sus/{rid}/padded"] = np.asarray(sp["padded"], np.int32)
                if self.paged:
                    spill_meta["len"] = sp["kv"]["len"]
                    if sp["kv"]["layers"] is not None:
                        tree[f"sus/{rid}/kv"] = sp["kv"]["layers"]
                else:
                    tree[f"sus/{rid}/kv"] = sp["kv"]
            sus_meta[str(rid)] = {
                "generated": [int(t) for t in sus.generated],
                "admit_step": sus.admit_step,
                "admit_time": sus.admit_time,
                "first_token_step": sus.first_token_step,
                "first_token_time": sus.first_token_time,
                "preemptions": sus.preemptions,
                "spill": spill_meta,
            }
        comp_meta = []
        for i, c in enumerate(self.completions):
            tree[f"comp/{i}/prompt"] = np.asarray(c.prompt_tokens, np.int32)
            tree[f"comp/{i}/new"] = np.asarray(c.new_tokens, np.int32)
            comp_meta.append({
                "request_id": c.request_id,
                "finish_reason": c.finish_reason,
                "arrival_step": c.arrival_step,
                "admit_step": c.admit_step,
                "first_token_step": c.first_token_step,
                "finish_step": c.finish_step,
                "admit_time": c.admit_time,
                "first_token_time": c.first_token_time,
                "finish_time": c.finish_time,
                "req_class": c.req_class,
                "preemptions": c.preemptions,
            })
        if self.paged:
            pool_meta = {
                "free_slots": list(self.pool._free_slots),
                "used": sorted(self.pool._used),
                "slot_blocks": {
                    str(s): list(ch)
                    for s, ch in self.pool._slot_blocks.items()
                },
                "free_blocks": [list(q) for q in self.pool._free_blocks],
                "quarantined": sorted(self.pool.quarantined),
                "doomed": sorted(self.pool._doomed),
                "lost_devices": sorted(self.pool._lost_devices),
                "peak_blocks_in_use": self.pool.peak_blocks_in_use,
                "peak_blocks_reserved": self.pool.peak_blocks_reserved,
                "peak_reserved_per_device": [
                    int(x) for x in self.pool.peak_reserved_per_device
                ],
                "peak_used_per_device": [
                    int(x) for x in self.pool.peak_used_per_device
                ],
            }
            tree["tables"] = np.asarray(self.pool.tables)
            tree["refcounts"] = np.asarray(self.pool.refcounts)
            tree["reserved"] = np.asarray(self.pool._reserved)
            tree["shared"] = np.asarray(self.pool._shared)
            tree["owned"] = np.asarray(self.pool._owned)
        else:
            pool_meta = {
                "free": list(self.pool._free),
                "used": sorted(self.pool._used),
            }
        extra = {
            "topology": self._topology(),
            "step_count": self.step_count,
            "counters": {
                "active_steps": self._active_steps,
                "decode_steps": self._decode_steps,
                "fused_ticks": self._fused_ticks,
                "prefill_lane_steps": self._prefill_lane_steps,
                "generated": self._generated,
                "guarded_ticks": self._guarded_ticks,
                "attended_tokens": self._attended_tokens,
                "preemptions": self._preemptions,
                "resumes": self._resumes,
                "rejections": self._rejections,
                "sentinel_checks": self._sentinel_checks,
                "sentinel_violations": self._sentinel_violations,
                "retries": self._retries,
                "fallbacks": self._fallbacks,
                "table_repairs": self._table_repairs,
            },
            "phase_log": [list(x) for x in self.phase_log],
            "horizon_log": [list(x) for x in self.horizon_log],
            "buckets_seen": {
                k: sorted(v) for k, v in self._buckets_seen.items()
            },
            "event_log": [list(e) for e in self.event_log],
            "fault_retries": {
                str(k): v for k, v in self._fault_retries.items()
            },
            "slots": slots_meta,
            "requests": req_meta,
            "scheduler": sched_meta,
            "suspended": sus_meta,
            "completions": comp_meta,
            "pool": pool_meta,
        }
        return str(ckpt_store.save(path, self.step_count, tree, extra=extra))

    @staticmethod
    def _nest(flat: dict, prefix: str) -> dict:
        """Rebuild a nested dict from flat ``prefix/...`` keys (digit path
        components become int keys — SSM carry trees index layers by int)."""
        out: dict = {}
        for name, arr in flat.items():
            if not name.startswith(prefix):
                continue
            parts = [
                int(p) if p.isdigit() else p
                for p in name[len(prefix):].split("/")
            ]
            d = out
            for p in parts[:-1]:
                d = d.setdefault(p, {})
            d[parts[-1]] = arr
        return out

    def _restore_request(self, flat: dict, rid: int, meta: dict) -> Request:
        return Request(
            tokens=np.asarray(flat[f"req/{rid}/tokens"], np.int32),
            max_new_tokens=meta["max_new_tokens"],
            temperature=meta["temperature"],
            stop_token=meta["stop_token"],
            arrival_step=meta["arrival_step"],
            extras={
                k: np.asarray(flat[f"req/{rid}/extras/{k}"])
                for k in meta["extras_keys"]
            },
            id=rid,
            padded_tokens=(
                np.asarray(flat[f"req/{rid}/padded"], np.int32)
                if meta["has_padded"] else None
            ),
            prefix_hint=meta["prefix_hint"],
            req_class=meta["req_class"],
        )

    def restore(self, path, step: Optional[int] = None) -> None:
        """Restore a ``snapshot`` into this engine (freshly constructed with
        the same model/params and a matching topology).  ``step`` defaults
        to the latest snapshot under ``path``.  After restore the engine
        continues greedy-token-identically to the run that wrote it."""
        if step is None:
            step = ckpt_store.latest_step(path)
            if step is None:
                raise FileNotFoundError(f"no snapshot under {path}")
        flat, manifest = ckpt_store.restore_flat(path, step)
        extra = manifest["extra"]
        want = extra["topology"]
        have = self._topology()
        diff = {k: (v, have.get(k)) for k, v in want.items() if have.get(k) != v}
        if diff:
            raise ValueError(f"snapshot topology mismatch: {diff}")
        self.reset()
        # --- device state -------------------------------------------------
        leaves, treedef = jax.tree_util.tree_flatten_with_path(self.pool.cache)
        vals = []
        for kpath, leaf in leaves:
            name = "cache/" + "/".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in kpath
            )
            # match the fresh leaf's commitment, not just its sharding: an
            # uncommitted leaf re-placed with an explicit device_put comes
            # back *committed*, which is part of the pjit compilation-cache
            # key — every warmed tick entry would silently recompile
            new = jnp.asarray(flat[name], leaf.dtype)
            vals.append(jax.device_put(new, leaf.sharding)
                        if leaf.committed else new)
        self.pool.cache = jax.tree_util.tree_unflatten(treedef, vals)
        self._last_logits = self._put(
            jnp.asarray(flat["last_logits"]), self._sh_row
        )
        self._key = self._put(jnp.asarray(flat["key"]), self._sh_rep)
        self._temps = np.array(flat["temps"], np.float32)
        self.pool.positions[:] = flat["positions"]
        self._clip_streak = np.array(flat["clip_streak"], np.int32)
        self._device_admits = np.array(flat["device_admits"], np.int64)
        self._lanes_dirty = True  # step() refreshes pos/active/temps mirrors
        # --- pool ledger --------------------------------------------------
        import collections
        p = extra["pool"]
        if self.paged:
            self.pool.tables[:] = flat["tables"]
            self.pool.tables_dirty = True
            self.pool.refcounts = np.array(flat["refcounts"], np.int32)
            self.pool._reserved = np.array(flat["reserved"], np.int32)
            self.pool._shared = np.array(flat["shared"], np.int32)
            self.pool._owned = np.array(flat["owned"], np.int32)
            self.pool._free_slots = collections.deque(p["free_slots"])
            self.pool._used = set(p["used"])
            self.pool._slot_blocks = {
                int(s): list(ch) for s, ch in p["slot_blocks"].items()
            }
            self.pool._free_blocks = [
                collections.deque(q) for q in p["free_blocks"]
            ]
            self.pool.quarantined = set(p["quarantined"])
            self.pool._doomed = set(p["doomed"])
            self.pool._lost_devices = set(p["lost_devices"])
            self.pool.peak_blocks_in_use = p["peak_blocks_in_use"]
            self.pool.peak_blocks_reserved = p["peak_blocks_reserved"]
            self.pool.peak_reserved_per_device = np.array(
                p["peak_reserved_per_device"], np.int64
            )
            self.pool.peak_used_per_device = np.array(
                p["peak_used_per_device"], np.int64
            )
            self.pool.check_ledger()
        else:
            self.pool._free = collections.deque(p["free"])
            self.pool._used = set(p["used"])
        # --- requests / scheduler / slots / suspended ---------------------
        reqs = {
            int(rid): self._restore_request(flat, int(rid), m)
            for rid, m in extra["requests"].items()
        }
        sm = extra["scheduler"]
        self.scheduler._next_id = sm["next_id"]
        self.scheduler._pad_tokens = sm["pad_tokens"]
        if self.sched_policy == "priority":
            for c, ids in sm["queues"].items():
                self.scheduler._queues[c] = collections.deque(
                    reqs[rid] for rid in ids
                )
            self.scheduler._resumed = set(sm["resumed"])
            self.scheduler.shed_count = sm["shed_count"]
        else:
            self.scheduler._queue = collections.deque(
                reqs[rid] for rid in sm["queue"]
            )
        for s_str, m in extra["slots"].items():
            s = int(s_str)
            self._slots[s] = _SlotState(
                req=reqs[m["rid"]],
                admit_step=m["admit_step"],
                admit_time=m["admit_time"],
                generated=list(m["generated"]),
                phase=m["phase"],
                padded=(
                    np.asarray(flat[f"slot/{s}/padded"], np.int32)
                    if m["has_padded"] else None
                ),
                written=m["written"],
                prefill_len=m["prefill_len"],
                first_token_step=m["first_token_step"],
                first_token_time=m["first_token_time"],
                preemptions=m["preemptions"],
            )
        for rid_str, m in extra["suspended"].items():
            rid = int(rid_str)
            spill = None
            if m["spill"] is not None:
                sp = m["spill"]
                if self.paged:
                    layers = self._nest(flat, f"sus/{rid}/kv/") or None
                    kv = {"len": sp["len"], "layers": layers}
                else:
                    kv = self._nest(flat, f"sus/{rid}/kv/")
                spill = {
                    "kv": kv,
                    "position": sp["position"],
                    "padded": (
                        np.asarray(flat[f"sus/{rid}/padded"], np.int32)
                        if sp["has_padded"] else None
                    ),
                    "written": sp["written"],
                    "prefill_len": sp["prefill_len"],
                    "phase": sp["phase"],
                    "last_logits": np.asarray(flat[f"sus/{rid}/last_logits"]),
                }
            self._suspended[rid] = _Suspended(
                generated=list(m["generated"]),
                admit_step=m["admit_step"],
                admit_time=m["admit_time"],
                first_token_step=m["first_token_step"],
                first_token_time=m["first_token_time"],
                preemptions=m["preemptions"],
                spill=spill,
            )
        # --- completions / logs / counters --------------------------------
        for i, m in enumerate(extra["completions"]):
            self.completions.append(Completion(
                request_id=m["request_id"],
                prompt_tokens=np.asarray(flat[f"comp/{i}/prompt"], np.int32),
                new_tokens=np.asarray(flat[f"comp/{i}/new"], np.int32),
                finish_reason=m["finish_reason"],
                arrival_step=m["arrival_step"],
                admit_step=m["admit_step"],
                first_token_step=m["first_token_step"],
                finish_step=m["finish_step"],
                admit_time=m["admit_time"],
                first_token_time=m["first_token_time"],
                finish_time=m["finish_time"],
                req_class=m["req_class"],
                preemptions=m["preemptions"],
            ))
        def detuple(e):
            return tuple(detuple(x) if isinstance(x, list) else x for x in e)
        self.event_log = [detuple(e) for e in extra["event_log"]]
        self.phase_log = [tuple(x) for x in extra["phase_log"]]
        self.horizon_log = [tuple(x) for x in extra["horizon_log"]]
        self._buckets_seen = {
            k: set(v) for k, v in extra["buckets_seen"].items()
        }
        self._fault_retries = {
            int(k): v for k, v in extra["fault_retries"].items()
        }
        c = extra["counters"]
        self.step_count = extra["step_count"]
        self._active_steps = c["active_steps"]
        self._decode_steps = c["decode_steps"]
        self._fused_ticks = c["fused_ticks"]
        self._prefill_lane_steps = c["prefill_lane_steps"]
        self._generated = c["generated"]
        self._guarded_ticks = c["guarded_ticks"]
        self._attended_tokens = c["attended_tokens"]
        self._preemptions = c["preemptions"]
        self._resumes = c["resumes"]
        self._rejections = c["rejections"]
        self._sentinel_checks = c["sentinel_checks"]
        self._sentinel_violations = c["sentinel_violations"]
        self._retries = c["retries"]
        self._fallbacks = c["fallbacks"]
        self._table_repairs = c["table_repairs"]

    # -------------------------------------------------------------- metrics --
    def device_occupancy(self) -> list[int]:
        """Live (admitted) slots per device range right now — the quantity
        least-loaded placement balances."""
        pds = self.num_slots // self.num_devices
        return [
            sum(st is not None for st in self._slots[d * pds : (d + 1) * pds])
            for d in range(self.num_devices)
        ]

    @property
    def shard_balance(self) -> float:
        """Admission balance across device slot ranges: min/max of per-device
        admitted-request counts (1.0 = perfectly balanced, and trivially 1.0
        single-device).  The bench tracks it next to num_devices so a
        placement regression (one hot device hoarding admissions) shows up
        in the history trajectory."""
        if self.num_devices == 1 or self._device_admits.max() == 0:
            return 1.0
        return float(self._device_admits.min() / self._device_admits.max())

    def metrics(self) -> dict:
        util = self._active_steps / max(1, self._decode_steps * self.num_slots)
        pref = self._prefill_lane_steps / max(1, self._active_steps)
        out = {
            "decode_steps": self._decode_steps,
            "generated_tokens": self._generated,
            "mean_slot_utilization": util,
            "prefill_lane_fraction": pref,
            "fused_ticks": self._fused_ticks,
            "completions": len(self.completions),
            "chunk": self.chunk,
            "intake_padding": getattr(self.scheduler, "intake_padding", 0),
            # CountingJit: always ints (one trace == one compilation).
            # Slab pools: fused=1 / decode<=1.  Paged pools: exactly one
            # trace per (step kind, horizon bucket actually seen) — i.e.
            # len(fused_buckets) / len(decode_buckets), bounded by
            # len(horizon_bucket_grid) each.
            "decode_compilations": self._decode.compilations,
            "fused_step_compilations": self._fused.compilations,
            # chunked prefill rides the fused step: _length_prefills stays
            # empty unless a fallback reintroduces per-length tracing.  The
            # attribute access is deliberately strict: registering a plain
            # jax.jit here would silently count 0 — wrap it in CountingJit.
            "prefill_compilations": sum(
                f.compilations for f in self._length_prefills.values()
            ),
            "kv_paged": self.paged,
            "kv_hbm_bytes": self.pool.hbm_bytes(),
            "transfer_guarded_ticks": self._guarded_ticks,
            # SLA control plane: policy knobs + the preemption/shedding
            # counters the sla bench scenario reports per configuration
            "sched": self.sched_policy,
            "preempt_mode": self.preempt_mode,
            "preemptions": self._preemptions,
            "preempt_resumes": self._resumes,
            "rejections": self._rejections,
            "shed_count": getattr(self.scheduler, "shed_count", 0),
            # fault tolerance: sentinel probe telemetry + recovery counters
            # (docs/serving.md §Fault tolerance).  sentinel_checks counts
            # (slot, tick) health evaluations; violations count tripped
            # slots plus table repairs; retries/fallbacks count the two
            # recovery paths actually taken.
            "sentinels": self.sentinels,
            "sentinel_checks": self._sentinel_checks,
            "sentinel_violations": self._sentinel_violations,
            "quarantined_blocks": (
                len(self.pool.quarantined) if self.paged else 0
            ),
            "retries": self._retries,
            "fallbacks": self._fallbacks,
            "table_repairs": self._table_repairs,
            "failed_completions": sum(
                1 for c in self.completions if c.finish_reason == "failed"
            ),
            # slot-pool sharding over the batch axis (devices=1 -> one range,
            # balance trivially 1.0; see docs/serving.md §Device mesh)
            "num_devices": self.num_devices,
            "per_device_slots": self.num_slots // self.num_devices,
            "shard_balance": self.shard_balance,
            "device_admits": [int(n) for n in self._device_admits],
            # wall seconds per host phase of step() since the last reset
            # (serve/tracing.py PHASES)
            "host_phase_s": dict(self._phase_s),
        }
        if self.paged:
            out.update(
                block_size=self.pool.block_size,
                num_blocks=self.pool.num_blocks,
                peak_blocks_in_use=self.pool.peak_blocks_in_use,
                peak_blocks_reserved=self.pool.peak_blocks_reserved,
                block_utilization=(
                    self.pool.peak_blocks_in_use / max(1, self.pool.num_blocks)
                ),
                # which gather-free read this engine's ticks take (pallas/
                # streamed; 'gathered' only under a forced/baseline
                # fallback), how many ticks ran the Pallas kernel, and the
                # horizon-bucketing trajectory: the grid, the buckets each
                # step kind actually traced (compile counters are exactly
                # one per (kind, bucket) -> the documented upper bound), and
                # the mean attended stream width per tick — the quantity
                # that now scales with live tokens, not max_seq
                read_path=self.read_path,
                kernel_read_ticks=self._kernel_read_ticks,
                kv_dtype=self.pool.kv_dtype,
                horizon_bucket_grid=list(self.horizon_bucket_grid),
                horizon_buckets=sorted(
                    self._buckets_seen["fused"] | self._buckets_seen["decode"]
                ),
                fused_buckets=sorted(self._buckets_seen["fused"]),
                decode_buckets=sorted(self._buckets_seen["decode"]),
                mean_attended_tokens_per_tick=(
                    self._attended_tokens / max(1, self._decode_steps)
                ),
                prefix_cache=self.prefix is not None,
            )
            if self.prefix is not None:
                out.update(
                    # token-weighted: cached prompt tokens / admitted prompt
                    # tokens — the bench's headline hit metric
                    prefix_hit_rate=(
                        self._prefix_hit_tokens
                        / max(1, self._prefix_prompt_tokens)
                    ),
                    prefix_hit_tokens=self._prefix_hit_tokens,
                    prefix_prompt_tokens=self._prefix_prompt_tokens,
                    prefix_hit_requests=self._prefix_hit_requests,
                    prefix_forks=self.pool.prefix_forks,
                    prefix_evictions=self.pool.prefix_evictions,
                    prefix_cached_blocks=self.pool.cached_blocks,
                    prefix_inserts=self.prefix.inserts,
                )
        else:
            out["read_path"] = "slab"
            out["kernel_read_ticks"] = 0
            out["kv_dtype"] = "fp"
        return out
