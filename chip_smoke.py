"""Serve internlm2-1.8b at its published widths on a TPU and check the result.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the 4-device slot mesh against devices=1

One chip: the staggered continuous-batching workload of
``python -m repro.launch.serve --arch internlm2-1.8b --continuous`` (8
requests, prompts of 64/128/192 tokens, 16 or 32 new tokens, greedy) runs
through ``ContinuousEngine`` (block-paged pool, GN sentinels on) with the
streamed read forced, unreduced, with f32 params from PRNGKey(0).  Every
request must finish on its length budget with the fault counters at 0.
Its greedy tokens must match the static ``generate`` oracle's up to
reduction order, and fed the served tokens the oracle's logits must match
the served ones (see ``ULPS``).  A second phase serves the same workload
as it runs by default on one TPU: the paged read is the Pallas kernel,
which must be in the compiled tick, run every tick, keep the sentinels
quiet and pass the same logit gate.  A third phase serves both reads with
float32 activations under float32 matmul precision, where the gates are
tight enough to fail on a stale KV block or a position off by one
(``chip_diag.py`` plants both).  A fourth serves both reads there over int8
KV arenas: the kernel unpacks four heads from each 32-bit word of an int8
arena, and its tokens and first-token logits must match the streamed
read's over the same quantized arenas under the float32 margin gate.

Four chips: the same workload on ``ContinuousEngine(devices=4)``, built as
``launch/serve.py --devices 4`` builds it (params made on the replicated
mesh), and on ``devices=1`` over device 0's replica in the same process;
their greedy tokens must match under the bfloat16 bound, and fed the
tokens the mesh served, the static oracle's logits must match the mesh's.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
N being the number of devices the run served on.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import attention  # noqa: E402
from repro.models.transformer import make_model  # noqa: E402
from repro.serve.engine import static_reference  # noqa: E402

ARCH = "internlm2-1.8b"
# The --continuous workload at chip scale: 64-token chunks and blocks keep
# the horizon-bucket grid at [1, 2, 4], so each engine compiles at most six
# tick programs.
SERVE_ARGS = ["--arch", ARCH, "--continuous", "--requests", "8",
              "--prompt-len", "128", "--new-tokens", "32", "--stagger", "2",
              "--chunk", "64", "--num-slots", "4"]
FAULT_COUNTERS = ("sentinel_violations", "retries", "fallbacks",
                  "quarantined_blocks", "failed_completions")
# Greedy identity holds bit for bit on the CPU, but XLA:TPU compiles the
# differently shaped programs it compares (the (slots, chunk) tick, the
# static (group, prompt) prefill and (group, 1) decode, one device against
# four) with different accumulation orders and intermediate roundings.
# Bounds are counted in bfloat16 ulps at the largest logit's magnitude |z|
# (one ulp is 2^(floor(log2|z|) - 7)), whatever the activation dtype: the
# GN softmax rounds scores to a 1/8 grid before its LUT exponential, so a
# rounding change that moves a score across a grid step moves the logits by
# about one bfloat16 ulp even in float32.  Tokens are compared up to the
# first step where the reference's top-2 margin falls below the first
# bound, and first-token logits must agree within it.  Fed the same tokens,
# the served and oracle logits must agree at every step within the second.
#
# Measured on a v5e at these widths (chip_diag.py): bfloat16, served vs
# oracle on the same tokens at most 0.63 (20 ulps), the oracle batch 1 vs
# batched 0.25; float32 under float32 matmul precision, 0.026 and 0.013.  A
# stale KV block and a position off by one moved the logits by 6.6 and 2.9
# (bfloat16), 6.6 and 2.7 (float32).
ULPS = {"bfloat16": (8, 32), "float32": (2, 4)}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def tokens_by_id(comps) -> dict[int, np.ndarray]:
    return {c.request_id: c.tokens for c in comps}


def check_served(engine, reqs, comps, label: str) -> dict:
    """Every request completes on its length budget with the fault machinery
    idle: a recovered fault would hide a numerical fault of the chip."""
    m = engine.metrics()
    check(sorted(c.request_id for c in comps) == [r.id for r in reqs],
          f"{label}: completions do not match the requests")
    reasons = {c.request_id: c.finish_reason for c in comps}
    check(all(r == "length" for r in reasons.values()),
          f"{label}: finish reasons {reasons}")
    check(m["sentinels"] and m["sentinel_checks"] > 0,
          f"{label}: sentinels did not run")
    faults = {k: m[k] for k in FAULT_COUNTERS}
    print(f"[{label}] sentinel checks {m['sentinel_checks']}, fault counters "
          f"{faults}")
    check(not any(faults.values()), f"{label}: fault counters {faults}")
    return m


def top2_margin(logits: np.ndarray) -> np.ndarray:
    """Top-1 minus top-2 logit along the last axis."""
    top2 = np.sort(logits, axis=-1)[..., -2:]
    return top2[..., 1] - top2[..., 0]


def logits_by_id(comps) -> dict[int, np.ndarray]:
    """The held logits each completion's tokens were chosen from (an engine
    built with ``record_logits=True``)."""
    return {c.request_id: c.logits for c in comps}


def logit_bound(logits: dict[int, np.ndarray], ulps: int) -> float:
    """``ulps`` bfloat16 ulps at the largest logit's magnitude."""
    zmax = max(float(np.max(np.abs(z))) for z in logits.values())
    return float(ulps * 2.0 ** (np.floor(np.log2(zmax)) - 7))


def margin_gate(label, reqs, got, want, got_logits, want_logits,
                dtype: str) -> None:
    """Greedy identity up to reduction order (see ULPS): ``got``'s tokens
    equal ``want``'s up to the first step where ``want``'s top-2 margin is
    below the bound, and the first-token logits agree within it."""
    ulps = ULPS[dtype][0]
    bound = logit_bound(want_logits, ulps)
    exact = all(np.array_equal(got[r.id], want[r.id]) for r in reqs)
    print(f"[{label}] greedy tokens bit-identical: {exact}; {dtype} bound "
          f"{bound} ({ulps} bfloat16 ulps)")
    compared = total = 0
    for r in reqs:
        g, w = got[r.id][r.prompt_len:], want[r.id][r.prompt_len:]
        margins = top2_margin(want_logits[r.id])
        close = np.nonzero(margins < bound)[0]
        horizon = int(close[0]) if len(close) else len(margins)
        diff = np.nonzero(g != w)[0]
        step = int(diff[0]) if len(diff) else None
        first = float(np.max(np.abs(got_logits[r.id][0] - want_logits[r.id][0])))
        row = {"request": r.id, "steps": len(w), "compared": horizon,
               "first_divergence": step, "first_token_max_abs_diff": first}
        if step is not None:
            row.update(
                ref_top2_margin=float(margins[step]),
                ref_gap_to_served_token=float(
                    want_logits[r.id][step, w[step]] - want_logits[r.id][step, g[step]]),
                max_abs_diff_there=float(np.max(np.abs(
                    got_logits[r.id][step] - want_logits[r.id][step]))))
        print(f"[{label}] {row}")
        check(step is None or step >= horizon,
              f"{label}: request {r.id} diverged at step {step}, where the "
              f"reference's top-2 margin {margins[step]} >= {bound}")
        check(first <= bound, f"{label}: request {r.id} first-token logits "
                              f"differ by {first} > {bound}")
        compared += horizon
        total += len(w)
    print(f"[{label}] margin gate passed: {compared} of {total} generated "
          "tokens compared")


def forced_gate(label, oracle, params, reqs, comps, scfg) -> dict:
    """Fed the tokens ``comps`` served, the static ``oracle`` model's logits
    must match the served ones within the forced bound of its activation
    dtype at every step: the same tokens go in, so any gap is the two
    programs' arithmetic alone.  Returns the oracle's logits, by request
    id."""
    _, forced = static_reference(oracle, params, reqs, scfg,
                                 forced=tokens_by_id(comps), return_logits=True)
    held = logits_by_id(comps)
    dtype = oracle.cfg.dtype
    ulps = ULPS[dtype][1]
    bound = logit_bound(forced, ulps)
    gaps = {r.id: float(np.max(np.abs(held[r.id] - forced[r.id]))) for r in reqs}
    print(f"[{label}] max |served - oracle| logits over every step, oracle fed "
          f"the served tokens: {gaps}; {dtype} bound {bound} ({ulps} bfloat16 ulps)")
    check(max(gaps.values()) <= bound,
          f"{label}: served logits left the oracle's on the same tokens")
    return forced


def print_widths(cfg, params) -> None:
    leaves = jax.tree.leaves(params)
    n_params = sum(int(x.size) for x in leaves)
    n_bytes = sum(int(x.nbytes) for x in leaves)
    print(f"[arch] {cfg.name}: layers {cfg.n_layers}, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} kv, head_dim {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, norm {cfg.norm_impl}, "
          f"softmax {cfg.softmax_impl}, params {n_params} "
          f"({cfg.param_dtype}, {n_bytes} bytes), activations {cfg.dtype}")


def print_memory(label: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{label}] device 0 peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")


def print_compiles(label: str, m: dict, wall: float) -> None:
    print(f"[{label}] compiles: fused {m['fused_step_compilations']} "
          f"(buckets {m['fused_buckets']}), decode {m['decode_compilations']} "
          f"(buckets {m['decode_buckets']}), prefill {m['prefill_compilations']}; "
          f"tokens served {m['generated_tokens']} in {m['decode_steps']} ticks; "
          f"set-up wall time, compiles included: {wall:.1f} s")


def serve_streamed(model, params, args, scfg, **engine_kw):
    """``serve.serve_continuous`` with the streamed paged read forced."""
    attention.FORCE_PAGED_READ = "streamed"
    try:
        return serve.serve_continuous(model, params, args, scfg,
                                      record_logits=True, **engine_kw)
    finally:
        attention.FORCE_PAGED_READ = None


serve_default = functools.partial(serve.serve_continuous, record_logits=True)


def one_chip() -> None:
    args = serve.parse_args(SERVE_ARGS)
    cfg, model, params, scfg = serve.build(args)
    print_widths(cfg, params)

    engine, reqs, comps, wall = serve_streamed(model, params, args, scfg)
    m = check_served(engine, reqs, comps, "streamed")
    check(m["kv_paged"] and m["read_path"] == "streamed",
          f"the forced streamed paged read did not run: {m['read_path']}")
    print_compiles("streamed", m, wall)
    print_memory("streamed")
    served = tokens_by_id(comps)

    t0 = time.time()
    ref, ref_logits = static_reference(model, params, reqs, scfg,
                                       return_logits=True)
    print(f"[oracle] static generate set-up wall time, compiles included: "
          f"{time.time() - t0:.1f} s")
    margin_gate("oracle", reqs, served, ref, logits_by_id(comps), ref_logits,
                cfg.dtype)
    forced_gate("oracle", model, params, reqs, comps, scfg)
    del engine

    engine, _, comps, wall = serve_default(model, params, args, scfg)
    m = check_served(engine, reqs, comps, "pallas")
    check(m["read_path"] == "pallas"
          and m["kernel_read_ticks"] == m["decode_steps"],
          f"default read on one TPU: {m['read_path']}, "
          f"{m['kernel_read_ticks']} of {m['decode_steps']} ticks in the kernel")
    print_compiles("pallas", m, wall)
    agree = {}
    for c in sorted(comps, key=lambda c: c.request_id):
        diff = np.nonzero(c.tokens != served[c.request_id])[0]
        agree[c.request_id] = (int(diff[0]) - len(c.prompt_tokens)
                               if len(diff) else None)
    print(f"[pallas] greedy agreement with the streamed run: "
          f"{sum(d is None for d in agree.values())}/{len(comps)} requests "
          f"token-identical; first differing generated step per request "
          f"{agree}")
    forced_gate("pallas", model, params, reqs, comps, scfg)
    has_kernel = "tpu_custom_call" in engine.compiled_tick_text(reqs)
    print(f"[pallas] compiled fused tick holds tpu_custom_call: {has_kernel}")
    check(has_kernel, "the Pallas read is not in the compiled tick")
    print_memory("pallas")
    del engine

    # both reads again with float32 activations under float32 matmul
    # precision: the rounding noise shrinks about 25-fold, so the gates
    # compare most tokens and sit far below what a fault does (see ULPS)
    f32 = dataclasses.replace(cfg, dtype="float32")
    oracle = make_model(f32)
    with jax.default_matmul_precision("float32"):
        ref32, ref32_logits = static_reference(oracle, params, reqs, scfg,
                                               return_logits=True)
        for read in ("streamed", "pallas"):
            label = f"f32-{read}"
            serve_fn = serve_streamed if read == "streamed" else serve_default
            engine, _, comps, wall = serve_fn(oracle, params, args, scfg)
            m = check_served(engine, reqs, comps, label)
            check(m["read_path"] == read, f"{label}: read {m['read_path']}")
            print_compiles(label, m, wall)
            margin_gate(label, reqs, tokens_by_id(comps), ref32,
                        logits_by_id(comps), ref32_logits, "float32")
            forced_gate(label, oracle, params, reqs, comps, scfg)
            del engine

        # int8 KV arenas: both reads dequantize the same per-block int8
        # values, so the gap is the reads' arithmetic alone
        served8 = {}
        for read, serve_fn in (("streamed", serve_streamed),
                               ("pallas", serve_default)):
            label = f"f32-int8-{read}"
            engine, _, comps, wall = serve_fn(oracle, params, args, scfg,
                                              kv_dtype="int8")
            m = check_served(engine, reqs, comps, label)
            ticks = m["decode_steps"] if read == "pallas" else 0
            check(m["read_path"] == read and m["kv_dtype"] == "int8"
                  and m["kernel_read_ticks"] == ticks,
                  f"{label}: read {m['read_path']}, kv {m['kv_dtype']}, "
                  f"{m['kernel_read_ticks']} of {m['decode_steps']} ticks "
                  "in the kernel")
            print_compiles(label, m, wall)
            served8[read] = comps
            del engine
        margin_gate("f32-int8", reqs, tokens_by_id(served8["pallas"]),
                    tokens_by_id(served8["streamed"]),
                    logits_by_id(served8["pallas"]),
                    logits_by_id(served8["streamed"]), "float32")
    print_memory("f32")


def four_chips() -> None:
    # `launch/serve.py --devices 4` builds the params straight onto the
    # replicated slot mesh; the devices=1 engine reads device 0's replica
    # in place, so no device holds two copies of the 7.6 GB of f32 params
    args4 = serve.parse_args(SERVE_ARGS + ["--devices", "4"])
    args1 = serve.parse_args(SERVE_ARGS)
    cfg, model, params4, scfg = serve.build(args4)
    dev0 = jax.devices()[0]
    params1 = jax.tree.map(
        lambda x: next(s.data for s in x.addressable_shards if s.device == dev0),
        params4)
    print_widths(cfg, params4)

    engine, reqs, comps4, wall = serve.serve_continuous(
        model, params4, args4, scfg, record_logits=True)
    m = check_served(engine, reqs, comps4, "devices=4")
    leaves = jax.tree.leaves(engine.params)
    replicated = NamedSharding(engine.mesh, P())
    placed = all(x.sharding == replicated and x.is_fully_replicated
                 for x in leaves)
    in_place = all(a is b for a, b in zip(leaves, jax.tree.leaves(params4)))
    print(f"[devices=4] params replicated on the slot mesh "
          f"({len(engine.mesh.devices.flat)} devices): {placed}; placed "
          f"without a copy: {in_place}")
    check(placed, "devices=4 engine params are not replicated on its mesh")
    print(f"[devices=4] admissions per device {m['device_admits']}, balance "
          f"{m['shard_balance']:.2f}")
    print_compiles("devices=4", m, wall)
    forced_gate("devices=4", model, params4, reqs, comps4, scfg)
    del engine

    engine, _, comps1, wall = serve.serve_continuous(
        model, params1, args1, scfg, record_logits=True)
    m = check_served(engine, reqs, comps1, "devices=1")
    print_compiles("devices=1", m, wall)
    margin_gate("mesh", reqs, tokens_by_id(comps4), tokens_by_id(comps1),
                logits_by_id(comps4), logits_by_id(comps1), cfg.dtype)
    print_memory("mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 1
    if len(jax.devices()) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} devices, "
              f"JAX sees {len(jax.devices())}", file=sys.stderr)
        return 1
    print(f"[env] jax {jax.__version__}, {dev.device_kind}, "
          f"{len(jax.devices())} devices; compile cache {use_compile_cache()}")
    (four_chips if opts.chips == 4 else one_chip)()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": opts.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
