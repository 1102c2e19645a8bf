"""Check chip_smoke.py's logit gates against the oracle's own spread and
against planted serving faults.

    python chip_diag.py                      # internlm2-1.8b, unreduced, on a TPU
    python chip_diag.py --smoke              # reduced config, any backend
    python chip_diag.py --out diag.json      # the results as JSON too

It serves chip_smoke's workload through ``repro.launch.serve`` with the
engine's held logits recorded, then measures two things:

1. Oracle self-spread.  The static oracle is fed the served tokens twice: with
   each (prompt_len, max_new) group batched as chip_smoke runs it, and with
   each request alone (batch 1).  The gap between the two is reduction order
   alone, since one program family sees the same tokens.  The gates' bounds
   must clear it.
2. Planted faults.  The warm engine replays the workload while one silent
   serving fault is planted between ticks.  chip_smoke's gates then run on
   what it served.  Each fault must fail them:

   - ``stale_block``: one decoding slot's first KV block is overwritten, at
     every layer, with another live slot's first block.  This is what a
     wrong block-table entry or Pallas index map reads.
   - ``position``: one decoding slot's device position runs one ahead until
     the lanes are next re-uploaded.  This is a position off by one.

   GN keeps the sum of p at 1 whichever KV it reads, so the sentinels should
   stay quiet on both.  The fault counters are printed for each run.

On a TPU, run it alone: it holds the chip for the whole process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import chip_smoke
from chip_smoke import SmokeFailure, logits_by_id, tokens_by_id
from repro.launch import serve
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import make_model
from repro.serve.engine import static_reference

# The tick before which each fault is planted: by then every slot of the
# chip_smoke workload is decoding (its timeline runs PPDD at tick 6).
PLANT_TICK = 8


def _decoding_pair(engine):
    """A decoding slot with committed KV and another live slot, or None."""
    pool = engine.pool
    live = [s for s, st in enumerate(engine._slots)
            if st is not None and int(pool.positions[s]) > 0
            and len(pool.chain_of(s))]
    victims = [s for s in live if engine._slots[s].phase == "decoding"]
    if not victims or len(live) < 2:
        return None
    return victims[0], next(s for s in live if s != victims[0])


def plant_stale_block(engine) -> dict | None:
    pair = _decoding_pair(engine)
    if pair is None:
        return None
    victim, source = pair
    dst = int(engine.pool.chain_of(victim)[0])
    src = int(engine.pool.chain_of(source)[0])
    layers = engine.pool.cache["layers"]
    engine.pool.cache = {**engine.pool.cache, "layers": {
        name: leaf.at[:, dst].set(leaf[:, src]) for name, leaf in layers.items()}}
    return {"request": engine._slots[victim].req.id, "block": dst,
            "copied_from": src}


def plant_position(engine) -> dict | None:
    pair = _decoding_pair(engine)
    if pair is None:
        return None
    victim = pair[0]
    old = engine._pos_dev
    new = jnp.asarray(np.asarray(old) + (np.arange(old.shape[0]) == victim),
                      old.dtype)
    engine._pos_dev = jax.device_put(new, old.sharding) if old.committed else new
    return {"request": engine._slots[victim].req.id,
            "position": int(engine.pool.positions[victim]) + 1}


PLANTS = {"stale_block": plant_stale_block, "position": plant_position}


def replay_planted(engine, reqs, plant):
    """Replay ``reqs`` on the warm ``engine``, planting one fault before tick
    PLANT_TICK (or the first tick after it with a target)."""
    engine.reset()
    for r in reqs:
        engine.submit(r)
    where = None
    while True:
        if where is None and engine.step_count >= PLANT_TICK:
            where = plant(engine)
            if where is not None:
                where["tick"] = engine.step_count
        if not engine.step():
            break
    return engine.completions, where


def gaps(got: dict, want: dict) -> dict:
    """Per request, the largest and the root-mean-square logit gap over
    every step and vocabulary entry, and one bfloat16 ulp at the largest
    logit."""
    return {"max": {i: float(np.max(np.abs(got[i] - want[i]))) for i in want},
            "rms": {i: float(np.sqrt(np.mean((got[i] - want[i]) ** 2)))
                    for i in want},
            "bf16_ulp": chip_smoke.logit_bound(want, 1)}


def gates(label, model, params, reqs, comps, ref, ref_logits, scfg) -> dict:
    """Run chip_smoke's gates on ``comps``: the first failure (or None) and
    the served logits' gaps to the oracle fed the served tokens."""
    failed, forced = None, None
    try:
        chip_smoke.margin_gate(label, reqs, tokens_by_id(comps), ref,
                               logits_by_id(comps), ref_logits,
                               model.cfg.dtype)
        forced = chip_smoke.forced_gate(label, model, params, reqs, comps, scfg)
    except SmokeFailure as e:
        failed = str(e)
    if forced is None:
        _, forced = static_reference(model, params, reqs, scfg,
                                     forced=tokens_by_id(comps),
                                     return_logits=True)
    return {"gates_fail": failed,
            "gap": gaps(logits_by_id(comps), forced)}


def oracle_spread(model, params, reqs, served, scfg) -> dict:
    """The oracle fed ``served``: each request alone against its group."""
    _, grouped = static_reference(model, params, reqs, scfg, forced=served,
                                  return_logits=True)
    alone = {}
    for r in reqs:
        alone.update(static_reference(model, params, [r], scfg, forced=served,
                                      return_logits=True)[1])
    return gaps(alone, grouped)


def diagnose(model, params, args, scfg) -> dict:
    engine, reqs, comps, _ = serve.serve_continuous(
        model, params, args, scfg, record_logits=True)
    served = tokens_by_id(comps)
    ref, ref_logits = static_reference(model, params, reqs, scfg,
                                       return_logits=True)
    result = {"clean": gates("clean", model, params, reqs, comps, ref,
                             ref_logits, scfg)}
    print(f"[clean] {result['clean']}")
    result["oracle_spread"] = oracle_spread(model, params, reqs, served, scfg)
    print(f"[spread] oracle batch 1 vs batched, same tokens: "
          f"{result['oracle_spread']}")
    compiles = engine.metrics()["fused_step_compilations"]
    for name, plant in PLANTS.items():
        comps, where = replay_planted(engine, reqs, plant)
        m = engine.metrics()
        row = {"planted": where,
               "faults": {k: m[k] for k in chip_smoke.FAULT_COUNTERS},
               **gates(name, model, params, reqs, comps, ref, ref_logits,
                       scfg)}
        print(f"[{name}] {row}")
        result[name] = row
    result["recompiled"] = engine.metrics()["fused_step_compilations"] - compiles
    print(f"[diag] fused tick compiles added by the replays: "
          f"{result['recompiled']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, on whatever backend JAX has")
    ap.add_argument("--dtypes", default="bfloat16,float32",
                    help="activation dtypes to diagnose; float32 runs with "
                         "float32 matmul precision")
    ap.add_argument("--out", default=None, help="write the results as JSON")
    opts = ap.parse_args(argv)
    dev = jax.devices()[0]
    if not opts.smoke and dev.platform != "tpu":
        print(f"chip_diag: JAX's first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    print(f"[env] jax {jax.__version__}, {dev.device_kind}; compile cache "
          f"{use_compile_cache()}")
    args = serve.parse_args(chip_smoke.SERVE_ARGS
                            + (["--smoke", "--chunk", "8"] if opts.smoke else []))
    cfg, _, params, scfg = serve.build(args)
    chip_smoke.print_widths(cfg, params)
    results, ok = {}, True
    for dtype in opts.dtypes.split(","):
        print(f"[diag] activations {dtype}")
        model = make_model(dataclasses.replace(cfg, dtype=dtype))
        precision = "float32" if dtype == "float32" else None
        with jax.default_matmul_precision(precision):
            r = results[dtype] = diagnose(model, params, args, scfg)
        caught = {n: r[n]["gates_fail"] is not None for n in PLANTS}
        clean = r["clean"]["gates_fail"] is None
        print(json.dumps({"activations": dtype, "clean_passes": clean,
                          "planted_faults_caught": caught}))
        ok &= clean and all(caught.values())
    if opts.out:
        Path(opts.out).parent.mkdir(parents=True, exist_ok=True)
        Path(opts.out).write_text(json.dumps(results, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
