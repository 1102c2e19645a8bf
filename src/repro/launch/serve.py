"""Serving driver: static batches or continuous batching, GN datapath.

The serving analogue of launch/train.py — loads (or initializes) weights,
then serves synthetic request workloads, reporting latency/throughput and
score-oriented integrity (mean log-prob of the generated continuations,
which is exactly the quantity guaranteed normalization protects).

Two modes:
  * static (default): the seed engine — uniform-length prompt batches,
    everyone decodes to --new-tokens.  Kept as the correctness oracle.
  * --continuous: FCFS continuous batching over a slot-paged KV pool with
    chunked prefill fused into a single jitted per-tick step — prompts are
    bucketed to the chunk grid and stream through idle lanes while other
    slots decode (see serve/engine.ContinuousEngine).  Greedy outputs are
    verified token-identical to the static path.

Usage (CPU smoke scale):
  python -m repro.launch.serve --arch internlm2-1.8b --smoke --batches 3
  python -m repro.launch.serve --smoke --continuous
  python -m repro.launch.serve --smoke --continuous --devices 2

``--continuous --devices N`` shards the slot pool over an N-device mesh
(slot-axis NamedSharding, least-loaded admission — docs/serving.md §Device
mesh).  Under ``--smoke`` (CPU) the launcher forces N host-platform devices
itself; on real hardware export the matching XLA/topology env first.
"""
from __future__ import annotations

import argparse
import sys as _sys
import time

from repro.launch._host_devices import force_host_devices

# --smoke --devices N on CPU: force N host-platform devices BEFORE jax
# initializes (XLA reads the flag once at backend creation).  Only fires
# for the smoke path; an explicit operator XLA_FLAGS always wins.
if "--smoke" in _sys.argv:
    force_host_devices()

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import store
from repro.configs.registry import get_config, list_archs, reduce_config
from repro.data.synthetic import DataConfig, batch_at
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import make_model
from repro.parallel.sharding import make_slot_mesh
from repro.serve.engine import (
    ContinuousEngine,
    ServeConfig,
    generate,
    perplexity,
    round_slots_to_devices,
    static_reference,
)
from repro.serve.workload import required_max_seq, staggered_requests


def _serve_static(model, cfg, params, args, scfg):
    data = DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                      global_batch=args.batch_size, seed=11)
    total_tok = 0.0
    t_all = time.time()
    for i in range(args.batches):
        req = batch_at(data, i)
        if cfg.family == "encdec":
            req["frames"] = jnp.zeros((args.batch_size, cfg.encoder_seq, cfg.d_model))
        if cfg.family == "vlm":
            req["patches"] = jnp.zeros((args.batch_size, cfg.num_patches, cfg.d_model))
        t0 = time.time()
        out = generate(model, params, req, scfg)
        dt = time.time() - t0
        new_tok = args.batch_size * args.new_tokens
        total_tok += new_tok
        ppl = perplexity(model, params, {**req, "tokens": out})
        print(f"batch {i}: {out.shape} in {dt:.2f}s "
              f"({new_tok/dt:.1f} tok/s)  seq ppl {ppl:.3f}")
    dt_all = time.time() - t_all
    print(f"served {args.batches} batches, {total_tok/dt_all:.1f} tok/s overall "
          f"(softmax={cfg.softmax_impl}, norm={cfg.norm_impl})")


def serve_continuous(model, params, args, scfg, **engine_kw):
    """Serve the staggered workload ``args`` describes through
    ``ContinuousEngine`` (``engine_kw`` adds constructor options), print the
    run's counts, and check the compile counters against the horizon-bucket
    bound.  Returns ``(engine, requests, completions, wall_s)``; ``wall_s``
    covers compilation on a cold engine.  ``--continuous`` and
    ``chip_smoke.py`` both serve through here."""
    reqs = staggered_requests(
        model.cfg, n_requests=args.requests, base_len=args.prompt_len,
        max_new_tokens=args.new_tokens, stagger=args.stagger, seed=11,
    )
    num_slots = round_slots_to_devices(args.num_slots, args.devices)
    engine = ContinuousEngine(model, params, num_slots=num_slots,
                              max_seq=required_max_seq(reqs), cfg=scfg,
                              chunk=args.chunk, devices=args.devices,
                              **engine_kw)
    t0 = time.time()
    comps = engine.run(reqs)
    wall = time.time() - t0
    m = engine.metrics()
    print(f"continuous: {len(comps)} requests, {m['generated_tokens']} tokens, "
          f"slots={num_slots} util={m['mean_slot_utilization']:.2f}")
    if m["num_devices"] > 1:
        print(f"slot pool sharded over {m['num_devices']} devices "
              f"({m['per_device_slots']} slots each): admissions/device "
              f"{m['device_admits']}, balance {m['shard_balance']:.2f}")
    print(f"fused step compiled {m['fused_step_compilations']}x, decode "
          f"{m['decode_compilations']}x, per-prompt-length prefill "
          f"{m['prefill_compilations']}x  (chunk={m['chunk']}, intake "
          f"padding {m['intake_padding']} tok)")
    print("host phases of step(), ms: " + ", ".join(
        f"{name} {s * 1e3:.1f}" for name, s in m["host_phase_s"].items()))
    if m["kv_paged"]:
        print(f"paged reads: {m['read_path']}; horizon buckets "
              f"{m['horizon_buckets']} of grid {m['horizon_bucket_grid']} "
              f"(mean attended {m['mean_attended_tokens_per_tick']:.1f} "
              "tok/tick)")

    # per-tick slot phase occupancy: the fusion benefit made visible —
    # prefill chunks ride lanes that would otherwise idle while decoding.
    print("tick phases (P=prefill lanes, D=decode lanes, .=idle):")
    for chunk_rows in range(0, len(engine.phase_log), 20):
        rows = engine.phase_log[chunk_rows : chunk_rows + 20]
        lanes = " ".join(
            f"{'P'*p}{'D'*d}{'.'*(engine.num_slots-p-d)}" for p, d in rows
        )
        print(f"  tick {chunk_rows:3d}+ [{lanes}]")
    pf = m["prefill_lane_fraction"]
    print(f"  {m['fused_ticks']}/{m['decode_steps']} ticks carried prefill "
          f"chunks ({pf*100:.0f}% of busy lanes were prefill)")
    for c in sorted(comps, key=lambda c: c.request_id):
        print(f"  req {c.request_id}: prompt {len(c.prompt_tokens)} "
              f"+{len(c.new_tokens)} [{c.finish_reason}]  "
              f"arrive@{c.arrival_step} admit@{c.admit_step} "
              f"finish@{c.finish_step}")

    # Counters are explicit trace counts (always ints).  Slab engines
    # compile the fused step exactly once (decode fast path may be unused);
    # paged engines compile once per (step kind, horizon bucket actually
    # seen), bounded by the bucket grid — see docs/serving.md §Paged read
    # paths.
    if m["kv_paged"]:
        grid = m["horizon_bucket_grid"]
        if not m["fused_step_compilations"] == len(m["fused_buckets"]) <= len(grid):
            raise RuntimeError("fused step recompiled beyond the bucket bound!")
        if not m["decode_compilations"] == len(m["decode_buckets"]) <= len(grid):
            raise RuntimeError("decode step recompiled beyond the bucket bound!")
    else:
        if m["fused_step_compilations"] != 1:
            raise RuntimeError("fused step recompiled!")
        if m["decode_compilations"] not in (0, 1):
            raise RuntimeError("decode step recompiled!")
    if m["prefill_compilations"] != 0:
        raise RuntimeError("per-prompt-length prefill is back?!")
    return engine, reqs, comps, wall


def _serve_continuous(model, params, args, scfg):
    engine, reqs, comps, wall = serve_continuous(model, params, args, scfg)
    gen_tok = engine.metrics()["generated_tokens"]
    print(f"{gen_tok} tokens in {wall:.2f}s ({gen_tok/wall:.1f} tok/s); "
          "latency ms: " + " ".join(
              f"{c.request_id}:{c.latency_s*1e3:.0f}"
              for c in sorted(comps, key=lambda c: c.request_id)))
    if scfg.temperature == 0:
        ref = static_reference(model, params, reqs, scfg)
        same = all(np.array_equal(c.tokens, ref[c.request_id]) for c in comps)
        print(f"greedy outputs token-identical to static path: {same}")
        assert same, "continuous batching diverged from the static oracle"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt", default=None, help="checkpoint dir to restore")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (staggered-arrival workload)")
    ap.add_argument("--requests", type=int, default=8,
                    help="continuous: number of requests in the workload")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="continuous: KV pool capacity (concurrent sequences)")
    ap.add_argument("--stagger", type=int, default=2,
                    help="continuous: arrival gap between requests (steps)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="continuous: prefill chunk size (fused-step lanes)")
    ap.add_argument("--devices", type=int, default=1,
                    help="continuous: shard the slot pool over N devices "
                         "(--smoke forces N host-platform devices itself)")
    return ap.parse_args(argv)


def build(args):
    """The config, model, params (PRNGKey(0), or ``--ckpt``) and serve config
    that ``args`` names.  With ``--devices N > 1`` the params are made
    straight onto the replicated slot-mesh sharding the engine serves from,
    so no device holds a second copy."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    model = make_model(cfg)
    replicated = (NamedSharding(make_slot_mesh(args.devices), P())
                  if args.devices > 1 else None)
    key = jax.random.PRNGKey(0)
    if args.ckpt:
        step = store.latest_step(args.ckpt)
        (params,), _ = store.restore(args.ckpt, step,
                                     (jax.eval_shape(model.init, key),))
        params = jax.device_put(params, replicated)
        print(f"restored checkpoint step {step} from {args.ckpt}")
    else:
        # one jitted init whatever the device count (eager init rounds some
        # values differently), so --devices N serves --devices 1's params
        placed = {} if replicated is None else {"out_shardings": replicated}
        params = jax.jit(model.init, **placed)(key)
    scfg = ServeConfig(max_new_tokens=args.new_tokens, temperature=args.temperature)
    return cfg, model, params, scfg


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    cfg, model, params, scfg = build(args)
    if args.continuous:
        _serve_continuous(model, params, args, scfg)
    else:
        _serve_static(model, cfg, params, args, scfg)


if __name__ == "__main__":
    main()
