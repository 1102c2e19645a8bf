"""Fault-tolerant training driver.

Features exercised by tests/test_fault_tolerance.py:
  * checkpoint every K steps (atomic), auto-resume from the latest valid one;
  * deterministic, step-keyed data (restart reproduces the uninterrupted run
    bit-for-bit);
  * failure injection: ``--fail-at N`` (or REPRO_FAIL_AT_STEP) hard-kills the
    process mid-run to simulate a node failure;
  * straggler watchdog: per-step wall time against a running median — slow
    steps are logged with a restart hint (on real multi-pod deployments this
    feeds the controller that evicts the slow host);
  * optional mesh (``--mesh dxm``) with FSDP+TP sharding rules, optional int8
    error-feedback gradient compression for the cross-pod axis.

Usage (CPU-scale):
  python -m repro.launch.train --arch internlm2-1.8b --smoke --steps 20
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time
from pathlib import Path

import jax

from repro.checkpoint import store
from repro.configs.registry import get_config, reduce_config
from repro.data.synthetic import DataConfig, batch_at
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import make_model
from repro.parallel.sharding import use_sharding
from repro.train.loop import make_train_step
from repro.train.optimizer import OptimizerConfig, init_opt_state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-every", type=int, default=5)
    ap.add_argument("--outdir", default="runs/default")
    ap.add_argument("--fail-at", type=int, default=int(os.environ.get("REPRO_FAIL_AT_STEP", -1)))
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 (data x model)")
    ap.add_argument("--grad-compression", type=int, default=None)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_config(cfg)
    model = make_model(cfg)
    data_cfg = DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch, seed=1234
    )
    opt_cfg = OptimizerConfig(
        lr=args.lr, warmup_steps=5, total_steps=args.steps,
        grad_compression=args.grad_compression,
    )

    mesh_ctx = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"))
        mesh_ctx = use_sharding(mesh)

    outdir = Path(args.outdir)
    ckpt_dir = outdir / "ckpt"
    outdir.mkdir(parents=True, exist_ok=True)
    log_path = outdir / "train_log.jsonl"

    def run():
        params = model.init(jax.random.PRNGKey(0))
        opt_state = init_opt_state(opt_cfg, params)
        start = 0
        latest = store.latest_step(ckpt_dir)
        if latest is not None:
            (params, opt_state), man = store.restore(
                ckpt_dir, latest, (params, opt_state)
            )
            start = man["step"]
            print(f"[resume] from checkpoint step {start}", flush=True)

        train_step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
        times: list[float] = []
        log = open(log_path, "a")
        for step in range(start, args.steps):
            t0 = time.time()
            batch = batch_at(data_cfg, step)
            params, opt_state, metrics = train_step(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            times.append(dt)
            med = statistics.median(times[-20:])
            straggler = len(times) > 3 and dt > args.straggler_factor * med
            rec = {"step": step + 1, "loss": loss, "sec": round(dt, 4),
                   "straggler": bool(straggler)}
            log.write(json.dumps(rec) + "\n")
            log.flush()
            print(f"step {step+1:5d} loss {loss:.4f} {dt*1e3:7.1f}ms"
                  + ("  [STRAGGLER]" if straggler else ""), flush=True)
            if (step + 1) % args.checkpoint_every == 0 or step + 1 == args.steps:
                store.save(ckpt_dir, step + 1, (params, opt_state),
                           extra={"arch": cfg.name})
            if args.fail_at == step + 1:
                print(f"[failure-injection] dying at step {step+1}", flush=True)
                os._exit(42)  # hard kill: no cleanup, like a real node loss
        log.close()
        final = float(metrics["loss"])
        print(f"[done] final loss {final:.4f}")
        return final

    if mesh_ctx is not None:
        with mesh_ctx:
            return run()
    return run()


if __name__ == "__main__":
    main()
