"""Readings that set a cell's correctness limit: the program's widest
logit gap on many seeds, and the float8 control's on the same sample.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 15

One process builds the cell once; each seed then gets its own weights in
the same engine (the compiled ticks are the window's own), its own traffic
at the cell's load with a short window, and the comparison.  The first
``--control-seeds`` seeds also read the control on the same sample.  Rows
also go to ``benchmarks/chip/.out/calibrate-<cell>.jsonl``.  The limit is then set
between the two readings in the configuration file, and the readings go
into PERF.md.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    out = harness.HERE / ".out" / f"calibrate-{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    st = harness.build(cell, seeds[0], log=log)
    dtype = st.weights["embed"]["tok"].dtype
    for i, seed in enumerate(seeds):
        if i:
            st.engine.params = st.weights = None
            gc.collect()
            st.weights = reference.make_weights(st.struct, seed, dtype)
            st.engine.params = st.weights
            st.engine.reset()
        res, comps, rec = harness.serve(st, cell, seed, args.seconds, False,
                                        rate=args.rate, t_start=time.time(), log=log)
        res = harness.check(res, comps, rec, cell["config"], st.weights, seed,
                            control=i < args.control_seeds, log=log)
        row = {"seed": seed, "correct": res["correct"],
               **{k: v["value"] for k, v in res["checks"].items()}, **res["_run"]}
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")
        st.engine.reset()
    return 0


if __name__ == "__main__":
    sys.exit(main())
