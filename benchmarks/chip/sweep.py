"""Find an open-loop cell's knee: serve it at several mean rates, one
process and one engine, and print the end-to-end numbers and the backlog
at each.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 2,3,4 --seconds 25 --seed 5

The knee is the highest rate whose backlog at the close stays small and
whose TTFT stays flat; the cell's mix is then set to 0.8 of it.  Results
also go to ``benchmarks/chip/.out/sweep-<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    out = harness.HERE / ".out" / f"sweep-{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    st = harness.build(cell, args.seed, log=log)
    for rate in (float(r) for r in args.rates.split(",")):
        res, comps, rec = harness.serve(st, cell, args.seed, args.seconds, False,
                                        rate=rate, t_start=time.time(), log=log)
        res = harness.check(res, comps, rec, cell["config"], st.weights, args.seed, log=log)
        row = {"rate_rps": rate, **res["_run"], "correct": res["correct"],
               "checks": res["checks"]}
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")
        st.engine.reset()
    return 0


if __name__ == "__main__":
    sys.exit(main())
