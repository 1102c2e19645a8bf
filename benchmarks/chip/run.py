"""Run one cell of the on-chip serving benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the cell's chips.  The
last line of standard output is the result object; the numbers compared
for ``correct`` are also the last lines of standard error.  Exits non-zero,
with no result, where JAX finds no TPU or too few chips, or where the
checkout lacks the program.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    here = Path(__file__).resolve().parent
    sys.path.insert(0, str(here))
    try:
        import harness
        from harness import BenchError
    except ImportError as e:  # the checkout lacks the program
        print(f"error: {e}", file=sys.stderr)
        return 2
    harness.jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        from repro.launch.compile_cache import use_compile_cache

        use_compile_cache()
        cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
        res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                               t_start=T_PROCESS,
                               log=lambda s: print(s, file=sys.stderr, flush=True))
    except (BenchError, ImportError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    extra = res.pop("_run")
    print(json.dumps({"run": extra}), file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
