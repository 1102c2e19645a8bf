"""The benchmark's CPU tests: a cell cut to a size the CPU serves in a
second or two, through the same machinery as a chip run."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

TINY_WIDTHS = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2, vocab_size=256)
TINY_PROGRAM = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                    vocab=256, head_dim=16)
TINY_MLA = dict(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, num_key_value_heads=4)
# Readings of the tiny dense cells on the CPU, 1.5 s windows, 256 tokens
# compared, seeds 1-6 of each: the program's widest gap 0.0007-0.0042, the
# float8 control's 0.0124-0.0521.  The limit sits between them, with more
# room above the program's readings.
TINY_LIMIT = 0.008


# The MLA cell's files are kept for a later benchmark change (PERF.md, open
# questions); the tests still drive them through the machinery.
MLA_CELL = {"name": "minicpm3-docqa-8k", "config": "minicpm3-4b", "traffic": "docqa-8k",
            "chips": 1, "file": "benchmarks/chip/configs/minicpm3-4b.json"}


def bench_with_mla() -> dict:
    import harness

    bench = copy.deepcopy(harness.load_benchmark())
    bench["configs"].append({"name": MLA_CELL["config"], "file": MLA_CELL["file"]})
    bench["workloads"].append({k: MLA_CELL[k] for k in ("name", "config", "traffic", "chips")})
    return bench


def tiny_cell(name: str = "internlm2-chat") -> dict:
    import harness

    cell = copy.deepcopy(harness.resolve_cell(bench_with_mla(), name))
    cfg = cell["config"]
    cfg.update(TINY_WIDTHS)
    cfg["program"] = {"arch": cfg["program"]["arch"], "overrides": dict(TINY_PROGRAM)}
    if "kv_lora_rank" in cfg:
        from repro.configs.base import MLAConfig

        cfg.update(TINY_MLA)
        cfg["program"]["overrides"].update(
            n_kv_heads=4, head_dim=24,
            mla=MLAConfig(**{k: v for k, v in TINY_MLA.items() if k != "num_key_value_heads"}))
    cfg["correct"] = {"max_logit_gap": TINY_LIMIT, "sample_tokens": 256}
    mix = cell["mix"]
    mix.update(lead_in_s=0.3, strata=4,
               prompt={"dist": "lognormal", "median": 12, "sigma": 0.8, "min": 4, "max": 40},
               output={"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 16})
    if mix["kind"] == "open_loop":
        mix["rate_rps"] = 20.0
    cell["engine"] = {"num_slots": 4, "chunk": 8, "block_size": 8, "max_seq": 64, "devices": 1}
    return cell


@pytest.fixture
def tiny():
    return tiny_cell
