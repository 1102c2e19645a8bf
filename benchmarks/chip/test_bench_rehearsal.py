"""The benchmark's machinery end to end on the CPU at a tiny size, its
refusal to run without a TPU, and what a later change adds by files only."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
MLA = "minicpm3-docqa-8k"
CONTRACT = ("correct", "attempted", "failed", "metrics", "device")


@pytest.mark.parametrize("name", CELLS + [MLA])
def test_rehearsal_result_has_the_contract_keys(tiny, name):
    cell = tiny(name)
    res = harness.run_cell(cell, 2**33 + 1, 1.5, False, require_tpu=False, log=lambda s: None)
    res.pop("_run")
    assert all(k in res for k in CONTRACT) and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in cell["end_to_end"]}
    assert set(res["metrics"]) == want and "setup_s" in want
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    for k, v in res["metrics"].items():
        assert v["unit"] == units[k] and v["value"] > 0
    assert res["device"]["count"] == 1 and res["device"]["platform"] == "cpu"
    assert res["checks"]["compiles_in_window"]["value"] == 0
    json.dumps(res)


def test_traced_rehearsal_reads_the_trace(tiny):
    cell = tiny("internlm2-chat")
    res = harness.run_cell(cell, 7, 1.5, True, require_tpu=False, log=lambda s: None)
    assert res["correct"] is True
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    got = res["metrics"]
    # off the chip there is no table of peaks, so no share of a peak is read
    assert "decode_tick_roofline.chat" not in got and "step_mfu.chat" not in got
    for name in ("queue_wait_ms.chat", "device_idle_share.chat", "fused_tick_ms.chat"):
        assert got[name]["value"] > 0
    assert not harness.TRACE_DIR.exists()


@pytest.mark.parametrize("name", CELLS)
def test_recorded_ticks_match_the_engine(tiny, name):
    """The harness's per-tick record agrees with the engine's own: every
    tick's lanes with its phase_log entry, every finished request's token
    ticks with its first-token and finish steps."""
    import time

    cell = tiny(name)
    st = harness.build(cell, 11, require_tpu=False, log=lambda s: None)
    res, comps, rec = harness.serve(st, cell, 11, 1.5, False, t_start=time.time(),
                                    log=lambda s: None)
    assert rec.ticks and all(len(t.lanes) == t.prefill + t.decode for t in rec.ticks.values())
    done = [c for c in comps if c.finish_reason == "length"]
    assert done
    for c in done:
        ks = rec.emitted[c.request_id]
        assert len(ks) == len(c.new_tokens)
        assert (ks[0], ks[-1]) == (c.first_token_step, c.finish_step)
        assert rec.admit[c.request_id] == c.admit_step
    assert res["_run"]["e2e"]["throughput_tok_s"] > 0


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0], "--seed", "3",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_refuses_a_machine_without_tpu():
    out = _run_py(harness.ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_entry_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    out = _run_py(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_new_config_mix_cell_and_metric_are_files_only(tmp_path):
    root = tmp_path
    chip = root / "benchmarks/chip"
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(HERE / sub, chip / sub)
    cfg = json.loads((HERE / "configs/internlm2-1.8b.json").read_text())
    (chip / "configs/internlm2-1.8b-copy.json").write_text(json.dumps(cfg))
    mix = json.loads((HERE / "traffic/chat.json").read_text())
    mix["rate_rps"] = 1.5
    (chip / "traffic/chat-slow.json").write_text(json.dumps(mix))
    (chip / "cells/copy-chat-slow.json").write_text(json.dumps(
        {"why": "test", "engine": {"num_slots": 4, "chunk": 64, "block_size": 64,
                                   "max_seq": 2048, "devices": 1}}))
    (chip / "metrics/requests_due.chat.py").write_text(
        "def read(ctx):\n    return len(ctx['queue_wait_ms']) or None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "internlm2-1.8b-copy",
                             "file": "benchmarks/chip/configs/internlm2-1.8b-copy.json"})
    bench["workloads"].append({"name": "copy-chat-slow", "config": "internlm2-1.8b-copy",
                               "traffic": "chat-slow", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "requests_due.chat", "unit": "count", "better": "higher",
                               "source": "host_clock", "layer": "scheduler",
                               "moves": "ttft_p50_ms", "workloads": ["copy-chat-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve_cell(harness.load_benchmark(root), "copy-chat-slow", root)
    assert cell["mix"]["rate_rps"] == 1.5 and cell["engine"]["num_slots"] == 4
    assert [m["name"] for m in cell["per_layer"]] == ["requests_due.chat"]
    read = harness.metric_reader("requests_due.chat", chip / "metrics")
    assert read({"queue_wait_ms": [1.0, 2.0]}) == 2
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.resolve_cell(bench, "no-such-cell", root)
