"""The trace-to-metrics reduction, on intervals worked by hand and on a
small trace this test records itself with ``jax.profiler`` (CPU)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402


def test_union_gaps_and_labels_hand_worked():
    busy = trace_reduce.union([(5, 8), (0, 2), (1, 3), (9, 9), (7, 10)])
    assert busy == [(0, 3), (5, 10)]
    assert trace_reduce.gaps(busy, 0, 12) == [(3, 5), (10, 12)]
    assert trace_reduce.gaps(busy, 1, 6) == [(3, 5)]
    spans = {"engine.step": [(2.5e9, 4e9)], "harness.wait_arrival": [(4e9, 13e9)]}
    idle = trace_reduce.label_gaps([(3e9, 5e9), (10e9, 12e9), (20e9, 21e9)], spans)
    # (3,5): 1 s of step, 1 s of wait -> the first best; (10,12) wait; (20,21) none
    assert idle == {"engine.step": pytest.approx(2.0), "harness.wait_arrival": pytest.approx(2.0),
                    "other": pytest.approx(1.0)}
    assert trace_reduce.op_kind("%convert_reduce_fusion.12 = bf16[8] fusion(x)") == \
        "convert_reduce_fusion"
    assert trace_reduce.op_kind("%copy.3 = bf16[8] copy(x)") == "copy"
    assert trace_reduce.op_kind("%reshape.670.remat = bf16[8] reshape(x)") == "reshape"
    assert trace_reduce.op_kind("dot_general.1") == "dot_general"


def _decode_sample_paged(x):
    import jax.numpy as jnp

    return jnp.tanh(x @ x) @ x


def test_reduce_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    tick = jax.jit(_decode_sample_paged)
    x = jnp.ones((384, 384), jnp.float32) * 0.01
    tick(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("engine.step"):
                tick(x).block_until_ready()
            with jax.profiler.TraceAnnotation("harness.wait_arrival"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp_path)
    ticks = {"decode": "_decode_sample", "fused": "_fused_step"}
    # a run on the chip never reads host threads as the device
    with pytest.raises(trace_reduce.TraceError, match="no TPU plane"):
        trace_reduce.reduce(path, modules=ticks)
    red = trace_reduce.reduce(path, modules=ticks, require_tpu=False)
    assert red["window_s"] >= 4 * 0.02
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["module_calls"]["decode"] >= 4 and red["module_calls"]["fused"] == 0
    idle = dict(red["idle_gaps"])
    assert idle["harness.wait_arrival"] >= 4 * 0.02 * 0.9
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert red["device_ops"] and all(t > 0 for _, t in red["device_ops"])
    # each engine.step span holds the tick program it waited for
    assert len(red["step_modules"]) == 4
    assert all(mods and {k for k, _ in mods} == {"decode"} for mods in red["step_modules"])
    with pytest.raises(trace_reduce.TraceError, match="no tick program"):
        trace_reduce.reduce(path, modules={"x": "_no_such_tick"}, require_tpu=False)


def test_missing_trace_is_an_error(tmp_path):
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.find_xplane(tmp_path)
