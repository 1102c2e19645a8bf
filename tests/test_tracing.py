"""Device scopes and host phase spans of the paged serving tick.

The scopes (``jax.named_scope``) name the layers of the tick in the HLO
``op_name`` metadata; the phases (``serve/tracing.phase``) are profiler
spans inside ``ContinuousEngine.step`` whose wall times also add up in
``metrics()["host_phase_s"]``.
"""
import re
import time

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config, reduce_config
from repro.models.transformer import make_model
from repro.serve import tracing
from repro.serve.engine import ContinuousEngine
from repro.serve.scheduler import Request


@pytest.fixture(scope="module")
def dense():
    cfg = reduce_config(get_config("internlm2-1.8b"))
    model = make_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests():
    return [Request(id=i, tokens=(np.arange(n, dtype=np.int32) * 7 + i) % 97,
                    max_new_tokens=3)
            for i, n in enumerate((9, 5))]


def _engine(dense, **kw):
    model, params = dense
    return ContinuousEngine(model, params, num_slots=2, max_seq=32, chunk=4,
                            block_size=4, **kw)


def _tick_op_paths(dense, sentinels: bool) -> dict[str, set]:
    """The ``op_name`` paths of the lowered fused and decode ticks."""
    engine = _engine(dense, sentinels=sentinels)
    ticks = {"fused": engine._fused, "decode": engine._decode}
    for jit in ticks.values():
        jit.capture_avals = {}
    engine.run(_requests())
    out = {}
    for kind, jit in ticks.items():
        avals = next(iter(jit.capture_avals.values()))
        text = jit.jitted.lower(*avals).as_text(debug_info=True)
        out[kind] = set(re.findall(r'loc\("([^"]*/[^"]*)"', text))
    return out


def _components(path: str) -> list[str]:
    return path.split("/")


def test_scope_and_span_names_are_the_callers_own():
    names = set(tracing.SCOPES) | set(tracing.PHASES) | {tracing.GC_SPAN}
    assert len(names) == len(tracing.SCOPES) + len(tracing.PHASES) + 1
    assert not names & set(tracing.RESERVED)
    assert not any(n.startswith("harness.") for n in names)


@pytest.mark.parametrize("sentinels", [True, False], ids=["sentinels", "no_sentinels"])
def test_ticks_carry_every_scope(dense, sentinels):
    paths = _tick_op_paths(dense, sentinels)
    for kind, ops in paths.items():
        seen = {c for p in ops for c in _components(p)}
        want = set(tracing.SCOPES) - ({tracing.GN_PROBE} if not sentinels else set())
        assert want <= seen, (kind, sorted(want - seen))
        # the sentinel probes exist only where the engine asks for them
        assert (tracing.GN_PROBE in seen) == sentinels, kind
        # the GN softmax is part of the paged read, never beside it
        soft = [p for p in ops if tracing.GN_SOFTMAX in _components(p)]
        assert soft
        for p in soft:
            comps = _components(p)
            assert tracing.PAGED_READ in comps[:comps.index(tracing.GN_SOFTMAX)], p


def _host_spans(path) -> dict[str, list]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans: dict[str, list] = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                spans.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    return spans


def test_phase_spans_sit_inside_the_callers_step_span(dense, tmp_path):
    import gc

    engine = _engine(dense)
    engine.run(_requests())  # compile outside the trace
    engine.reset()
    for r in _requests():
        engine.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    steps = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        while True:
            with jax.profiler.TraceAnnotation("engine.step"):
                worked = engine.step()
            steps += 1
            if not worked:
                break
        gc.collect()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    spans = _host_spans(path)
    outer = sorted(spans["engine.step"])
    # the engine writes no span of the caller's names
    assert len(outer) == steps
    assert not [n for n in spans if n.startswith("harness.")]
    for name in tracing.PHASES:
        assert spans.get(name), name
        for s, e in spans[name]:
            assert any(os <= s and e <= oe for os, oe in outer), name
    assert spans.get(tracing.GC_SPAN)


def test_host_phase_seconds_add_up_and_reset(dense):
    engine = _engine(dense)
    for r in _requests():
        engine.submit(r)
    wall = 0.0
    while True:
        t0 = time.perf_counter()
        worked = engine.step()
        wall += time.perf_counter() - t0
        if not worked:
            break
    phases = engine.metrics()["host_phase_s"]
    assert set(phases) == set(tracing.PHASES)
    assert all(v >= 0.0 for v in phases.values())
    assert phases["engine.dispatch"] > 0 and phases["engine.device_get"] > 0
    assert sum(phases.values()) <= wall
    engine.reset()
    assert engine.metrics()["host_phase_s"] == dict.fromkeys(tracing.PHASES, 0.0)

