"""The per-scope and per-phase reduction (``scopes.py``) on a trace built by
hand with a TPU plane, and on a small trace this test records itself (CPU)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402  (puts the program on the path)
import scopes  # noqa: E402
import trace_reduce  # noqa: E402

TICKS = {"fused": "_fused_step", "decode": "_decode_sample"}
PREFIX = "jit(_fused_step_paged)/while/body/closed_call/"


def test_names_are_the_programs():
    from repro.serve import tracing

    assert scopes.SCOPES == tracing.SCOPES
    assert scopes.PHASES == tracing.PHASES
    assert scopes.GC_SPAN == tracing.GC_SPAN
    assert scopes.STEP_SPAN in tracing.RESERVED and scopes.WAIT_SPAN in tracing.PHASES
    assert set(trace_reduce.SPANS) | {trace_reduce.WINDOW_SPAN} == set(tracing.RESERVED)


# --------------------------------------------------- a trace by hand -----
def _xspace(planes: dict, hlo: dict) -> bytes:
    """A serialized XSpace: ``planes`` maps a plane name to its lines, each
    a list of (name, start_ns, end_ns) events; ``hlo`` maps a program's
    name to {instruction: op_name}, kept on the ``/host:metadata`` plane as
    the profiler keeps each program's HLO."""
    msgs = scopes._messages()
    xs = msgs["XSpace"]()
    for pname, lines in planes.items():
        plane = xs.planes.add(name=pname)
        ids: dict[str, int] = {}
        for lname, events in lines.items():
            line = plane.lines.add(name=lname, timestamp_ns=0)
            for name, s, e in events:
                mid = ids.setdefault(name, len(ids) + 1)
                line.events.add(metadata_id=mid, offset_ps=s * 1000, duration_ps=(e - s) * 1000)
        for name, mid in ids.items():
            plane.event_metadata.add(key=mid).value.name = name
    meta = xs.planes.add(name="/host:metadata")
    for key, (prog, table) in enumerate(hlo.items(), 1):
        proto = msgs["HloProto"]()
        comp = proto.hlo_module.computations.add()
        for inst, op_name in table.items():
            comp.instructions.add(name=inst).metadata.op_name = op_name
        entry = meta.event_metadata.add(key=key).value
        entry.name = prog
        entry.stats.add(bytes_value=proto.SerializeToString())
    return xs.SerializeToString()


def _fused_tick(t0: int):
    """One fused tick at ``t0`` ns: host spans, the program call, its ops
    as (event name, start, end, op_name path)."""
    host = [("engine.step", t0, t0 + 300), ("engine.admit", t0, t0 + 30),
            ("engine.dispatch", t0 + 30, t0 + 50), ("engine.device_get", t0 + 50, t0 + 290),
            ("engine.commit", t0 + 290, t0 + 300)]
    mods = [("jit__fused_step_paged(7)", t0 + 60, t0 + 260)]
    ops = [("while.3", t0 + 60, t0 + 200, PREFIX[:-len("closed_call/")] + "while"),
           ("%exp_fusion.1 = f32[8] fusion(x)", t0 + 60, t0 + 80,
            PREFIX + "attn.paged_read/gn.softmax/exp"),
           ("fusion.2", t0 + 80, t0 + 110, PREFIX + "attn.paged_read/dot_general"),
           ("fusion.3", t0 + 110, t0 + 150, PREFIX + "mlp/dot_general"),
           ("add.4", t0 + 150, t0 + 160, PREFIX + "add"),
           ("convert.6", t0 + 160, t0 + 170, None),  # made by the compiler, no metadata
           ("fusion.5", t0 + 200, t0 + 260, PREFIX + "attn.proj/dot_general")]
    return host, mods, ops


def _hand_trace(tmp_path, scoped: bool = True, hlo: bool = True) -> Path:
    host = [("harness.window", 0, 1000), ("harness.wait_arrival", 0, 100),
            ("python.gc", 800, 850)]
    mods, ops = [], []
    for t0 in (100, 500):
        h, m, o = _fused_tick(t0)
        host, mods, ops = host + h, mods + m, ops + o
    host += [("engine.step", 850, 950), ("engine.device_get", 860, 940),
             ("engine.commit", 940, 950)]
    mods += [("jit__decode_sample_paged(8)", 870, 930), ("jit_other(9)", 960, 990)]
    ops += [("fusion.9", 870, 930, "jit(_decode_sample_paged)/while/body/mlp/dot")]
    if not scoped:
        ops = [(n, s, e, p and "jit(f)/while/body/add") for n, s, e, p in ops]
    programs = {"jit__fused_step_paged(7)": {}, "jit__decode_sample_paged(8)": {}}
    for n, _, _, p in ops:
        if p:
            prog = "jit__decode_sample_paged(8)" if "decode" in p else "jit__fused_step_paged(7)"
            programs[prog][n.split(" = ")[0].lstrip("%")] = p
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace({
        "/device:TPU:0": {"XLA Ops": [(n, s, e) for n, s, e, _ in ops], "XLA Modules": mods},
        "/host:CPU": {"python": host},
    }, programs if hlo else {}))
    return path


def test_scope_times_on_a_hand_built_tpu_trace(tmp_path):
    red = scopes.reduce(_hand_trace(tmp_path), TICKS)
    assert red["window_s"] == pytest.approx(1000e-9)
    f, d = red["scopes"]["fused"], red["scopes"]["decode"]
    assert (f["ticks"], d["ticks"]) == (2, 1)
    assert f["device_s"] == pytest.approx(2 * 200e-9) and f["op_s"] == pytest.approx(2 * 170e-9)
    # nested scopes count in their parents; self time is the innermost's
    assert f["inclusive"] == pytest.approx({"attn.paged_read": 100e-9, "gn.softmax": 40e-9,
                                            "mlp": 80e-9, "attn.proj": 120e-9})
    assert f["self"] == pytest.approx({"attn.paged_read": 60e-9, "gn.softmax": 40e-9,
                                       "mlp": 80e-9, "attn.proj": 120e-9})
    assert f["top"] == pytest.approx({"attn.paged_read": 100e-9, "mlp": 80e-9,
                                      "attn.proj": 120e-9})
    # an instruction without metadata, or with no scope on its path, is unscoped
    assert f["unscoped"] == pytest.approx(40e-9)
    assert f["unscoped_kinds"] == pytest.approx({"add": 20e-9, "convert": 20e-9})
    assert sum(f["top"].values()) + f["unscoped"] == pytest.approx(f["op_s"])
    assert d["inclusive"] == pytest.approx({"mlp": 60e-9})
    # host time of a tick: its step span less its wait for the tokens
    ht = red["host_tick_s"]
    assert ht["fused"] == pytest.approx([60e-9, 60e-9]) and ht["decode"] == pytest.approx([20e-9])
    assert sorted(ht["all"]) == pytest.approx([20e-9, 60e-9, 60e-9])
    # gaps today's rule gives to engine.step go to the phase overlapping
    # most, ties to the shorter span; other gaps keep their label
    assert red["idle_gaps"] == pytest.approx({
        "harness.wait_arrival": 160e-9, "engine.admit": 200e-9,
        "python.gc": 110e-9, "engine.commit": 70e-9})


def test_readers_divide_per_tick_and_refuse_a_trace_without_scopes(tmp_path, monkeypatch):
    peaks = {"bf16_flops_per_s": 1.0}
    ctx = {"peaks": peaks}
    for name in ("scoped", "unscoped", "nopath"):
        (tmp_path / name).mkdir()
    _hand_trace(tmp_path / "scoped")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "scoped")
    assert scopes.scope_ms(ctx, "fused", "attn.paged_read") == pytest.approx(100e-9 / 2 * 1e3)
    assert scopes.scope_ms(ctx, "decode", "mlp") == pytest.approx(60e-9 * 1e3)
    assert scopes.scope_ms(ctx, "decode", "gn.softmax") is None
    assert scopes.host_tick_ms(ctx) == pytest.approx(60e-9 * 1e3)
    assert scopes.host_tick_ms(ctx, "decode") == pytest.approx(20e-9 * 1e3)
    # a TPU trace whose tick programs carry no scope, or whose programs'
    # HLO is missing, is an error for a program that names its scopes ...
    for name, kw, match in (("unscoped", {"scoped": False}, "carries a scope"),
                            ("nopath", {"hlo": False}, "op_name path")):
        _hand_trace(tmp_path / name, **kw)
        monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / name)
        with pytest.raises(trace_reduce.TraceError, match=match):
            scopes.scope_ms({"peaks": peaks}, "fused", "mlp")
    # ... and reads nothing for one that predates them
    monkeypatch.setattr(scopes, "program_has_scopes", lambda: False)
    ctx = {"peaks": peaks}
    assert scopes.scope_ms(ctx, "fused", "mlp") is None
    assert scopes.host_tick_ms(ctx) is None


# ------------------------------------------------ a recorded CPU trace ---
def _decode_sample_paged(x):
    import jax.numpy as jnp

    return jnp.tanh(x @ x) @ x


def test_recorded_cpu_trace_keeps_todays_numbers(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.serve import tracing

    tick = jax.jit(_decode_sample_paged)
    x = jnp.ones((384, 384), jnp.float32) * 0.01
    tick(x).block_until_ready()
    seconds: dict = {}
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("engine.step"):
                with tracing.phase("engine.admit", seconds):
                    time.sleep(0.002)
                with tracing.phase("engine.dispatch", seconds):
                    y = tick(x)
                with tracing.phase("engine.device_get", seconds):
                    y.block_until_ready()
                with tracing.phase("engine.commit", seconds):
                    time.sleep(0.002)
            with jax.profiler.TraceAnnotation("harness.wait_arrival"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp_path)
    today = trace_reduce.reduce(path, modules=TICKS, require_tpu=False)
    red = scopes.reduce(path, TICKS, require_tpu=False)
    # the host spans are read the same way, and a CPU trace has no scopes
    assert red["window_s"] == pytest.approx(today["window_s"], rel=1e-9)
    assert red["scopes"] is None
    idle = dict(today["idle_gaps"])
    assert sum(red["idle_gaps"].values()) == pytest.approx(sum(idle.values()), rel=1e-6)
    assert red["idle_gaps"].get("harness.wait_arrival") == pytest.approx(
        idle["harness.wait_arrival"], rel=1e-9)
    # the idle inside a step goes to its phases
    assert red["idle_gaps"]["engine.admit"] > 0
    assert red["idle_gaps"].get("engine.step", 0.0) < 0.1 * idle["engine.step"]
    ht = red["host_tick_s"]
    assert len(ht["all"]) == 4 and len(ht["decode"]) == 4
    assert all(0.004 <= h < 0.5 for h in ht["all"])
    # the phase spans inside each step leave today's pairing of steps and
    # tick programs as it was
    assert len(today["step_modules"]) == 4
    assert all({k for k, _ in mods} == {"decode"} for mods in today["step_modules"])
