"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb`` file; ``jax.profiler.ProfileData``
reads it.  A TPU's plane (``/device:TPU:<n>``) carries a line of XLA
operations (``XLA Ops``) and one of whole programs (``XLA Modules``).  The
host plane (``/host:CPU``) carries the harness's own spans, written with
``jax.profiler.TraceAnnotation``: ``harness.window`` around the traced
window, and ``engine.step``, ``harness.submit`` and
``harness.wait_arrival`` inside it.

* busy: the union of the operation intervals of each chip, clipped to the
  window; ``busy_s`` is its mean over the chips and the idle share is
  1 - busy_s / window_s.
* modules: the device time of each call of a program whose name contains a
  given fragment (the engine's ticks: ``_fused_step``, ``_decode_sample``).
* idle gaps: the stretches of the window in which no operation ran on the
  first chip, each put down to the harness span that overlaps it most
  (``other`` where none does), summed per span.
* step modules: for each ``engine.step`` span in the window, in order, the
  tick programs whose midpoint falls inside it (a tick runs while the host
  waits for its tokens inside the span).
* device ops: device time per chip by kind of operation (the HLO
  instruction's name without its number: ``convert``, ``copy``,
  ``convert_reduce_fusion``, ...); a loop or call counts only through the
  operations of its body.

A run on the chip reads TPU planes only: a trace without one is an error.
Only where the caller says no TPU is required (the tests' CPU backend) do
the events of the XLA:CPU client's executor threads stand for the
operations, and a program's ``PjitFunction(<name>)`` spans on the host for
its calls.
"""
from __future__ import annotations

import bisect
import shutil
from collections import defaultdict
from pathlib import Path

SPANS = ("engine.step", "harness.submit", "harness.wait_arrival")
CONTAINERS = ("while", "conditional", "call")  # ops whose time is their body's
WINDOW_SPAN = "harness.window"


class TraceError(RuntimeError):
    pass


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise TraceError(f"no .xplane.pb under {directory}")
    return found[-1]


def remove(directory) -> None:
    shutil.rmtree(directory, ignore_errors=True)


# ----------------------------------------------------------- intervals ---
def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi) that merged ``busy`` leaves uncovered."""
    out, cur = [], lo
    for s, e in clip(busy, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def label_gaps(gap_list, spans: dict) -> dict[str, float]:
    """Seconds of idle per span name: each gap goes to the span that
    overlaps it most.  ``spans`` maps a name to its (start, end) list."""
    flat = sorted((s, e, n) for n, ivs in spans.items() for s, e in ivs)
    starts = [s for s, _, _ in flat]
    out: dict[str, float] = defaultdict(float)
    for gs, ge in gap_list:
        best, name = 0.0, "other"
        i = bisect.bisect_left(starts, ge)
        for s, e, n in flat[max(0, i - 64):i]:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, name = ov, n
        out[name] += (ge - gs) * 1e-9
    return dict(out)


# ---------------------------------------------------------------- read ---
def op_kind(name: str) -> str:
    """``%convert_reduce_fusion.12 = bf16[...] ...`` -> ``convert_reduce_fusion``:
    the HLO instruction's name without its instance number or suffixes."""
    short = name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") else name
    return short.split(".", 1)[0]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)) for e in line.events]


def read_planes(path, require_tpu: bool = True) -> dict:
    """Ops and module calls per chip, and the host spans, in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, host = [], defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if "XLA Ops" in lines:
                chips.append({"name": plane.name, "ops": lines["XLA Ops"],
                              "modules": lines.get("XLA Modules", [])})
        elif plane.name == "/host:CPU":
            cpu_ops = []
            for ln in plane.lines:
                evs = _events(ln)
                if ln.name.startswith("tf_XLAPjRtCpuClient") or ln.name.startswith("tf_XLAEigen"):
                    cpu_ops += [e for e in evs if e[2] > e[1]
                                and not e[0].startswith("ThreadpoolListener")]
                for name, s, e in evs:
                    host[name].append((s, e))
            host["__cpu_ops__"] = cpu_ops
    cpu_ops = host.pop("__cpu_ops__", [])
    if not chips and cpu_ops and not require_tpu:
        mods = [(n[len("PjitFunction("):-1], s, e) for n, ivs in host.items()
                if n.startswith("PjitFunction(") for s, e in ivs]
        chips.append({"name": "/host:CPU", "ops": cpu_ops, "modules": mods})
    chips.sort(key=lambda c: c["name"])
    return {"chips": chips, "host": dict(host)}


def reduce(path, modules: dict[str, str], chips: int = 1, require_tpu: bool = True) -> dict:
    """The trace's numbers; ``modules`` maps a short name to the fragment
    its program's name contains."""
    planes = read_planes(path, require_tpu)
    devs = planes["chips"][:chips]
    if not devs:
        where = "no TPU plane with XLA Ops" if require_tpu else "no device operations"
        raise TraceError(f"{where} in {path}")
    if len(devs) < chips:
        raise TraceError(f"{len(devs)} device planes in {path}, the run used {chips}")
    win = planes["host"].get(WINDOW_SPAN)
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for d in devs for _, s, _ in d["ops"])
        hi = max(e for d in devs for _, _, e in d["ops"])
    window_ns = hi - lo
    busy_each, op_time = [], defaultdict(float)
    for d in devs:
        busy = union(clip([(s, e) for _, s, e in d["ops"]], lo, hi))
        busy_each.append(busy)
        for n, s, e in d["ops"]:
            ov = min(e, hi) - max(s, lo)
            kind = op_kind(n)
            if ov > 0 and kind not in CONTAINERS:
                op_time[kind] += ov * 1e-9 / len(devs)
    busy_ns = sum(sum(e - s for s, e in b) for b in busy_each) / len(devs)
    calls = {}
    for key, frag in modules.items():
        calls[key] = [(e - s) * 1e-9 for n, s, e in devs[0]["modules"]
                      if frag in n and s >= lo and e <= hi]
    if not any(calls.values()):
        names = sorted({n for n, _, _ in devs[0]["modules"]})[:20]
        raise TraceError(f"no tick program ({modules}) in the traced window; programs: {names}")
    spans = {n: planes["host"].get(n, []) for n in SPANS}
    idle = label_gaps(gaps(busy_each[0], lo, hi), spans)
    steps = sorted((s, e) for s, e in spans["engine.step"] if s >= lo and e <= hi)
    mods = sorted(((s + e) / 2, key, (e - s) * 1e-9) for key, frag in modules.items()
                  for n, s, e in devs[0]["modules"] if frag in n)
    mids = [m for m, _, _ in mods]
    step_modules = [[(key, dur) for _, key, dur in
                     mods[bisect.bisect_left(mids, s):bisect.bisect_right(mids, e)]]
                    for s, e in steps]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "modules": calls,
        "module_calls": {k: len(v) for k, v in calls.items()},
        "step_modules": step_modules,
        "device_ops": sorted(([n, t] for n, t in op_time.items()), key=lambda x: -x[1]),
        "idle_gaps": sorted(([n, t] for n, t in idle.items()), key=lambda x: -x[1]),
    }
