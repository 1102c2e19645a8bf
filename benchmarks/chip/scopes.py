"""Per-scope device time, host time per tick and phase-labelled idle, from
the program's own names in a ``--trace 1`` run's profiler trace.

The program names the layers of its paged tick with ``jax.named_scope``
(``SCOPES``) and the host phases of ``ContinuousEngine.step`` with
``TraceAnnotation`` spans (``PHASES``); each pass of Python's collector is a
``python.gc`` span.  The names are the benchmark's own copy of the
program's; a test holds the two equal.

* scopes: every operation of the first chip's ``XLA Ops`` line whose
  midpoint falls inside a call of a tick program in the traced window is
  put down to the scopes that are ``/``-components of its HLO ``op_name``
  path (a scan adds ``while/body/``).  A TPU trace's events carry no such
  path, so it is the instruction's metadata in its program's HLO, which
  the profiler keeps on its ``/host:metadata`` plane under the program's
  name.  A fusion carries the path of its root instruction, so an ``exp``
  fused into the P.V product counts where the product does; an
  instruction the compiler made without metadata (the weight casts it
  hoists out of the layer scan) is unscoped.  Per tick kind: each scope's
  inclusive time (nested scopes count in their parents), its self time
  (innermost scope only), the top-level split (outermost scope only), the
  unscoped remainder, and the tick programs' own device time.  A loop or
  call counts only through the operations of its body.
* host time per tick: each ``engine.step`` span in the window that holds an
  ``engine.device_get`` span, minus that span: the stretch in which the
  synchronous loop keeps the chip waiting on the host.
* idle by phase: each idle gap that today's rule (``trace_reduce``) puts
  down to ``engine.step`` goes to the phase (or ``python.gc``) span that
  overlaps it most, ties to the shorter span; every other gap keeps its
  label.

Off a TPU (the tests' CPU backend) no scope is read.  On a TPU trace,
operations with no path at all, or with paths but no scope in any tick
program, are an error: the readers report nothing only where the program
predates its scopes.
"""
from __future__ import annotations

import bisect
import functools
import importlib.util
import statistics
import sys
from collections import defaultdict
from pathlib import Path

import trace_reduce
from trace_reduce import TraceError

SCOPES = ("attn.proj", "attn.kv_write", "attn.paged_read", "gn.softmax",
          "gn.norm", "mlp", "lm_head", "gn.probe")
PHASES = ("engine.check_tables", "engine.admit", "engine.pool_ensure",
          "engine.upload", "engine.dispatch", "engine.device_get",
          "engine.sentinel_scan", "engine.commit")
GC_SPAN = "python.gc"
STEP_SPAN = "engine.step"
WAIT_SPAN = "engine.device_get"
PROGRAM_MODULE = "repro.serve.tracing"  # where the program keeps its names


# ---------------------------------------------------------------- read ---
@functools.cache
def _messages() -> dict:
    """The ``XSpace`` and ``HloProto`` messages, declared here for the
    fields read below: ``jax.profiler.ProfileData`` does not show the HLO
    the profiler keeps with a trace."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                            package="bench_xspace", syntax="proto3")
    i64, s = F.TYPE_INT64, F.TYPE_STRING
    schema = {  # message: (field, number, scalar type or "Message" or "Message*" for a list)
        "XStat": [("bytes_value", 6, F.TYPE_BYTES)],
        "XEvent": [("metadata_id", 1, i64), ("offset_ps", 2, i64), ("duration_ps", 3, i64)],
        "XLine": [("name", 2, s), ("timestamp_ns", 3, i64), ("events", 4, "XEvent*")],
        "XEventMetadata": [("name", 2, s), ("stats", 5, "XStat*")],
        # a proto map is a list of (key = 1, value = 2) messages on the wire
        "EventMetadataEntry": [("key", 1, i64), ("value", 2, "XEventMetadata")],
        "XPlane": [("name", 2, s), ("lines", 3, "XLine*"),
                   ("event_metadata", 4, "EventMetadataEntry*")],
        "XSpace": [("planes", 1, "XPlane*")],
        "OpMetadata": [("op_name", 2, s)],
        "HloInstructionProto": [("name", 1, s), ("metadata", 7, "OpMetadata")],
        "HloComputationProto": [("instructions", 2, "HloInstructionProto*")],
        "HloModuleProto": [("computations", 3, "HloComputationProto*")],
        "HloProto": [("hlo_module", 1, "HloModuleProto")],
    }
    for name, fields in schema.items():
        m = fd.message_type.add(name=name)
        for fname, num, typ in fields:
            if isinstance(typ, str):
                m.field.add(name=fname, number=num, type=F.TYPE_MESSAGE,
                            type_name=".bench_xspace." + typ.rstrip("*"),
                            label=F.LABEL_REPEATED if typ.endswith("*") else F.LABEL_OPTIONAL)
            else:
                m.field.add(name=fname, number=num, type=typ, label=F.LABEL_OPTIONAL)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(pool.FindMessageTypeByName(f"bench_xspace.{name}"))
            for name in ("XSpace", "HloProto")}


def _events(plane, line):
    """(name, start, end) of a line's events in ns, as ``ProfileData``
    gives them."""
    names = {m.key: m.value.name for m in plane.event_metadata}
    ts = line.timestamp_ns
    for e in line.events:
        s = ts + e.offset_ps // 1000
        yield names.get(e.metadata_id, ""), float(s), float(s + e.duration_ps // 1000)


def _hlo_op_names(xs) -> dict[str, dict[str, str]]:
    """Program name -> instruction name -> ``op_name``, from the HLO of
    each program that the profiler keeps on its ``/host:metadata`` plane."""
    hlo_proto = _messages()["HloProto"]
    out = {}
    for plane in xs.planes:
        if plane.name != "/host:metadata":
            continue
        for m in plane.event_metadata:
            for st in m.value.stats:
                if st.bytes_value:
                    mod = hlo_proto.FromString(st.bytes_value).hlo_module
                    out[m.value.name] = {i.name: i.metadata.op_name for c in mod.computations
                                         for i in c.instructions if i.metadata.op_name}
    return out


def read_trace(path, require_tpu: bool = True) -> dict:
    """The first chip's operations as (start, end, kind, op_name path or
    None) and its program calls as (name, start, end), and the host spans,
    in ns.  Off a TPU (``require_tpu`` False, the tests' CPU backend) the
    XLA:CPU client's events stand for the operations, with no path, as in
    ``trace_reduce.read_planes``."""
    xs = _messages()["XSpace"].FromString(Path(path).read_bytes())
    tpu = sorted((p for p in xs.planes if p.name.startswith("/device:TPU:")
                  and "Core" not in p.name and any(ln.name == "XLA Ops" for ln in p.lines)),
                 key=lambda p: p.name)
    ops, mods, host, cpu_ops = [], [], defaultdict(list), []
    if tpu:
        lines = {ln.name: ln for ln in tpu[0].lines}
        if "XLA Modules" in lines:
            mods = sorted(_events(tpu[0], lines["XLA Modules"]), key=lambda m: m[1])
        hlo, starts = _hlo_op_names(xs), [s for _, s, _ in mods]
        for n, s, e in _events(tpu[0], lines["XLA Ops"]):
            # an instruction's name is unique within its program, whose
            # call holds the operation's midpoint
            i = bisect.bisect_right(starts, (s + e) / 2) - 1
            table = hlo.get(mods[i][0], {}) if i >= 0 else {}
            ops.append((s, e, trace_reduce.op_kind(n),
                        table.get(n.split(" = ", 1)[0].lstrip("%"))))
    for plane in xs.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            cpu = (ln.name.startswith("tf_XLAPjRtCpuClient")
                   or ln.name.startswith("tf_XLAEigen"))
            for n, s, e in _events(plane, ln):
                host[n].append((s, e))
                if cpu and e > s and not n.startswith("ThreadpoolListener"):
                    cpu_ops.append((s, e, trace_reduce.op_kind(n), None))
    if not tpu:
        if require_tpu:
            raise TraceError(f"no TPU plane with XLA Ops in {path}")
        ops = cpu_ops
        mods = [(n[len("PjitFunction("):-1], s, e) for n, ivs in host.items()
                if n.startswith("PjitFunction(") for s, e in ivs]
    return {"ops": sorted(ops, key=lambda o: o[:2]), "modules": sorted(mods, key=lambda m: m[1]),
            "host": dict(host), "tpu": bool(tpu)}


def window(tr: dict) -> tuple[float, float]:
    win = tr["host"].get(trace_reduce.WINDOW_SPAN)
    if win:
        return win[0]
    return tr["ops"][0][0], max(e for _, e, _, _ in tr["ops"])


def tick_calls(tr: dict, modules: dict[str, str], lo: float, hi: float) -> list:
    """(start, end, kind) of every tick program call inside the window."""
    return sorted((s, e, key) for key, frag in modules.items()
                  for n, s, e in tr["modules"] if frag in n and s >= lo and e <= hi)


# -------------------------------------------------------------- scopes ---
def scope_times(tr: dict, calls: list) -> dict | None:
    """Per tick kind: ticks, program and operation seconds, and each
    scope's inclusive, self and top-level seconds and the unscoped
    remainder (summed over the ticks).  None where no operation carries an
    ``op_name`` path."""
    if not any(p is not None for _, _, _, p in tr["ops"]):
        if tr["tpu"]:
            raise TraceError("no operation has an op_name path in its program's HLO")
        return None
    out = {}
    for _, _, kind in calls:
        out.setdefault(kind, {"ticks": 0, "device_s": 0.0, "op_s": 0.0,
                              "inclusive": defaultdict(float), "self": defaultdict(float),
                              "top": defaultdict(float), "unscoped": 0.0,
                              "unscoped_kinds": defaultdict(float)})
    for s, e, kind in calls:
        out[kind]["ticks"] += 1
        out[kind]["device_s"] += (e - s) * 1e-9
    starts = [s for s, _, _ in calls]
    for s, e, op, path in tr["ops"]:
        if op in trace_reduce.CONTAINERS:
            continue
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i < 0 or mid > calls[i][1]:
            continue
        k = out[calls[i][2]]
        dur = (e - s) * 1e-9
        k["op_s"] += dur
        named = [c for c in (path or "").split("/") if c in SCOPES]
        if not named:
            k["unscoped"] += dur
            k["unscoped_kinds"][op] += dur
            continue
        for c in dict.fromkeys(named):
            k["inclusive"][c] += dur
        k["self"][named[-1]] += dur
        k["top"][named[0]] += dur
    if calls and not any(k["inclusive"] for k in out.values()):
        raise TraceError("no operation of a tick program carries a scope "
                         f"of {SCOPES} in its op_name path")
    return {kind: {**k, **{f: dict(k[f]) for f in
                           ("inclusive", "self", "top", "unscoped_kinds")}}
            for kind, k in out.items()}


# ---------------------------------------------------------------- host ---
def host_ticks(tr: dict, calls: list, lo: float, hi: float) -> dict | None:
    """Host seconds of every tick in the window, all together and per tick
    kind (the kind of the tick program inside its ``engine.step`` span).
    None where the program writes no ``engine.device_get`` span."""
    waits = sorted(tr["host"].get(WAIT_SPAN, []))
    if not waits:
        return None
    wstarts = [s for s, _ in waits]
    mids = [((s + e) / 2, kind) for s, e, kind in calls]
    mid_t = [m for m, _ in mids]
    out: dict[str, list] = {"all": []}
    for s, e in sorted(tr["host"].get(STEP_SPAN, [])):
        if s < lo or e > hi:
            continue
        inside = waits[bisect.bisect_left(wstarts, s):bisect.bisect_right(wstarts, e)]
        inside = [(a, b) for a, b in inside if b <= e]
        if not inside:  # a step call that ran no tick
            continue
        host_s = ((e - s) - sum(b - a for a, b in inside)) * 1e-9
        out["all"].append(host_s)
        kinds = {k for _, k in mids[bisect.bisect_left(mid_t, s):bisect.bisect_right(mid_t, e)]}
        if len(kinds) == 1:
            out.setdefault(kinds.pop(), []).append(host_s)
    return out


def label_idle(gap_list, host: dict) -> dict[str, float]:
    """Idle seconds per label: today's label, except that a gap today's
    rule gives to ``engine.step`` goes to the phase or ``python.gc`` span
    that overlaps it most (ties to the shorter span)."""
    today = {n: host.get(n, []) for n in trace_reduce.SPANS}
    named = sorted((s, e, n) for n in PHASES + (GC_SPAN,) for s, e in host.get(n, []))
    starts = [s for s, _, _ in named]
    out: dict[str, float] = defaultdict(float)
    for gs, ge in gap_list:
        (label, secs), = trace_reduce.label_gaps([(gs, ge)], today).items()
        if label == STEP_SPAN:
            best = (0.0, 0.0, label)
            i = bisect.bisect_left(starts, ge)
            for s, e, n in named[max(0, i - 64):i]:
                ov = min(e, ge) - max(s, gs)
                if ov > 0 and (ov, -(e - s)) > best[:2]:
                    best = (ov, -(e - s), n)
            label = best[2]
        out[label] += secs
    return dict(out)


def reduce(path, modules: dict[str, str], require_tpu: bool = True) -> dict:
    tr = read_trace(path, require_tpu)
    if not tr["ops"]:
        raise TraceError(f"no device operations in {path}")
    lo, hi = window(tr)
    calls = tick_calls(tr, modules, lo, hi)
    busy = trace_reduce.union(trace_reduce.clip([(s, e) for s, e, _, _ in tr["ops"]], lo, hi))
    return {"window_s": (hi - lo) * 1e-9,
            "scopes": scope_times(tr, calls),
            "host_tick_s": host_ticks(tr, calls, lo, hi),
            "idle_gaps": label_idle(trace_reduce.gaps(busy, lo, hi), tr["host"])}


# ------------------------------------------------------------- readers ---
def program_has_scopes() -> bool:
    return importlib.util.find_spec(PROGRAM_MODULE) is not None


def for_run(ctx: dict) -> dict:
    """The reduction of the run's trace, read once per run (kept in the
    run's context) however many metrics ask.  A program that names its
    scopes and has a trace where no scope can be read is an error; one
    that predates its scopes reads nothing."""
    if "scope_reduction" not in ctx:
        import harness

        path = trace_reduce.find_xplane(harness.TRACE_DIR)
        try:
            red = reduce(path, harness.TICK_MODULES, require_tpu=ctx["peaks"] is not None)
            log(red)
        except TraceError:
            if program_has_scopes():
                raise
            red = {}
        ctx["scope_reduction"] = red
    return ctx["scope_reduction"]


def log(red: dict, out=sys.stderr) -> None:
    """Every scope's inclusive and self ms per tick, the unscoped remainder
    and its heaviest op kinds, host ms per tick, and idle by phase."""
    for kind, k in sorted((red["scopes"] or {}).items()):
        n = max(k["ticks"], 1)
        ms = lambda s: f"{1e3 * s / n:.4f}"  # noqa: E731
        cover = (sum(k["top"].values()) + k["unscoped"]) / k["device_s"] if k["device_s"] else 0
        print(f"scopes {kind}: {k['ticks']} ticks, program {ms(k['device_s'])} ms/tick, "
              f"ops {ms(k['op_s'])}; top-level + unscoped = {100 * cover:.2f}% of program",
              file=out)
        print(f"scopes {kind} inclusive/self ms per tick: " + ", ".join(
            f"{s} {ms(k['inclusive'].get(s, 0.0))}/{ms(k['self'].get(s, 0.0))}"
            for s in SCOPES), file=out)
        heavy = sorted(k["unscoped_kinds"].items(), key=lambda x: -x[1])[:6]
        print(f"scopes {kind} unscoped {ms(k['unscoped'])} ms per tick: " + ", ".join(
            f"{op} {ms(t)}" for op, t in heavy), file=out)
    for kind, vals in sorted((red["host_tick_s"] or {}).items()):
        if vals:
            print(f"host tick {kind}: {len(vals)} ticks, median "
                  f"{1e3 * statistics.median(vals):.4f} ms, max {1e3 * max(vals):.4f} ms",
                  file=out)
    idle = sorted(red["idle_gaps"].items(), key=lambda x: -x[1])
    print("idle by phase s: " + ", ".join(f"{n} {t:.6f}" for n, t in idle), file=out)
    out.flush()


def scope_ms(ctx: dict, kind: str, scope: str):
    """Mean device ms per ``kind`` tick inside ``scope``, inclusive."""
    k = (for_run(ctx).get("scopes") or {}).get(kind)
    if not k or not k["ticks"] or scope not in k["inclusive"]:
        return None
    return 1e3 * k["inclusive"][scope] / k["ticks"]


def host_tick_ms(ctx: dict, kind: str = "all"):
    """Median host ms per tick."""
    vals = (for_run(ctx).get("host_tick_s") or {}).get(kind)
    return 1e3 * statistics.median(vals) if vals else None
