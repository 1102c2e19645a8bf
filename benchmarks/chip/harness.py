"""The on-chip serving benchmark: one cell, one run.

A run finds the chips, makes the weights from the seed, builds the
program's ``ContinuousEngine`` with the cell's engine settings, serves one
request per horizon bucket of both tick kinds so that every tick shape the
traffic can reach is compiled, resets the engine, and starts the traffic.
The traffic's lead-in belongs to set-up, so the window opens with the
engine busy.  The window drives ``engine.submit`` and ``engine.step`` for
``seconds``; after each tick the harness records what every request did in
it (a prompt chunk written, a token emitted), read from the engine's slots
and its finished requests.  A request due in the window without a first
token at the close counts at its age then.  The device memory peak is read,
the engine is freed, and a sample of the finished requests, the longest
among them, is compared with the plain reference (``reference.py``).

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by its name: ``configs/<config>.json``,
``traffic/<mix>.json``, ``cells/<cell>.json`` (engine settings) and
``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import itertools
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(HERE), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402
import work  # noqa: E402

TRACE_SECONDS = 10.0     # the traced part of a --trace 1 window
TRACE_DIR = HERE / ".out" / "trace"
MIN_SAMPLE = 4          # requests compared with the reference, at least
TICK_MODULES = {"fused": "_fused_step", "decode": "_decode_sample"}


class BenchError(RuntimeError):
    """The run cannot be measured: no result is printed."""


# -------------------------------------------------------------- lookup ---
def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def resolve_cell(bench: dict, name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfile = Path(root) / cfgs[w["config"]]["file"]
    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": load_json(cfile),
        "mix": traffic_gen.load_mix(w["traffic"], Path(root) / "benchmarks/chip/traffic"),
        "engine": load_json(Path(root) / "benchmarks/chip/cells" / f"{name}.json")["engine"],
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def metric_reader(name: str, directory: Path = HERE / "metrics"):
    path = Path(directory) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or not path.is_file():
        raise BenchError(f"no reader for per-layer metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- devices ---
def find_devices(chips: int, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r}; have {sorted(table)}")
    return table[kind]


# --------------------------------------------------------------- model ---
WIDTHS = {  # configuration key -> the program's ModelConfig field
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
    "rope_theta": "rope_theta",
}
MLA_WIDTHS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim")


def build_model(config: dict):
    """The program's model for the configuration file, checked against it."""
    from repro.configs.registry import get_config
    from repro.models.transformer import make_model

    prog = config["program"]
    mcfg = dataclasses.replace(get_config(prog["arch"]), **prog.get("overrides", {}))
    wrong = {k: (config[k], getattr(mcfg, f)) for k, f in WIDTHS.items()
             if float(config[k]) != float(getattr(mcfg, f))}
    if "kv_lora_rank" in config:
        wrong.update({k: (config[k], getattr(mcfg.mla, k)) for k in MLA_WIDTHS
                      if int(config[k]) != int(getattr(mcfg.mla, k))})
    elif mcfg.head_dim * mcfg.n_heads != mcfg.d_model:
        wrong["head_dim"] = (mcfg.d_model // mcfg.n_heads, mcfg.head_dim)
    checks = {"dtype": mcfg.dtype, "param_dtype": mcfg.param_dtype}
    wrong.update({k: (config[k], v) for k, v in checks.items() if config[k] != v})
    if mcfg.softmax_impl != "gn" or mcfg.norm_impl != "gn_rms":
        wrong["gn"] = ("gn/gn_rms", f"{mcfg.softmax_impl}/{mcfg.norm_impl}")
    if wrong:
        raise BenchError(f"program config departs from the file (file, program): {wrong}")
    return mcfg, make_model(mcfg)


def make_engine(model, params, eng: dict):
    from repro.serve.engine import ContinuousEngine

    return ContinuousEngine(
        model, params, num_slots=eng["num_slots"], max_seq=eng["max_seq"],
        chunk=eng["chunk"], block_size=eng["block_size"],
        num_blocks=eng.get("num_blocks", 0), devices=eng.get("devices", 1))


def warm_requests(engine, eng: dict, max_footprint: int) -> list:
    """One request per horizon bucket the traffic can reach: its prompt
    just crosses into the bucket, so its prefill ticks pass through every
    smaller fused bucket and its two decode ticks run at that bucket."""
    from repro.serve.scheduler import Request

    bs = eng["block_size"]
    top = -(-max_footprint // bs)
    grid = [b for b in engine.horizon_bucket_grid]
    reach = [b for b in grid if b < top] + [next(b for b in grid if b >= top)]
    reqs, prev = [], 0
    for b in reach:
        plen = prev * bs + 1
        reqs.append(Request(tokens=np.arange(plen, dtype=np.int32) % 97,
                            max_new_tokens=2, temperature=0.0))
        prev = b
    return reqs


# --------------------------------------------------------------- drive ---
@dataclasses.dataclass
class Sent:
    req: traffic_gen.Req
    due: float        # host clock, seconds
    sent: float       # host clock at submit


@dataclasses.dataclass
class Tick:
    """One tick the engine ran: its host clock, its lanes by kind (the
    engine's ``phase_log``) and each slot's (tokens, context start)."""

    start: float
    end: float
    prefill: int
    decode: int
    lanes: list


class Recorder:
    """What every tick did, on the host clock, and the compile events of
    the window: any trace, lowering or compilation JAX reports while
    ``counting``.  Each request's admission tick and the ticks that emitted
    its tokens are observed after the tick, not inferred from a model of
    the scheduler."""

    def __init__(self):
        self.ticks: dict[int, Tick] = {}
        self.admit: dict[int, int] = {}           # request id -> tick of admission
        self.emitted: dict[int, list[int]] = {}   # request id -> ticks of its tokens
        self._seen: dict[int, tuple[int, int]] = {}  # id -> (written, generated)
        self._done = 0
        self.compile_events = 0
        self.counting = False
        self.trace_calls = None  # (tick, whether a tick ran) per step call traced
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.counting and event.startswith("/jax/core/compile/"):
            self.compile_events += 1

    def step(self, engine) -> bool:
        k, n = engine.step_count, len(engine.phase_log)
        start = time.time()
        with jax.profiler.TraceAnnotation("engine.step"):
            worked = engine.step()
        end = time.time()
        ran = len(engine.phase_log) > n
        if ran:
            self._observe(engine, k, start, end)
        if self.trace_calls is not None:
            self.trace_calls.append((k, ran))
        return worked

    def _observe(self, engine, k: int, start: float, end: float) -> None:
        """Tick ``k``'s lanes and tokens: each live slot's prompt tokens
        written and tokens generated, against the previous tick's, and the
        same for the requests that finished in it."""
        now = {}
        for st in engine._slots:
            if st is not None:
                now[st.req.id] = (st.req.prompt_len, st.written, len(st.generated))
        live = {rid: (w, g) for rid, (_, w, g) in now.items()}
        comps = engine.completions
        for c in comps[self._done:]:
            if c.admit_step >= 0:
                p = len(c.prompt_tokens)
                now[c.request_id] = (p, p, len(c.new_tokens))
        self._done = len(comps)
        lanes = []
        for rid, (plen, w, g) in now.items():
            pw, pg = self._seen.get(rid, (0, 0))
            self.admit.setdefault(rid, k)
            if w > pw:
                lanes.append((w - pw, pw))
            if g > pg:
                lanes.append((1, plen + pg))
                self.emitted.setdefault(rid, []).append(k)
        self._seen = live
        pf, dc = engine.phase_log[-1]
        self.ticks[k] = Tick(start, end, pf, dc, lanes)


def _submit(engine, r: traffic_gen.Req, due: float, sent: dict):
    from repro.serve.scheduler import Request

    rid = engine.submit(Request(tokens=r.tokens, max_new_tokens=r.max_new,
                                arrival_step=engine.step_count, temperature=0.0))
    sent[rid] = Sent(r, due, time.time())


def drive(engine, cell: dict, seed: int, seconds: float, rec: Recorder, *,
          rate=None, trace_dir=None):
    """Lead-in and window.  Returns the run's host-side record."""
    mix = cell["mix"]
    lead = float(mix["lead_in_s"])
    vocab = int(cell["config"]["vocab_size"])
    sent: dict[int, Sent] = {}
    gen_mark = {}
    trace_ticks = run_calls = None
    if mix["kind"] == "open_loop":
        reqs = traffic_gen.open_loop_requests(mix, vocab, seed, lead, seconds, rate)
        queue = iter(reqs)
        nxt = next(queue, None)
    else:
        clients = int(mix["clients"])
        queue = traffic_gen.closed_loop_stream(
            mix, vocab, seed, clients * int(mix["queue_per_client"]))
        first = list(itertools.islice(queue, clients * int(mix["queue_per_client"])))
        queue = itertools.chain(first, queue)
    t0 = time.time()
    t_open, t_end = t0 + lead, t0 + lead + seconds
    if mix["kind"] == "closed_loop":
        for _ in range(clients):
            _submit(engine, next(queue), t0, sent)
    done = 0
    opened = tracing = False
    while True:
        now = time.time()
        if not opened and now >= t_open:
            opened, t_open = True, now
            gen_mark["open"] = engine.metrics()["generated_tokens"]
            gen_mark["open_tick"] = len(engine.phase_log)
            rec.counting = True
        # the last TRACE_SECONDS of the window are traced; writing the trace
        # out blocks for seconds, so it happens after the close
        if (trace_dir is not None and opened and not tracing
                and now >= t_end - min(TRACE_SECONDS, seconds)):
            jax.profiler.start_trace(str(trace_dir))
            tracing = True
            trace_ticks = [engine.step_count, None]
            rec.trace_calls = []
            trace_span = jax.profiler.TraceAnnotation("harness.window")
            trace_span.__enter__()
        if opened and now >= t_end:
            break
        if mix["kind"] == "open_loop":
            with jax.profiler.TraceAnnotation("harness.submit"):
                while nxt is not None and t0 + nxt.due_s <= now:
                    _submit(engine, nxt, t0 + nxt.due_s, sent)
                    nxt = next(queue, None)
            if not rec.step(engine):
                wake = min(t_end if opened else t_open,
                           t0 + nxt.due_s if nxt is not None else t_end)
                with jax.profiler.TraceAnnotation("harness.wait_arrival"):
                    time.sleep(max(0.0, wake - time.time()))
        else:
            rec.step(engine)
            with jax.profiler.TraceAnnotation("harness.submit"):
                while done < len(engine.completions):
                    done += 1
                    _submit(engine, next(queue), time.time(), sent)
    t_close = time.time()
    if tracing:
        trace_span.__exit__(None, None, None)
        trace_ticks[1] = engine.step_count
        run_calls, rec.trace_calls = rec.trace_calls, None
    rec.counting = False
    m = engine.metrics()
    gen_mark["close"] = m["generated_tokens"]
    gen_mark["close_tick"] = len(engine.phase_log)
    if tracing:
        jax.profiler.stop_trace()
    return {
        "t0": t0, "t_open": t_open, "t_close": t_close, "sent": sent,
        "gen": gen_mark, "trace_ticks": trace_ticks,
        "trace_calls": run_calls if tracing else None,
    }


# ------------------------------------------------------------- metrics ---
def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(run: dict, rec: Recorder) -> tuple[dict, dict]:
    """Throughput over the window; TTFT, TPOT and queue wait from the
    recorded ticks.  TTFT and queue wait are over every request due in the
    window, one not yet served (admitted) at the close counting at its age
    then; TPOT is over the requests with two or more tokens inside it."""
    t_open, t_close = run["t_open"], run["t_close"]
    out = {"throughput_tok_s": (run["gen"]["close"] - run["gen"]["open"]) / (t_close - t_open)}
    ttft, tpot, qwait = [], [], []
    for rid, s in run["sent"].items():
        ks = rec.emitted.get(rid, [])
        if t_open <= s.due < t_close:
            first = rec.ticks[ks[0]].end if ks else math.inf
            ttft.append(min(first, t_close) - s.due)
            adm = rec.ticks[rec.admit[rid]].start if rid in rec.admit else math.inf
            qwait.append(min(adm, t_close) - s.due)
        times = [t for t in (rec.ticks[k].end for k in ks) if t_open <= t <= t_close]
        if len(times) >= 2:
            tpot.append((times[-1] - times[0]) / (len(times) - 1))
    for name, vals in (("ttft", ttft), ("tpot", tpot)):
        if vals:
            out[f"{name}_p50_ms"] = pct(vals, 50) * 1e3
            out[f"{name}_p95_ms"] = pct(vals, 95) * 1e3
    backlog = sum(1 for rid, s in run["sent"].items() if s.due < t_close and (
        rid not in rec.admit or rec.ticks[rec.admit[rid]].start > t_close))
    extra = {"requests_due": len(ttft), "queue_wait_ms": [q * 1e3 for q in qwait],
             "tpot_requests": len(tpot), "backlog_at_close": backlog}
    return out, extra


def traced_ticks(run: dict, rec: Recorder, config: dict, step_modules) -> list[dict]:
    """Kind, least work and device time of every tick inside the traced
    window: each slot's (tokens, context start) as recorded, and the tick
    program the trace shows inside the tick's own ``engine.step`` span
    (None where the trace holds no such program)."""
    k0, k1 = run["trace_ticks"]
    calls = run["trace_calls"]
    device_s = {}
    if len(calls) == len(step_modules):
        for (k, worked), mods in zip(calls, step_modules):
            if worked and len(mods) == 1:
                device_s[k] = mods[0]
    out = []
    for k in range(k0, k1):
        tick = rec.ticks.get(k)
        if tick is None:  # a step call that ran no tick
            continue
        kind = "fused" if tick.prefill else "decode"
        flops, nbytes = work.tick_work(config, tick.lanes)
        mod = device_s.get(k)
        out.append({"kind": kind, "flops": flops, "bytes": nbytes,
                    "complete": len(tick.lanes) == tick.prefill + tick.decode,
                    "device_s": mod[1] if mod is not None and mod[0] == kind else None})
    return out


def per_layer(cell: dict, ctx: dict) -> dict:
    out = {}
    for m in cell["per_layer"]:
        val = metric_reader(m["name"])(ctx)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


# --------------------------------------------------------- correctness ---
def sample_completions(comps, seed: int, want_tokens: int) -> list:
    """The longest finished request, then others drawn from the seed, until
    ``want_tokens`` served tokens and ``MIN_SAMPLE`` requests are covered."""
    done = [c for c in comps if c.finish_reason in ("length", "stop") and len(c.new_tokens)]
    if not done:
        return []
    done.sort(key=lambda c: c.request_id)
    longest = max(done, key=lambda c: (len(c.prompt_tokens) + len(c.new_tokens), -c.request_id))
    rng = np.random.default_rng(seed)
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not longest]
    pick, n = [longest], len(longest.new_tokens)
    for c in rest:
        if n >= want_tokens and len(pick) >= MIN_SAMPLE:
            break
        pick.append(c)
        n += len(c.new_tokens)
    return pick


def compare(config: dict, weights, picks, *, lowp=False) -> dict:
    gaps = [reference.served_gaps(config, weights, c.prompt_tokens, c.new_tokens,
                                  lowp=lowp) for c in picks]
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max_logit_gap": float(allg.max()) if allg.size else None,
            "mean_logit_gap": float(allg.mean()) if allg.size else None,
            "tokens": int(allg.size), "requests": len(picks)}


# ----------------------------------------------------------------- run ---
@dataclasses.dataclass
class Setup:
    """What a run builds before its traffic starts."""

    devs: list
    require_tpu: bool
    peaks: dict | None
    struct: object
    weights: object
    engine: object
    warm: dict


def build(cell: dict, seed: int, *, require_tpu: bool = True, log=print) -> Setup:
    """Chips, weights from the seed, the engine, and every tick shape the
    traffic can reach compiled."""
    devs = find_devices(cell["chips"], require_tpu)
    kind = devs[0].device_kind
    peaks = peaks_for(kind) if require_tpu else None
    log(f"device platform={devs[0].platform} device_kind={kind} count={len(devs)}")
    config, eng, mix = cell["config"], cell["engine"], cell["mix"]
    if eng.get("devices", 1) != cell["chips"]:
        raise BenchError(f"engine devices {eng.get('devices', 1)} != cell chips {cell['chips']}")
    pmax, omax = traffic_gen.length_bounds(mix)
    if pmax + omax > eng["max_seq"]:
        raise BenchError(f"mix needs {pmax + omax} tokens, engine max_seq is {eng['max_seq']}")
    mcfg, model = build_model(config)
    struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    weights = jax.block_until_ready(
        reference.make_weights(struct, seed, jnp.dtype(mcfg.param_dtype)))
    engine = make_engine(model, weights, eng)
    for r in warm_requests(engine, eng, pmax + omax):
        engine.run([r])
    warm = engine.metrics()
    engine.reset()
    return Setup(devs, require_tpu, peaks, struct, weights, engine, warm)


def serve(st: Setup, cell: dict, seed: int, seconds: float, trace: bool, *,
          rate=None, t_start: float, log=print) -> tuple[dict, list, Recorder]:
    """Lead-in and window on a built engine; the result object
    without ``correct`` and its checks, and the finished requests."""
    config, eng = cell["config"], cell["engine"]
    engine, devs = st.engine, st.devs
    rec = Recorder()
    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR
        trace_reduce.remove(trace_dir)
    run = drive(engine, cell, seed, seconds, rec, rate=rate, trace_dir=trace_dir)
    setup_s = run["t_open"] - t_start
    m = engine.metrics()
    sent = run["sent"]
    comps = list(engine.completions)
    # in flight at the close is not failed: only a request the engine gave
    # up on (a sentinel's retry budget spent) or shed is
    failed = sum(1 for c in comps if c.finish_reason in ("failed", "rejected"))
    late = [s.sent - s.due for s in sent.values() if s.sent >= run["t_open"]]
    due_in = sum(1 for s in sent.values() if run["t_open"] <= s.due < run["t_close"])
    log(f"compiles in window: {rec.compile_events}; warm-up "
        f"compiles fused {st.warm['fused_step_compilations']} decode "
        f"{st.warm['decode_compilations']}; after the run fused "
        f"{m['fused_step_compilations']} decode {m['decode_compilations']}")
    if late:
        log(f"generator lateness ms: p50 {pct(late, 50) * 1e3:.3f} max {max(late) * 1e3:.3f}")
    log(f"requests: sent {len(sent)}, due in window {due_in}, admitted "
        f"{len(rec.admit)}, finished {len(comps)}, "
        f"failed {failed}")
    e2e, extra = end_to_end(run, rec)
    e2e["setup_s"] = setup_s
    mem_peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": None, "attempted": len(sent), "failed": failed}
    if trace:
        red = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir), modules=TICK_MODULES,
                                  chips=len(devs), require_tpu=st.require_tpu)
        ticks = traced_ticks(run, rec, config, red["step_modules"])
        ctx = {"trace": red, "ticks": ticks, "peaks": st.peaks, "chips": len(devs),
               "queue_wait_ms": extra["queue_wait_ms"],
               "occupancy": [(pf + dc) / eng["num_slots"] for pf, dc in
                             engine.phase_log[run["gen"]["open_tick"]:run["gen"]["close_tick"]]],
               "memory_peak_bytes": mem_peak}
        result["metrics"] = per_layer(cell, ctx)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
        log(f"trace: {len(ticks)} ticks, {sum(t['device_s'] is not None for t in ticks)} "
            f"matched to their program; modules {json.dumps(red['module_calls'])}")
        trace_reduce.remove(trace_dir)
    else:
        result["metrics"] = {mm["name"]: {"value": float(e2e[mm["name"]]), "unit": mm["unit"]}
                             for mm in cell["end_to_end"] if mm["name"] in e2e}
    result["device"] = device
    result["_run"] = {"e2e": e2e, "requests_due": extra["requests_due"],
                      "tpot_requests": extra["tpot_requests"],
                      "backlog_at_close": extra["backlog_at_close"]}
    return result, comps, rec


def check(result: dict, comps: list, rec: Recorder, config: dict, weights, seed: int, *,
          control: bool = False, log=print) -> dict:
    """Compare a sample of the finished requests with the reference and set
    ``correct`` and the checks, which come last in the result."""
    t_ref = time.time()
    picks = sample_completions(comps, seed, int(config["correct"]["sample_tokens"]))
    cmp_ = compare(config, weights, picks)
    limit = config["correct"]["max_logit_gap"]
    checks = {
        "max_logit_gap": {"value": cmp_["max_logit_gap"], "limit": limit},
        "compiles_in_window": {"value": rec.compile_events, "limit": 0},
    }
    if control:
        ctl = compare(config, weights, picks, lowp=True)
        checks["control_max_logit_gap"] = {"value": ctl["max_logit_gap"], "limit": limit}
    log(f"reference: {cmp_['requests']} requests, {cmp_['tokens']} served tokens "
        f"compared in {time.time() - t_ref:.1f} s")
    result["correct"] = bool(
        limit is not None and cmp_["tokens"] > 0 and cmp_["max_logit_gap"] <= limit
        and rec.compile_events == 0 and result["failed"] == 0)
    extra = result.pop("_run")
    extra["reference_s"] = time.time() - t_ref
    extra["mean_logit_gap"] = cmp_["mean_logit_gap"]
    extra["compared_tokens"] = cmp_["tokens"]
    if control:
        extra["control_mean_logit_gap"] = ctl["mean_logit_gap"]
    result["checks"] = checks
    result["_run"] = extra
    return result


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, rate=None, control: bool = False,
             t_start: float | None = None, log=print) -> dict:
    """One run of one cell.  Returns the contract's result object (with the
    harness's own extras under ``_run``).  The engine is freed before the
    reference runs, so the reference sets no memory peak of its own."""
    t_start = time.time() if t_start is None else t_start
    st = build(cell, seed, require_tpu=require_tpu, log=log)
    result, comps, rec = serve(st, cell, seed, seconds, trace, rate=rate,
                               t_start=t_start, log=log)
    weights = st.weights
    st.engine = None
    del st
    gc.collect()
    return check(result, comps, rec, cell["config"], weights, seed, control=control, log=log)
