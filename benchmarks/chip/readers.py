"""What the per-layer metric files in ``metrics/`` compute from a run.

Each reader takes the run's context (the trace reduction, the traced
ticks' least work, the harness's own counts) and returns a number, or None
where the run holds nothing to read.  Shares are in percent.  Without a
table of peaks (a rehearsal off the chip) no share of a peak is read.
"""
from __future__ import annotations

import statistics

import work


def tick_ms(ctx: dict, kind: str):
    calls = ctx["trace"]["modules"].get(kind) or []
    return 1e3 * statistics.fmean(calls) if calls else None


def idle_share(ctx: dict):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline(ctx: dict, kind: str):
    """Least time of the traced ``kind`` ticks over their device time, over
    the ticks whose program the trace shows and whose work was rebuilt."""
    if ctx["peaks"] is None:
        return None
    ticks = [t for t in ctx["ticks"] if t["kind"] == kind
             and t["device_s"] is not None and t["complete"]]
    if not ticks:
        return None
    least = sum(work.least_time_s(t["flops"], t["bytes"], ctx["peaks"])[0] for t in ticks)
    return 100.0 * least / sum(t["device_s"] for t in ticks)


def step_mfu(ctx: dict):
    ticks = ctx["ticks"]
    if ctx["peaks"] is None or not ticks or not all(t["complete"] for t in ticks):
        return None
    flops = sum(t["flops"] for t in ticks)
    peak = float(ctx["peaks"]["bf16_flops_per_s"]) * ctx["chips"]
    return 100.0 * flops / (ctx["trace"]["window_s"] * peak)


def queue_wait_ms(ctx: dict):
    q = ctx["queue_wait_ms"]
    return statistics.median(q) if q else None


def slot_occupancy(ctx: dict):
    occ = ctx["occupancy"]
    return 100.0 * statistics.fmean(occ) if occ else None


def peak_hbm_share(ctx: dict):
    if ctx["peaks"] is None or not ctx["memory_peak_bytes"]:
        return None
    return 100.0 * ctx["memory_peak_bytes"] / float(ctx["peaks"]["hbm_bytes"])
