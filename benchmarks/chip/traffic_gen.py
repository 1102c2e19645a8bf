"""Traffic for the on-chip serving benchmark, from a mix file and a seed.

One general generator reads every mix file in ``traffic/``; a new mix is a
new data file, never new code.  Two kinds exist:

* ``open_loop`` - independent users: requests fall due on a schedule in
  seconds, whatever the server does, at the long-run mean rate
  ``rate_rps``.  Without burst keys the schedule is a Poisson process.
  With them it is a two-state Markov-modulated process (calm gaps at the
  calm rate, bursts ``burst_factor`` times hotter; the state is re-drawn at
  each arrival with probability ``p_enter_burst`` to enter a burst and
  ``p_stay_burst`` to stay in one).
* ``closed_loop`` - an offline batch: ``clients`` requests are outstanding
  at all times, and a finished one is replaced at once.

Lengths are drawn stratified: each block of ``strata`` requests takes one
length from each of ``strata`` equal-probability bands of the distribution
(the band's midpoint quantile), shuffled within the block; a window of a
few blocks always holds the whole distribution.  The arrival times and the
lengths come from the mix's own ``schedule_seed``, so every run seed offers
the same work in the same order; the run seed makes the prompts' tokens
(and the harness makes the weights from it).  On the chip a tick's time
does not depend on the values it computes, so runs of different seeds time
the same work.

Prompt tokens follow a Zipf-successor Markov chain: each token has
``branching`` successors, a fixed hash of the token, one of which follows
with Zipf(``zipf_a``) probability.

The arrival process and the token chain follow ``repro.serve.workload.
sla_requests`` and ``repro.data.synthetic.batch_at``, restated here in numpy
and on a clock in seconds rather than engine steps.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
KINDS = ("open_loop", "closed_loop")


@dataclasses.dataclass(frozen=True)
class Req:
    """One request of the mix: when it falls due (seconds from the start of
    the traffic, open loop only), its prompt and its output budget."""

    index: int
    due_s: float
    tokens: np.ndarray
    max_new: int


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        have = sorted(p.stem for p in Path(directory).glob("*.json"))
        raise KeyError(f"no traffic mix {name!r} in {directory}; have {have}")
    mix = json.loads(path.read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"mix {name!r}: kind must be one of {KINDS}")
    return mix


# ------------------------------------------------------------- lengths ---
def _quantile(dist: dict, q: float) -> int:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        v = lo + q * (hi - lo)
    elif dist["dist"] == "lognormal":
        v = float(dist["median"]) * math.exp(float(dist["sigma"]) * NormalDist().inv_cdf(q))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(hi, max(lo, round(v))))


def stratified_lengths(dist: dict, n: int, strata: int,
                       rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: consecutive blocks of ``strata`` each hold one length
    from every equal-probability band, shuffled by ``rng``; a last, shorter
    block of r lengths holds one from each of r bands."""
    blocks = []
    for start in range(0, n, strata):
        k = min(strata, n - start)
        band = np.array([_quantile(dist, (i + 0.5) / k) for i in range(k)])
        blocks.append(band[rng.permutation(k)])
    return np.concatenate(blocks) if blocks else np.zeros(0, np.int64)


def length_bounds(mix: dict) -> tuple[int, int]:
    """(largest prompt, largest output budget) the mix can draw."""
    return int(mix["prompt"]["max"]), int(mix["output"]["max"])


# -------------------------------------------------------------- tokens ---
def zipf_markov_tokens(lengths, vocab: int, rng: np.random.Generator,
                       branching: int = 8, zipf_a: float = 1.5) -> list[np.ndarray]:
    """One Zipf-successor Markov prompt per length, all chains advanced
    together."""
    lengths = np.asarray(lengths, np.int64)
    n = len(lengths)
    if n == 0:
        return []
    top = int(lengths.max())
    w = 1.0 / np.arange(1, branching + 1) ** zipf_a
    ks = rng.choice(branching, size=(n, max(top - 1, 0)), p=w / w.sum())
    out = np.empty((n, top), np.int64)
    out[:, 0] = rng.integers(0, vocab, n)
    for t in range(1, top):
        out[:, t] = (out[:, t - 1] * 31 + ks[:, t - 1] * 1000003 + 12345) % vocab
    return [out[i, : lengths[i]].astype(np.int32) for i in range(n)]


# ------------------------------------------------------------ arrivals ---
def calm_rate(mix: dict) -> float:
    """Calm-state rate that gives the mix its long-run mean ``rate_rps``.
    The burst state's share of arrivals is the stationary probability of
    the two-state chain, p_enter / (p_enter + 1 - p_stay)."""
    pe, ps, f = burst_params(mix)
    pi = pe / (pe + 1.0 - ps)
    return float(mix["rate_rps"]) * ((1.0 - pi) + pi / f)


def burst_params(mix: dict) -> tuple[float, float, float]:
    """(p_enter_burst, p_stay_burst, burst_factor); a mix without them never
    enters a burst."""
    return (float(mix.get("p_enter_burst", 0.0)), float(mix.get("p_stay_burst", 0.0)),
            float(mix.get("burst_factor", 1.0)))


def arrival_times(mix: dict, horizon_s: float, rate_rps: float | None = None) -> np.ndarray:
    """Due times in [0, horizon_s) of the open-loop schedule."""
    if rate_rps is not None:
        mix = {**mix, "rate_rps": rate_rps}
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    base = calm_rate(mix)
    pe, ps, f = burst_params(mix)
    out, clock, burst = [], 0.0, False
    while True:
        burst = rng.random() < (ps if burst else pe)
        eff = base * (f if burst else 1.0)
        clock += rng.exponential(1.0 / eff)
        if clock >= horizon_s:
            return np.asarray(out)
        out.append(clock)


# ------------------------------------------------------------ requests ---
def open_loop_requests(mix: dict, vocab: int, seed: int, lead_in_s: float,
                       window_s: float, rate_rps: float | None = None) -> list[Req]:
    """Every request due before the window closes.  The lead-in's requests
    and the window's take their lengths from separate stratified draws, so
    the window always holds the same set of lengths."""
    due = arrival_times(mix, lead_in_s + window_s, rate_rps)
    sched = np.random.default_rng([int(mix["schedule_seed"]), 1])
    strata = int(mix["strata"])
    plens, olens = [], []
    for part in (due[due < lead_in_s], due[due >= lead_in_s]):
        plens.append(stratified_lengths(mix["prompt"], len(part), strata, sched))
        olens.append(stratified_lengths(mix["output"], len(part), strata, sched))
    plens, olens = np.concatenate(plens), np.concatenate(olens)
    z = mix.get("zipf", {})
    toks = zipf_markov_tokens(plens, vocab, np.random.default_rng(seed),
                              z.get("branching", 8), z.get("a", 1.5))
    return [Req(i, float(d), t, int(o))
            for i, (d, t, o) in enumerate(zip(due, toks, olens))]


def closed_loop_requests(mix: dict, vocab: int, seed: int, count: int) -> list[Req]:
    """The first ``count`` requests of the closed loop's queue, in order."""
    return list(itertools.islice(closed_loop_stream(mix, vocab, seed, count), count))


def closed_loop_stream(mix: dict, vocab: int, seed: int, batch: int):
    """The closed loop's queue, endless, made ``batch`` requests at a time
    (rounded up to a multiple of ``strata``)."""
    sched = np.random.default_rng([int(mix["schedule_seed"]), 1])
    rng = np.random.default_rng(seed)
    strata = int(mix["strata"])
    batch = -(-batch // strata) * strata
    z = mix.get("zipf", {})
    index = 0
    while True:
        plens = stratified_lengths(mix["prompt"], batch, strata, sched)
        olens = stratified_lengths(mix["output"], batch, strata, sched)
        toks = zipf_markov_tokens(plens, vocab, rng, z.get("branching", 8), z.get("a", 1.5))
        for t, o in zip(toks, olens):
            yield Req(index, 0.0, t, int(o))
            index += 1
