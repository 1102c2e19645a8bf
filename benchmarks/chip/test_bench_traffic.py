"""Traffic generation of the on-chip benchmark (CPU, no JAX device work)."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import traffic_gen  # noqa: E402

MIXES = sorted(p.stem for p in traffic_gen.TRAFFIC_DIR.glob("*.json"))


def _requests(mix, seed, vocab=1000, window=30.0):
    if mix["kind"] == "open_loop":
        return traffic_gen.open_loop_requests(mix, vocab, seed, mix["lead_in_s"], window)
    return traffic_gen.closed_loop_requests(mix, vocab, seed, 48)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic_gen.load_mix(name)
    a, b = _requests(mix, 2**33 + 17), _requests(mix, 2**33 + 17)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.tokens, y.tokens)
    c = _requests(mix, 5)
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_the_same_work_for_every_seed(name):
    mix = traffic_gen.load_mix(name)
    sizes = []
    for seed in (1, 99, 2**31 + 3):
        reqs = _requests(mix, seed)
        p = np.array([len(r.tokens) for r in reqs])
        o = np.array([r.max_new for r in reqs])
        assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
        assert o.min() >= mix["output"]["min"] and o.max() <= mix["output"]["max"]
        assert all(((r.tokens >= 0) & (r.tokens < 1000)).all() for r in reqs)
        sizes.append((p.tolist(), o.tolist(), [r.due_s for r in reqs]))
    # every seed offers the same work in the same order; only tokens differ
    assert sizes[0] == sizes[1] == sizes[2]


def test_stratified_bands_hand_worked():
    dist = {"dist": "uniform", "min": 100, "max": 200}
    got = traffic_gen.stratified_lengths(dist, 8, 4, np.random.default_rng(0))
    assert sorted(got[:4].tolist()) == [112, 138, 162, 188]
    assert sorted(got[4:].tolist()) == [112, 138, 162, 188]


def _check_mean_rate(mix, spread):
    horizon = 4000.0
    due = traffic_gen.arrival_times(mix, horizon, rate_rps=3.0)
    assert abs(len(due) - 3.0 * horizon) < 6 * math.sqrt(3.0 * horizon) * spread
    assert np.all(np.diff(due) > 0)
    # the calm rate is the one that gives the stated mean
    pe, ps, f = traffic_gen.burst_params(mix)
    pi = pe / (pe + 1 - ps)
    calm = traffic_gen.calm_rate({**mix, "rate_rps": 3.0})
    assert (1 - pi) / calm + pi / (f * calm) == pytest.approx(1 / 3.0)
    return calm


def test_open_loop_mean_rate_within_sampling_error():
    # the chat mix is Poisson: 6 sigmas bound the count
    assert _check_mean_rate(traffic_gen.load_mix("chat"), 1) == 3.0


def test_bursty_open_loop_mean_rate_within_sampling_error():
    # bursts make arrivals over-dispersed; twice 6 Poisson sigmas bound them
    mix = {**traffic_gen.load_mix("chat"),
           "burst_factor": 4.0, "p_enter_burst": 0.25, "p_stay_burst": 0.7}
    assert _check_mean_rate(mix, 2) < 3.0


def test_new_mix_file_is_found_by_name(tmp_path):
    mix = {"kind": "closed_loop", "schedule_seed": 1, "clients": 2, "queue_per_client": 4, "lead_in_s": 0,
           "strata": 2, "prompt": {"dist": "uniform", "min": 10, "max": 20},
           "output": {"dist": "uniform", "min": 3, "max": 5}}
    (tmp_path / "tiny-offline.json").write_text(json.dumps(mix))
    got = traffic_gen.load_mix("tiny-offline", tmp_path)
    reqs = traffic_gen.closed_loop_requests(got, 50, 3, 4)
    assert [len(r.tokens) for r in reqs] and all(10 <= len(r.tokens) <= 20 for r in reqs)
    with pytest.raises(KeyError, match="tiny-offline"):
        traffic_gen.load_mix("missing", tmp_path)
