"""The numerical checks chip_smoke.py runs, exercised on the CPU at reduced
width.

Pinned invariants:
  1. an engine built with ``record_logits=True`` keeps, per request, the
     held logits each new token was chosen from; greedy tokens are their
     argmax, and on the CPU they equal bit for bit the static oracle's
     logits fed the same tokens;
  2. ``static_reference(..., return_logits=True)`` serves the same tokens
     as the plain oracle, and teacher forcing with its own tokens gives
     back its own logits;
  3. ``chip_diag.py``'s planted serving faults (a stale KV block, a
     position off by one) stay silent to the GN sentinels and show in the
     victim's logits alone;
  4. ``chip_smoke.py`` on a backend that is not a TPU exits non-zero and
     prints no result line.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config, reduce_config
from repro.models.transformer import make_model
from repro.serve.engine import ContinuousEngine, ServeConfig, static_reference
from repro.serve.workload import required_max_seq, staggered_requests

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_diag  # noqa: E402


@pytest.fixture(scope="module")
def served():
    cfg = reduce_config(get_config("internlm2-1.8b"))
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = staggered_requests(cfg, n_requests=4, base_len=16,
                              max_new_tokens=8, stagger=2, seed=11)
    scfg = ServeConfig(max_new_tokens=8)
    engine = ContinuousEngine(model, params, num_slots=2,
                              max_seq=required_max_seq(reqs), cfg=scfg,
                              chunk=8, record_logits=True)
    comps = engine.run(reqs)
    return model, params, reqs, scfg, engine, comps


def test_recorded_logits_are_the_forced_oracles(served):
    model, params, reqs, scfg, _, comps = served
    forced = {c.request_id: c.tokens for c in comps}
    toks, logits = static_reference(model, params, reqs, scfg, forced=forced,
                                    return_logits=True)
    for c in comps:
        assert c.logits.shape == (len(c.new_tokens), model.cfg.vocab)
        np.testing.assert_array_equal(np.argmax(c.logits, -1), c.new_tokens)
        np.testing.assert_array_equal(toks[c.request_id], c.tokens)
        np.testing.assert_array_equal(logits[c.request_id], c.logits)


def test_static_reference_logits_and_forcing(served):
    model, params, reqs, scfg, _, _ = served
    plain = static_reference(model, params, reqs, scfg)
    toks, logits = static_reference(model, params, reqs, scfg,
                                    return_logits=True)
    _, again = static_reference(model, params, reqs, scfg, forced=toks,
                                return_logits=True)
    for r in reqs:
        np.testing.assert_array_equal(toks[r.id], plain[r.id])
        assert logits[r.id].shape == (r.max_new_tokens, model.cfg.vocab)
        np.testing.assert_array_equal(again[r.id], logits[r.id])


def test_unrecorded_completions_hold_no_logits(served):
    model, params, reqs, scfg, engine, _ = served
    engine.record_logits = False
    try:
        engine.reset()
        assert all(c.logits is None for c in engine.run(reqs))
    finally:
        engine.record_logits = True


def test_compiled_tick_text(served):
    _, _, reqs, _, engine, _ = served
    text = engine.compiled_tick_text(reqs[:1])
    assert "HloModule" in text
    assert engine._fused.capture_avals is None


def test_planted_faults_are_silent_to_sentinels_and_seen_in_logits(tmp_path):
    """On the CPU the served logits equal the oracle's bit for bit, so each
    planted fault shows as a nonzero gap on its victim alone, with the
    fault counters at 0 and no recompilation (how far the gap clears the
    gates' bounds at full width is measured on the chip)."""
    out = tmp_path / "diag.json"
    chip_diag.main(["--smoke", "--dtypes", "bfloat16", "--out", str(out)])
    r = json.loads(out.read_text())["bfloat16"]
    assert r["clean"]["gates_fail"] is None
    assert set(r["clean"]["gap"]["max"].values()) == {0.0}
    assert r["recompiled"] == 0
    for name in chip_diag.PLANTS:
        victim = str(r[name]["planted"]["request"])
        gap = r[name]["gap"]["max"]
        assert gap[victim] > 0, name
        assert all(v == 0.0 for k, v in gap.items() if k != victim), name
        assert not any(r[name]["faults"].values()), name


def test_chip_smoke_refuses_a_backend_that_is_not_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stderr
