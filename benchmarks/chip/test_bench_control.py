"""``correct`` can fail: the float8 control and planted faults in the timed
path, on the tiny CPU cell, each read past the limit."""
from __future__ import annotations

import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
from repro.models.transformer import Model  # noqa: E402
from repro.serve.engine import ContinuousEngine  # noqa: E402


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_the_limit(tiny, seed):
    """The program and the control read the same requests, served to their
    end through the cell's engine, so the readings repeat exactly."""
    import traffic_gen

    cell = tiny("internlm2-docqa-4k")
    st = harness.build(cell, seed, require_tpu=False, log=lambda s: None)
    vocab = cell["config"]["vocab_size"]
    reqs = traffic_gen.closed_loop_requests(cell["mix"], vocab, seed, 24)
    from repro.serve.scheduler import Request

    comps = st.engine.run([Request(tokens=r.tokens, max_new_tokens=r.max_new,
                                   temperature=0.0) for r in reqs])
    limit = cell["config"]["correct"]["max_logit_gap"]
    prog = harness.compare(cell["config"], st.weights, comps)
    ctl = harness.compare(cell["config"], st.weights, comps, lowp=True)
    assert prog["tokens"] == ctl["tokens"] == sum(r.max_new for r in reqs)
    assert prog["max_logit_gap"] <= limit < ctl["max_logit_gap"]


def _token_altered(monkeypatch):
    orig = ContinuousEngine._sample_next

    def bad(self, last_logits, active, is_prefill, temps, key):
        nxt, key = orig(self, last_logits, active, is_prefill, temps, key)
        alt = (nxt + 1) % self.model.cfg.vocab
        return jnp.where(active & ~is_prefill, alt, nxt), key

    monkeypatch.setattr(ContinuousEngine, "_sample_next", bad)


def _state_unchanged(monkeypatch):
    orig = Model.fused_step_slots_paged

    def bad(self, params, cache, *args, **kw):
        out = orig(self, params, cache, *args, **kw)
        return (out[0], cache, *out[2:])

    monkeypatch.setattr(Model, "fused_step_slots_paged", bad)


def _half_batch(monkeypatch):
    orig = Model.fused_step_slots_paged

    def bad(self, params, cache, *args, **kw):
        out = orig(self, params, cache, *args, **kw)
        n = out[0].shape[0]
        kept = out[0][: n // 2]
        return (jnp.concatenate([kept, kept[: n - n // 2]]), *out[1:])

    monkeypatch.setattr(Model, "fused_step_slots_paged", bad)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
def test_faults_in_the_timed_path_read_not_correct(tiny, monkeypatch, fault):
    fault(monkeypatch)
    res = harness.run_cell(tiny(), 4, 1.5, False, require_tpu=False, log=lambda s: None)
    chk = res["checks"]["max_logit_gap"]
    assert res["correct"] is False and chk["value"] > chk["limit"]
