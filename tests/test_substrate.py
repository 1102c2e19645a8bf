"""Substrate tests: data, optimizer, checkpoint, serving, fault tolerance."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.configs.registry import get_config, reduce_config
from repro.data.synthetic import (
    DataConfig,
    batch_at,
    classification_batch,
    optimal_perplexity,
    zipf_probs,
)
from repro.models.transformer import make_model
from repro.serve.engine import ServeConfig, generate, perplexity
from repro.train.loop import make_train_step
from repro.train.optimizer import (
    OptimizerConfig,
    adamw_update,
    compress_with_error_feedback,
    init_opt_state,
    lr_at,
)

REPO = Path(__file__).resolve().parents[1]


class TestData:
    def test_deterministic_and_sharded(self):
        cfg = DataConfig(global_batch=8, seq_len=32)
        b1 = batch_at(cfg, step=3)
        b2 = batch_at(cfg, step=3)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
        # shards partition the batch deterministically
        s0 = batch_at(cfg, 3, shard=0, num_shards=2)
        s1 = batch_at(cfg, 3, shard=1, num_shards=2)
        assert s0["tokens"].shape == (4, 32)
        assert not np.array_equal(np.asarray(s0["tokens"]), np.asarray(s1["tokens"]))

    def test_markov_structure_learnable(self):
        """Every transition must be one of the K hashed successors."""
        cfg = DataConfig(vocab=97, seq_len=64, global_batch=4, branching=8)
        toks = np.asarray(batch_at(cfg, 0)["tokens"])
        from repro.data.synthetic import _successor

        for row in toks:
            for t in range(len(row) - 1):
                succ = {int(_successor(cfg, jnp.int32(row[t]), jnp.int32(k))) for k in range(8)}
                assert int(row[t + 1]) in succ

    def test_optimal_perplexity_positive(self):
        cfg = DataConfig()
        assert 1.0 < optimal_perplexity(cfg) < cfg.branching + 1


class TestOptimizer:
    def _tiny(self):
        return {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}

    def test_lr_schedule(self):
        cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100)
        assert float(lr_at(cfg, jnp.int32(5))) == pytest.approx(0.5)
        assert float(lr_at(cfg, jnp.int32(10))) == pytest.approx(1.0, rel=1e-3)
        assert float(lr_at(cfg, jnp.int32(100))) == pytest.approx(cfg.min_lr_ratio, rel=1e-2)

    def test_adamw_reduces_quadratic(self):
        cfg = OptimizerConfig(lr=0.1, warmup_steps=0, total_steps=100, weight_decay=0.0)
        params = self._tiny()
        state = init_opt_state(cfg, params)
        loss = lambda p: jnp.sum(jnp.square(p["w"])) + jnp.sum(jnp.square(p["b"] - 1))
        l0 = float(loss(params))
        for _ in range(50):
            grads = jax.grad(loss)(params)
            params, state, _ = adamw_update(cfg, params, grads, state)
        assert float(loss(params)) < 0.1 * l0

    def test_error_feedback_compression_converges(self):
        """EF-int8 must track the uncompressed trajectory closely."""
        def train(compression):
            cfg = OptimizerConfig(lr=0.05, warmup_steps=0, total_steps=200,
                                  weight_decay=0.0, grad_compression=compression)
            params = {"w": jnp.full((8, 8), 2.0)}
            state = init_opt_state(cfg, params)
            loss = lambda p: jnp.sum(jnp.square(p["w"] - 0.5))
            for _ in range(100):
                grads = jax.grad(loss)(params)
                params, state, _ = adamw_update(cfg, params, grads, state)
            return float(loss(params))

        assert train(8) < 1e-2
        assert abs(train(8) - train(None)) < 1e-2

    def test_compression_error_feedback_identity(self):
        g = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}
        e = {"w": jnp.zeros((8, 8))}
        deq, new_e = compress_with_error_feedback(g, e, bits=8)
        np.testing.assert_allclose(
            np.asarray(deq["w"] + new_e["w"]), np.asarray(g["w"]), atol=1e-6
        )  # deq + residual == input: nothing is lost, only delayed


class TestCheckpoint:
    def test_roundtrip_bitwise(self, tmp_path):
        tree = {
            "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "n": {"b": jnp.ones((2,), jnp.bfloat16), "s": jnp.int32(7)},
        }
        store.save(tmp_path, 5, tree, extra={"k": "v"})
        assert store.latest_step(tmp_path) == 5
        got, man = store.restore(tmp_path, 5, tree)
        assert man["extra"]["k"] == "v"
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
            assert np.asarray(a).dtype == np.asarray(b).dtype
            np.testing.assert_array_equal(
                np.asarray(a, np.float64), np.asarray(b, np.float64)
            )

    def test_atomicity_tmp_cleanup(self, tmp_path):
        tree = {"a": jnp.ones((2,))}
        # simulate a crashed save
        (tmp_path / ".tmp_step_00000003").mkdir(parents=True)
        store.save(tmp_path, 4, tree)
        assert not list(tmp_path.glob(".tmp_step_*"))
        assert store.latest_step(tmp_path) == 4

    def test_multiple_steps_latest(self, tmp_path):
        tree = {"a": jnp.ones((2,))}
        for s in (2, 7, 11):
            store.save(tmp_path, s, tree)
        assert store.latest_step(tmp_path) == 11


class TestServe:
    def test_generate_batched(self):
        cfg = reduce_config(get_config("internlm2-1.8b"))
        model = make_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompts = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, cfg.vocab)}
        out = generate(model, params, prompts, ServeConfig(max_new_tokens=4))
        assert out.shape == (3, 12)
        # greedy decode must be deterministic
        out2 = generate(model, params, prompts, ServeConfig(max_new_tokens=4))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))

    def test_decode_matches_forward_argmax(self):
        """Greedy continuation equals argmax of the teacher-forced forward."""
        cfg = reduce_config(get_config("stablelm-1.6b"))
        model = make_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
        out = generate(model, params, {"tokens": toks}, ServeConfig(max_new_tokens=1))
        logits, _ = jax.jit(model.forward)(params, {"tokens": toks})
        np.testing.assert_array_equal(
            np.asarray(out[:, -1]), np.asarray(jnp.argmax(logits[:, -1], -1))
        )


class TestFaultTolerance:
    def _run(self, outdir, extra):
        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO / "src")
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"  # tests keep no cache
        cmd = [
            sys.executable, "-m", "repro.launch.train",
            "--arch", "internlm2-1.8b", "--smoke",
            "--steps", "8", "--seq", "16", "--batch", "4",
            "--checkpoint-every", "3", "--outdir", str(outdir),
        ] + extra
        return subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=600)

    @pytest.mark.slow  # ~30s: full train-kill-restart subprocess cycle
    def test_checkpoint_restart_bitwise(self, tmp_path):
        # uninterrupted run
        r_full = self._run(tmp_path / "full", [])
        assert r_full.returncode == 0, r_full.stderr[-2000:]
        # failing run: dies at step 5 (after ckpt at step 3), then restarts
        r_fail = self._run(tmp_path / "ft", ["--fail-at", "5"])
        assert r_fail.returncode == 42
        r_resume = self._run(tmp_path / "ft", [])
        assert r_resume.returncode == 0, r_resume.stderr[-2000:]
        assert "[resume] from checkpoint step 3" in r_resume.stdout

        # deterministic data + step-keyed state => identical final loss
        def last_loss(d):
            lines = (d / "train_log.jsonl").read_text().strip().splitlines()
            return json.loads(lines[-1])["loss"]

        assert last_loss(tmp_path / "full") == pytest.approx(
            last_loss(tmp_path / "ft"), rel=1e-5
        )


class TestCompileCache:
    """The entry points' cache placement, checked without turning it on."""

    def _calls(self, monkeypatch):
        from repro.launch import compile_cache

        calls = []
        monkeypatch.setattr(compile_cache.jax.config, "update",
                            lambda name, value: calls.append((name, value)))
        return compile_cache, calls

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        compile_cache, calls = self._calls(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert calls == []

    def test_unset_env_uses_fixed_checkout_dir(self, monkeypatch):
        compile_cache, calls = self._calls(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = str(REPO / ".jax_cache")
        assert compile_cache.use_compile_cache() == path
        assert calls == [("jax_compilation_cache_dir", path)]
