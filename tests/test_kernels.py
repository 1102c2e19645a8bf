"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracle.

Sweeps shapes (odd/padded/lane-aligned) and dtypes per kernel, plus the
normalization-guarantee invariants on the kernel outputs themselves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import luts
from repro.core.luts import SoftmaxLUTConfig
from repro.kernels.common import lut_lookup
from repro.kernels.gn_attention.ops import gn_attention
from repro.kernels.gn_attention.ref import gn_attention_ref
from repro.kernels.gn_layernorm.ops import gn_layernorm, gn_rmsnorm
from repro.kernels.gn_layernorm.ref import gn_layernorm_ref
from repro.kernels.gn_softmax.ops import gn_softmax
from repro.kernels.gn_softmax.ref import gn_softmax_ref

KEY = jax.random.PRNGKey(7)


def _rand(shape, dtype=jnp.float32, scale=3.0, key=KEY):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


@pytest.mark.parametrize("table", ["coarse", "residual", "rsqrt"])
def test_lut_lookup_bit_equal_to_table(table):
    """The in-kernel ROM lookup returns the table's entries bit for bit, and
    0 past its end."""
    coarse, residual = luts.exp_luts(luts.TPU_SOFTMAX_LUT)
    values = {"coarse": coarse, "residual": residual,
              "rsqrt": luts.rsqrt_mantissa_lut(luts.PAPER_RSQRT)}[table]
    n = values.shape[0]
    idx = jnp.arange(8 * 128, dtype=jnp.int32).reshape(8, 128) % (n + 3)
    want = np.concatenate([values, np.zeros(3, np.float32)])[np.asarray(idx)]
    got = np.asarray(lut_lookup(idx, values))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


SOFTMAX_SHAPES = [
    (8, 128),          # exactly one tile
    (4, 7, 300),       # ragged cols, 3-D
    (1, 1000),         # single row
    (257, 64),         # ragged rows, narrow cols
    (2, 3, 5, 130),    # 4-D, barely off-lane
]


class TestGNSoftmaxKernel:
    @pytest.mark.parametrize("shape", SOFTMAX_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose_vs_ref(self, shape, dtype):
        x = _rand(shape, dtype)
        got = gn_softmax(x, interpret=True)
        want = gn_softmax_ref(x)
        tol = 1e-6 if dtype == jnp.float32 else 1e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
        )

    @pytest.mark.parametrize(
        "cfg",
        [
            SoftmaxLUTConfig(frac_bits=0),
            SoftmaxLUTConfig(frac_bits=3),
            SoftmaxLUTConfig(frac_bits=4, delta_scale=0.5),
        ],
    )
    def test_cfg_sweep(self, cfg):
        x = _rand((16, 200))
        got = gn_softmax(x, cfg=cfg, interpret=True)
        want = gn_softmax_ref(x, cfg=cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)

    def test_normalization_invariant(self):
        x = _rand((64, 333), scale=8.0)
        p = gn_softmax(x, interpret=True)
        np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, atol=2e-6)

    def test_block_rows_sweep(self):
        x = _rand((64, 256))
        want = gn_softmax_ref(x)
        for br in (8, 16, 64):
            got = gn_softmax(x, block_rows=br, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


LN_SHAPES = [(8, 128), (5, 300), (2, 3, 640), (100, 64)]


class TestGNLayerNormKernel:
    @pytest.mark.parametrize("shape", LN_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose_vs_ref(self, shape, dtype):
        x = _rand(shape, dtype)
        g = _rand(shape[-1:], key=jax.random.PRNGKey(1), scale=1.0)
        b = _rand(shape[-1:], key=jax.random.PRNGKey(2), scale=0.5)
        got = gn_layernorm(x, g, b, interpret=True)
        want = gn_layernorm_ref(x, g, b)
        tol = 2e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
        )

    def test_rms_variant(self):
        x = _rand((16, 256))
        g = jnp.ones((256,))
        got = gn_rmsnorm(x, g, interpret=True)
        want = gn_layernorm_ref(x, g, None, subtract_mean=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_sigma_invariant(self):
        x = _rand((32, 512), scale=11.0)
        y = gn_layernorm(x, interpret=True)
        np.testing.assert_allclose(np.asarray(y).std(-1), 1.0, atol=1e-4)


ATTN_SHAPES = [
    # (B, H, Hkv, Sq, Sk, D)
    (1, 2, 2, 128, 128, 64),     # MHA, exact tiles
    (2, 4, 2, 200, 200, 64),     # GQA 2:1, ragged seq
    (1, 8, 1, 64, 256, 32),      # MQA, kv longer (prefix decode pattern)
    (1, 2, 2, 100, 100, 80),     # ragged head dim
]


class TestGNAttentionKernel:
    @pytest.mark.parametrize("shape", ATTN_SHAPES)
    @pytest.mark.parametrize("causal", [False, True])
    def test_allclose_vs_ref(self, shape, causal):
        b, h, hkv, sq, sk, d = shape
        q = _rand((b, h, sq, d), scale=0.5)
        k = _rand((b, hkv, sk, d), scale=0.5, key=jax.random.PRNGKey(1))
        v = _rand((b, hkv, sk, d), scale=1.0, key=jax.random.PRNGKey(2))
        got = gn_attention(q, k, v, causal=causal, interpret=True)
        kk = jnp.repeat(k, h // hkv, axis=1)
        vv = jnp.repeat(v, h // hkv, axis=1)
        want = gn_attention_ref(q, kk, vv, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)

    def test_block_sweep(self):
        q = _rand((1, 2, 256, 64), scale=0.5)
        k = _rand((1, 2, 256, 64), scale=0.5, key=jax.random.PRNGKey(1))
        v = _rand((1, 2, 256, 64), key=jax.random.PRNGKey(2))
        want = gn_attention_ref(q, k, v, causal=True)
        for bq, bk in [(64, 64), (128, 256), (256, 128)]:
            got = gn_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=5e-5,
                err_msg=f"block_q={bq} block_k={bk}",
            )

    def test_bf16(self):
        q = _rand((1, 2, 128, 64), jnp.bfloat16, scale=0.5)
        k = _rand((1, 2, 128, 64), jnp.bfloat16, scale=0.5, key=jax.random.PRNGKey(1))
        v = _rand((1, 2, 128, 64), jnp.bfloat16, key=jax.random.PRNGKey(2))
        got = gn_attention(q, k, v, interpret=True)
        want = gn_attention_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
        )

    def test_attention_rows_normalized(self):
        """Σp = 1 survives tiling: feed v = identity columns to read p back."""
        sk = 256
        q = _rand((1, 1, 128, 64), scale=0.5)
        k = _rand((1, 1, sk, 64), scale=0.5, key=jax.random.PRNGKey(1))
        v = jnp.ones((1, 1, sk, 1)) * jnp.eye(sk, 1)  # e1 basis probe
        v = jnp.ones((1, 1, sk, 64))  # sum of p equals output of all-ones v
        out = gn_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)
