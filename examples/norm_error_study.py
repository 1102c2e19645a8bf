"""Normalization-error study — reproduces the structure of Fig. 2 and Fig. 5.

Fig. 2: normalization error (|1-sum p|, |1-sigma|) versus approximation level
        for the tunable baselines — showing the paper's trade-off curve.
Fig. 5: distribution of normalization error measured over transformer-scale
        activations, GN vs exact vs unnormalized baselines; the paper reports
        77.1% of Softmax and 100% of LayerNorm errors below 0.2e-6 for GN.

Plus the serving-path extension (PR 9): the same normalization-error lens
pointed at the block-paged GN-softmax read over **int8-quantized KV blocks**
(per-block scales, dequantized per streamed tile) — the error must stay
within the analytic bound, because quantization only perturbs the scores
and the GN guarantee is score-independent: the same approximated numerators
feed the sum, one reciprocal normalizes, masked columns saturate the LUT to
exactly-zero numerators.

Run:  PYTHONPATH=src python examples/norm_error_study.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines
from repro.core.api import get_norm, get_softmax
from repro.core.gn_softmax import SoftmaxLUTConfig, gn_softmax_hwsim
from repro.core.metrics import layernorm_norm_error, softmax_norm_error
from repro.serve.norm_error import paged_int8_read_norm_error


key = jax.random.PRNGKey(42)
# attention-logit-scale inputs: (rows, seq) as seen inside a transformer head
X = jax.random.normal(key, (4096, 256)) * 5.0
H = jax.random.normal(jax.random.fold_in(key, 1), (4096, 1024)) * 6.0 + 2.0


def q(v):  # summary of an error distribution
    v = np.asarray(v, np.float64)
    return (f"mean {v.mean():.3e}  p50 {np.percentile(v, 50):.3e}  "
            f"p99 {np.percentile(v, 99):.3e}  max {v.max():.3e}  "
            f"<2e-7: {100.0 * (v < 2e-7).mean():.1f}%")


print("== Fig. 5 analogue: softmax normalization-error distribution ==")
for name in ("exact", "gn", "gn_hwsim", "softermax", "pseudo", "log_domain"):
    err = softmax_norm_error(get_softmax(name)(X))
    print(f"  {name:<12} {q(err)}")

print("\n== Fig. 5 analogue: layernorm |1-sigma| distribution ==")
for name in ("exact_ln", "gn_ln", "gn_ln_hwsim", "integer_ln", "lut_ln"):
    err = layernorm_norm_error(get_norm(name)(H))
    print(f"  {name:<12} {q(err)}")

print("\n== Fig. 2 analogue: approximation level vs normalization error ==")
print("  (GN-softmax hw-sim, sweeping the fixed-point fractional bits f:")
print("   more bits = finer Delta grid = lower approximation level)")
for f in (0, 1, 2, 3, 4):
    cfg = SoftmaxLUTConfig(frac_bits=f)  # radix fixed at the paper's R=8
    p = gn_softmax_hwsim(X, cfg)
    nerr = softmax_norm_error(p)
    aerr = jnp.abs(p - get_softmax("exact")(X)).max()
    print(f"  f={f} (LUT {8 << f} entries)  max|p-exact| {float(aerr):.3e}   "
          f"|1-sum p| max {float(nerr.max()):.3e}")
print("  -> approximation error falls with bigger LUTs, while the normalization")
print("     error stays pinned near zero: the guarantee is structural (the same")
print("     approximated y feeds numerator and denominator), not a precision effect.")

print("\n== Softermax contrast: its normalization error IS its approximation ==")
for bits in (4, 6, 8, 10):
    p = baselines.softermax(X, frac_bits=bits)
    print(f"  softermax frac_bits={bits:<2}  |1-sum p| max "
          f"{float(softmax_norm_error(p).max()):.3e}")

print("\n== Paged serving read: |1-sum p| over int8 KV blocks vs bound ==")
print("  (streamed block-tile read, per-block dequant; quantization perturbs")
print("   the scores, the GN guarantee holds over whatever scores arrive)")
for kd in ("fp", "int8"):
    measured, bound, t = paged_int8_read_norm_error(kv_dtype=kd)
    print(f"  kv_dtype={kd:<5} t={t:<3} measured {measured:.3e}  "
          f"analytic bound (t+1)*2^-23 = {bound:.3e}  "
          f"{'OK' if measured <= bound else 'VIOLATION'}")
