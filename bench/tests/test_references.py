"""The plain references against the program at small sizes on the CPU,
fed the same seeded weights.

Tolerances, and why:
- reference vs program logits: max |diff| <= 5% of max |logit|. The
  program rounds its residual stream and every matmul input to bfloat16
  (relative 2^-8 a rounding), about ten roundings a layer; this reads
  1.8-2.0% here.
- the fp8 control must be off by more than 8%: e4m3 keeps 3 mantissa bits
  (relative 2^-4 a rounding); it reads 17-26% here.
- the quadratic SSD against a token-by-token recurrence in float64: 1e-5
  relative, float32 rounding of a 32-step sum of exponentials.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common
from harness.weights import make_init
from references import ssm_lm
from small import small_cell

CELLS = [("mamba2-370m", "train-b8s2048"), ("mistral-nemo-12b-l2", "train-s4096")]


def program_logits(cfg, params, tokens):
    from repro.models import lm_apply
    from repro.models.layers import lm_logits
    from repro.models.lm import _head_matrix

    h, _ = jax.jit(lambda p, t: lm_apply(cfg, p, t))(params, tokens)
    return np.asarray(lm_logits(h, _head_matrix(cfg, params)))


@pytest.mark.parametrize("workload", CELLS)
def test_reference_matches_program_and_control_does_not(workload):
    from repro.dist.train import abstract_state

    cell = small_cell(*workload)
    cfg = cell.program_config()
    params = jax.jit(make_init(abstract_state(cfg)[0]))(common.seed_key(5))
    tokens = np.random.default_rng(0).integers(2, 250, (2, 64)).astype(np.int32)
    got = program_logits(cfg, params, tokens)
    ref = cell.reference()
    for mode, lo, hi in (("f32", 0.0, 0.05), ("fp8", 0.08, np.inf)):
        want = np.stack([np.asarray(jax.jit(lambda p, t: ref.logits(cell.model, p, t, mode))(params, row))
                         for row in tokens])
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert lo < rel <= hi, (mode, rel)


def test_quadratic_ssd_matches_recurrence():
    rng = np.random.default_rng(1)
    s, h, p, g, n = 32, 4, 8, 2, 6
    x = rng.standard_normal((s, h, p))
    dt = rng.uniform(0.01, 0.5, (s, h))
    a = -rng.uniform(0.5, 4.0, h)
    bm = rng.standard_normal((s, g, n))
    cm = rng.standard_normal((s, g, n))
    with jax.default_matmul_precision("highest"):
        y = np.asarray(ssm_lm.ssd_quadratic(*(jnp.asarray(v, jnp.float32) for v in (x, dt, a, bm, cm)), "f32"))
    state = np.zeros((h, p, n))
    want = np.zeros((s, h, p))
    for t in range(s):
        for hh in range(h):
            gg = hh // (h // g)
            state[hh] = state[hh] * np.exp(dt[t, hh] * a[hh]) + dt[t, hh] * np.outer(x[t, hh], bm[t, gg])
            want[t, hh] = state[hh] @ cm[t, gg]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())
