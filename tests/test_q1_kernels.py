"""Flagship Q1 kernel tests: XLA path vs numpy oracle vs pallas fused kernel
(interpret mode on CPU; tests/test_tpu_compile.py compiles it for v5e)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.kernels.q1 import (make_example_batch, q1_final,
                                         q1_reference_numpy, q1_step)
from spark_rapids_tpu.kernels.q1_pallas import (q1_partial_pallas,
                                                q1_step_pallas)

def _assert_close(a, b):
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-4)


def test_xla_matches_numpy_oracle():
    batch, cutoff = make_example_batch(1 << 14, seed=3)
    got = q1_step(batch, jnp.int32(cutoff))
    import jax
    ref = q1_reference_numpy(jax.tree.map(np.asarray, batch), int(cutoff))
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]).astype(np.float64),
                                   ref[k], rtol=1e-4)


@pytest.mark.parametrize("n", [1 << 15, 12345, 100])
def test_pallas_matches_xla(n):
    batch, cutoff = make_example_batch(n, seed=7)
    ref = q1_step(batch, jnp.int32(cutoff))
    got = q1_final(q1_partial_pallas(batch, jnp.int32(cutoff),
                                     interpret=True))
    _assert_close(ref, got)


def test_pallas_respects_validity_mask():
    batch, cutoff = make_example_batch(1 << 12, seed=1)
    valid = np.ones(batch.valid.shape[0], bool)
    valid[::3] = False
    batch = batch._replace(valid=jnp.asarray(valid))
    ref = q1_step(batch, jnp.int32(cutoff))
    got = q1_final(q1_partial_pallas(batch, jnp.int32(cutoff),
                                     interpret=True))
    _assert_close(ref, got)


def test_pallas_step_raises_what_the_compiler_raised():
    """No compile-or-fallback: offered to a backend that cannot lower the
    kernel (the CPU backend outside interpret mode), the step raises the
    lowering's own error instead of quietly running something else."""
    batch, cutoff = make_example_batch(1 << 12)
    with pytest.raises(ValueError, match="interpret mode"):
        q1_step_pallas(batch, jnp.int32(cutoff))
    out = q1_step_pallas(batch, jnp.int32(cutoff), interpret=True)
    assert int(np.asarray(out["count_order"]).sum()) > 0
