"""Each plain reference against the program's own float32 forward pass, on
the same seeded weights at a small size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import _hidden, _static_cfg, reference_module
from bench.references.common import mm_f32, rms_norm
from bench.tests import _small
from bench.weights import make
from repro.config import RunConfig
from repro.models.model import forward


@pytest.mark.parametrize("which", ["gqa", "mla"])
def test_reference_matches_program_forward(which):
    cfg, m = getattr(_small, which)()
    w = make(reference_module(cfg).layout(cfg), 2**31 + 7, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, m.vocab,
                                                           (2, 40)))
    rc = RunConfig(dtype="float32", param_dtype="float32", remat=False)
    with jax.default_matmul_precision("highest"):
        want = forward(w, {"tokens": tokens}, m, rc)
    h = _hidden(w, tokens, _static_cfg(cfg), which, False)
    got = mm_f32(rms_norm(h, w["final_norm"], m.norm_eps), w["head"].T)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert float(jnp.std(want)) > 0.05       # the logits are not flat

