"""The float32 reference forward agrees with the served model's prefill.

``models/reference.py`` is the yardstick the chip smoke run holds bf16
serving to, so it must itself match the model where no rounding policy
separates them: float32 weights, float32 activations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.reference import last_logits_f32


@pytest.mark.parametrize("tie", [True, False])
@pytest.mark.parametrize("impl", ["naive", "xla_flash"])
def test_reference_matches_model_prefill(tie, impl):
    cfg = get_config("qwen2.5-3b", smoke=True).replace(
        tie_embeddings=tie, attention_impl=impl, flash_block_q=16,
        flash_block_k=16)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 21), 5,
                              cfg.vocab_size)
    served, _ = model.prefill(params, {"tokens": toks}, capacity=32)
    ref = last_logits_f32(params, toks, cfg)
    assert ref.shape == (3, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(served[:, :cfg.vocab_size]),
                               np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_reference_refuses_unsupported_stacks():
    cfg = get_config("mamba2-130m", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        last_logits_f32(params, jnp.zeros((1, 4), jnp.int32), cfg)
