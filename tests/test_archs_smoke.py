"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and no NaNs (assignment requirement f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models.model import build_model
from repro.training import AdamWConfig, init_opt_state, make_train_step

B, S = 2, 32


def _batch(cfg, key):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks,
             "mask": jnp.ones((B, S), jnp.float32)}
    if cfg.family in ("audio", "encdec"):
        batch["frames"] = jax.random.normal(key, (B, cfg.enc_frames, cfg.d_model))
    if cfg.frontend == "vision_stub":
        batch["prefix_embeds"] = jax.random.normal(
            key, (B, cfg.num_prefix_tokens, cfg.frontend_dim))
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    assert cfg.num_layers <= 5 and cfg.d_model <= 512
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))

    logits, aux = model.forward(params, batch)
    want_s = S + (cfg.num_prefix_tokens if cfg.frontend == "vision_stub" else 0)
    assert logits.shape == (B, want_s, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3)))
    opt = init_opt_state(params)
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_prefill_decode(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, caches = model.prefill(params, batch, capacity=S + 8 +
                                   (cfg.num_prefix_tokens or 0))
    assert logits.shape == (B, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    logits2, caches = model.decode_step(params, tok, caches)
    assert logits2.shape == (B, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32)))


def test_exact_config_specs():
    """The full configs match the assigned table exactly."""
    spec = {
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "mamba2-130m": (24, 768, 1, 1, 0, 50280),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "nemotron-4-340b": (96, 18432, 96, 8, 73728, 256000),
    }
    for arch, (l, d, h, kv, ff, v) in spec.items():
        cfg = get_config(arch)
        assert cfg.num_layers == l, arch
        assert cfg.d_model == d, arch
        assert cfg.num_heads == h, arch
        assert cfg.num_kv_heads == kv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab_size == v, arch
    # MoE extras
    assert get_config("arctic-480b").num_experts == 128
    assert get_config("arctic-480b").experts_per_token == 2
    assert get_config("arctic-480b").moe_dense_residual
    assert get_config("qwen3-moe-235b-a22b").num_experts == 128
    assert get_config("qwen3-moe-235b-a22b").experts_per_token == 8
    assert get_config("mamba2-130m").ssm_state == 128


def test_qwen2_5_3b_param_count_matches_published():
    """Qwen2.5-3B ties its embeddings; the published size is 3.09B."""
    cfg = get_config("qwen2.5-3b")
    assert cfg.tie_embeddings
    assert abs(cfg.param_count() / 3.09e9 - 1.0) < 0.01
