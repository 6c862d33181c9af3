"""Plain float32 reference forward for global-attention decoder stacks.

An independent straight-line implementation of what ``transformer.prefill``
computes for a stack of ATTN blocks (RMSNorm, GQA with optional QKV bias,
rotary positions, SwiGLU, tied or separate LM head).  It shares no code
with the served model: it reads the same parameter pytree, upcasts each
layer's weights to float32 inside the layer scan (so a bf16 model never
has a whole float32 copy on the device), and runs every matmul at
``highest`` precision.  Served logits are judged against it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import ATTN, ModelConfig

_EPS = 1e-6


def _rms_norm(x, scale):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + _EPS) * scale


def _rope(x, theta: float):
    """x (B, S, H, dh) at positions 0..S-1, rotate-half convention."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, cfg: ModelConfig):
    b, s, _ = x.shape
    g = cfg.q_per_kv
    h = _rms_norm(x, p["norm1"]["scale"])
    a = p["attn"]
    q = jnp.einsum("bsd,dhk->bshk", h, a["w_q"])
    k = jnp.einsum("bsd,dhk->bshk", h, a["w_k"])
    v = jnp.einsum("bsd,dhk->bshk", h, a["w_v"])
    if cfg.qkv_bias:
        q, k, v = q + a["b_q"], k + a["b_k"], v + a["b_v"]
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    # query head i reads KV head i // g
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("bshk,hkd->bsd", ctx, a["w_o"])
    h = _rms_norm(x, p["norm2"]["scale"])
    m = p["mlp"]
    up = jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])
    return x + up @ m["w_down"]


def last_logits_f32(params, tokens, cfg: ModelConfig):
    """Float32 logits at the last position: tokens (B, S) -> (B, vocab).

    Covers the configs the served path runs at full width: a scanned
    stack of global ATTN blocks with RMSNorm and SwiGLU.
    """
    if (tuple(cfg.block_pattern) != (ATTN,) or cfg.sliding_window
            or cfg.norm_type != "rmsnorm" or cfg.mlp_type != "swiglu"
            or cfg.logits_softcap or cfg.frontend != "none"):
        raise NotImplementedError(
            f"{cfg.name}: the float32 reference covers global-attention "
            f"RMSNorm/SwiGLU decoder stacks only")
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

        def body(x, layer_params):
            return _layer(x, f32(layer_params), cfg), None

        x, _ = jax.lax.scan(body, x, params["scan"][0])
        x = _rms_norm(x[:, -1], params["final_norm"]["scale"])
        if cfg.tie_embeddings:
            logits = x @ params["embed"].astype(jnp.float32).T
        else:
            logits = x @ params["lm_head"].astype(jnp.float32)
    return logits[:, :cfg.vocab_size]
