"""What the benchmark knows of the ``dense`` family's language model: its
plain float32 reference, its fp8 control, the fields the program's
configuration must hold, and how its weights are drawn.

The harness finds this module by a configuration's ``family``
(``reference/<family>.py``).  A module for another family exports the
same four names:

- ``KEYS``: the keys of the configuration's ``model`` section that its
  forward reads (``check.logit_gaps`` passes just these);
- ``served_gaps(params, seqs, m, precision="f32", targets=None)``: per
  (prompt, served) sequence, the gap by which each served token's logit
  lies below the reference's best, and the reference's argmax there;
  ``precision="fp8"`` is the control one step below the stated dtype;
- ``program_fields(m)``: ``ModelConfig`` attribute -> the value the
  program must serve, from the ``model`` section (``harness.check_config``);
- ``FAN_IN``: leaf name -> the axes (counted from the end) whose product
  is the fan-in, for the leaves the family adds beyond the table shared
  in ``bench/weights.py``; empty here.

A straight-line forward of the stack the ``dense`` configurations state
(RMSNorm, grouped-query attention with QKV bias, rotate-half rotary
positions, SwiGLU, tied or separate output head), written from the
published description and sharing no code with the program.  It reads
the weights the benchmark made (``bench/weights.py``), upcasts one layer
at a time inside the layer scan, and runs every matmul at ``highest``
precision, so a bf16 model never has a whole float32 copy on the device.

``precision="fp8"`` is the control: the same forward with every weight
and every matmul input rounded to float8 e4m3, the step below the
bfloat16 the configuration states.

Position ``p`` of a sequence attends to positions ``0..p``; a prompt the
program right-padded is read with its padding, as the program fed it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "vocab_size", "tie_word_embeddings")
# every leaf of the dense stack is in the shared table
FAN_IN = {}


def program_fields(m) -> dict:
    """``ModelConfig`` attribute -> the value the configuration states."""
    return {"d_model": m["hidden_size"], "num_layers": m["num_hidden_layers"],
            "num_heads": m["num_attention_heads"],
            "num_kv_heads": m["num_key_value_heads"],
            "d_ff": m["intermediate_size"], "vocab_size": m["vocab_size"],
            "rope_theta": m["rope_theta"],
            "tie_embeddings": m["tie_word_embeddings"],
            "dtype": m["torch_dtype"],
            "resolved_head_dim": (m.get("head_dim")
                                  or m["hidden_size"]
                                  // m["num_attention_heads"])}


def _q(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, dh) at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, m, precision):
    s = x.shape[1]
    eps = m["rms_norm_eps"]
    group = m["num_attention_heads"] // m["num_key_value_heads"]
    h = _rms(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = _mm("bsd,dhk->bshk", h, a["w_q"], precision) + a["b_q"]
    k = _mm("bsd,dhk->bshk", h, a["w_k"], precision) + a["b_k"]
    v = _mm("bsd,dhk->bshk", h, a["w_v"], precision) + a["b_v"]
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, group, axis=2)       # query head i reads KV head i // g
    v = jnp.repeat(v, group, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(q.shape[-1])
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    ctx = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision)
    x = x + _mm("bshk,hkd->bsd", ctx, a["w_o"], precision)
    h = _rms(x, p["norm2"]["scale"], eps)
    f = p["mlp"]
    up = (jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"], precision))
          * _mm("bsd,df->bsf", h, f["w_up"], precision))
    return x + _mm("bsf,fd->bsd", up, f["w_down"], precision)


def hidden(params, tokens, m, precision="f32"):
    """Final-normed hidden states (B, S, d) in float32."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, layer_params):
        return _layer(x, f32(layer_params), m, precision), None

    x, _ = jax.lax.scan(body, x, params["scan"][0])
    return _rms(x, params["final_norm"]["scale"].astype(jnp.float32),
                m["rms_norm_eps"])


def _head(params, m):
    if m["tie_word_embeddings"]:
        return params["embed"][:m["vocab_size"]].T
    return params["lm_head"][:, :m["vocab_size"]]


@functools.partial(jax.jit, static_argnames=("m", "precision", "chunk"))
def _gaps(params, tokens, targets, m, precision, chunk):
    """For each position: (best logit, logit of ``targets``, argmax id)."""
    mm = dict(m)
    x = hidden(params, tokens, mm, precision)
    w = _head(params, mm)
    b, s, d = x.shape
    xs = x.reshape(b, s // chunk, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, s // chunk, chunk).transpose(1, 0, 2)

    def one(args):
        xc, tc = args
        logits = _mm("bcd,dv->bcv", xc, w.astype(jnp.float32), precision)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
        return best, got, jnp.argmax(logits, -1).astype(jnp.int32)

    best, got, arg = jax.lax.map(one, (xs, ts))
    fix = lambda a: a.transpose(1, 0, 2).reshape(b, s)
    return fix(best), fix(got), fix(arg)


def served_gaps(params, seqs, m, precision="f32", chunk=64, targets=None,
                length=512, rows=8):
    """Gap by which each served token's logit lies below the best.

    ``seqs``: list of (prompt ids, served ids), run as one batch,
    right-padded to a multiple of ``chunk``.  Returns, per sequence,
    (gaps (n,), argmax ids (n,)) at the n positions that predict the
    served tokens.  ``targets`` (one id array per sequence) reads the gap
    of other ids at the same positions, over the same context.  The batch
    is padded to at least ``rows`` x ``length`` so that one program serves
    every run.
    """
    lens = [len(p) + len(t) for p, t in seqs]
    s = max(length, -(-max(lens) // length) * length)
    n = max(rows, len(seqs))
    toks = np.zeros((n, s), np.int32)
    tgts = np.zeros((n, s), np.int32)
    for i, (p, t) in enumerate(seqs):
        full = list(p) + list(t)
        toks[i, :len(full)] = full
        # position j predicts token j + 1
        want = t if targets is None else targets[i]
        tgts[i, len(p) - 1:len(full) - 1] = want
    key = tuple(sorted(m.items()))
    best, got, arg = jax.device_get(_gaps(params, jnp.asarray(toks),
                                          jnp.asarray(tgts), key, precision,
                                          chunk))
    out = []
    for i, (p, t) in enumerate(seqs):
        sl = slice(len(p) - 1, len(p) - 1 + len(t))
        out.append((best[i, sl] - got[i, sl], arg[i, sl]))
    return out
