"""Shared-prefix KV reuse in prefill (DESIGN.md §9).

The contract under test: ``prefill_with_prefix(suffix, prefix_cache)``
must be BYTE-identical to ``prefill([prefix | suffix])`` — logits, every
cache leaf, and (through the fused decode loop) the full generated
output — across batch and suffix-length buckets.  Plus the serving-layer
pieces that ride on it: cue-preserving truncation of over-long tweak
prompts, prompt-token accounting, explicit fallback for architectures
that can't guarantee the bitwise contract, and stale-prefix-cache
rebuild when the small generator is swapped.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st
from repro.configs import get_config
from repro.core import CacheConfig, RouterConfig, TweakLLMEngine
from repro.core import tweak as tweak_lib
from repro.models import ModelConfig, build_model
from repro.serving import GenerateConfig, Generator, SamplerConfig
from repro.tokenizer import HashWordTokenizer

VOCAB = 512
EOS = 2


def _flash_cfg(**kw):
    base = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                d_ff=128, vocab_size=VOCAB, max_seq_len=1024,
                dtype="float32", attention_impl="xla_flash",
                flash_block_q=32, flash_block_k=32)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def lm():
    cfg = _flash_cfg()
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _generator(model, params, *, mnt=8, temperature=0.0, vocab=VOCAB):
    gc = GenerateConfig(max_new_tokens=mnt, eos_id=EOS,
                        sampler=SamplerConfig(temperature=temperature,
                                              vocab_size=vocab))
    return Generator(model, params, gc)


def _prefix_suffix(b, p, s, seed=1, vocab=VOCAB):
    pre = jax.random.randint(jax.random.PRNGKey(seed), (1, p), 5, vocab)
    suf = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, s), 5, vocab)
    return jnp.broadcast_to(pre, (b, p)), suf


# Float tolerance of the prefill-level differential.  The prefix-only
# pass projects P token rows and the full pass P + S, and XLA's CPU dot
# picks its kernel, and so its reduction order, from the row count: the
# same row can come out a few float32 ulps apart (up to ~2e-6 on logits
# of order 1 with jax 0.9).  That is reduction order, not a reuse bug;
# a wrong prefix KV moves logits by O(0.1).  Integer leaves (slot
# positions, fill counters) still compare exactly.
PREFILL_ATOL = 1e-5
PREFILL_RTOL = 1e-5


def _assert_trees_close(a, b):
    def check(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, rtol=PREFILL_RTOL,
                                       atol=PREFILL_ATOL)
        else:
            np.testing.assert_array_equal(x, y)
    jax.tree.map(check, a, b)


# ------------------------------------------- prefill-level differential
@pytest.mark.parametrize("b,p,s", [(1, 45, 16), (2, 45, 32), (4, 45, 16),
                                   (4, 7, 128), (8, 45, 64)])
def test_prefix_prefill_bitwise_matches_full(lm, b, p, s):
    m, params = lm
    pre, suf = _prefix_suffix(b, p, s)
    cap = p + s + 9
    lf, cf = m.prefill(params, {"tokens": jnp.concatenate([pre, suf], 1)},
                       cap)
    prefix = m.prefill_prefix(params, pre)
    lp, cp = m.prefill_with_prefix(params, {"tokens": suf}, cap, prefix)
    _assert_trees_close(lf, lp)
    _assert_trees_close(cf, cp)


# ------------------------------------------- full-generation differential
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_prefix_generate_bitwise_matches_full(lm, temperature):
    """prefix-reuse prefill -> fused decode == full prefill -> fused decode:
    same tokens, lengths, ended flags, under greedy AND temperature
    sampling with fixed seeds."""
    m, params = lm
    gen = _generator(m, params, mnt=8, temperature=temperature)
    b, p, s = 4, 45, 32
    pre, suf = _prefix_suffix(b, p, s, seed=3)
    pc = gen.build_prefix_cache([int(t) for t in np.asarray(pre[0])], b)
    ft = gen.generate_with_lengths(
        {"tokens": jnp.concatenate([pre, suf], 1)}, max_new_tokens=8, seed=5)
    pt = gen.generate_with_lengths({"tokens": suf}, max_new_tokens=8, seed=5,
                                   prefix_cache=pc)
    for a, c in zip(ft, pt):
        np.testing.assert_array_equal(a, c)


def test_prefix_generate_matches_host_loop_oracle(lm):
    """Transitivity with the PR-4 oracle: prefix-reuse fused decode ==
    host-driven per-step decode of the concatenated prompt."""
    m, params = lm
    gen = _generator(m, params, mnt=6)
    b, p, s = 2, 45, 16
    pre, suf = _prefix_suffix(b, p, s, seed=7)
    pc = gen.build_prefix_cache([int(t) for t in np.asarray(pre[0])], b)
    pt = gen.generate_with_lengths({"tokens": suf}, max_new_tokens=6, seed=2,
                                   prefix_cache=pc)
    ht = gen.generate_with_lengths(
        {"tokens": jnp.concatenate([pre, suf], 1)}, max_new_tokens=6, seed=2,
        fused=False)
    for a, c in zip(pt, ht):
        np.testing.assert_array_equal(a, c)


def test_prefix_cache_batch_mismatch_raises(lm):
    m, params = lm
    gen = _generator(m, params)
    pre, suf = _prefix_suffix(2, 45, 16)
    pc = gen.build_prefix_cache([int(t) for t in np.asarray(pre[0])], 2)
    with pytest.raises(ValueError, match="batch"):
        gen.generate_with_lengths({"tokens": suf[:1]}, max_new_tokens=4,
                                  prefix_cache=pc)


# ------------------------------------------- hypothesis property
@given(st.data())
@settings(max_examples=10, deadline=None)
def test_prefix_prefill_equivalence_property(lm, data):
    """Bitwise prefix-reuse == full across sampled (batch, suffix bucket,
    prefix length, seed).  Shapes come from a small fixed grid so jit
    compiles stay bounded."""
    m, params = lm
    b = data.draw(st.sampled_from([1, 2, 4]), label="batch")
    s = data.draw(st.sampled_from([16, 32, 64]), label="suffix")
    p = data.draw(st.sampled_from([7, 45]), label="prefix")
    seed = data.draw(st.integers(min_value=0, max_value=2 ** 16), label="seed")
    pre, suf = _prefix_suffix(b, p, s, seed=seed % 97 + 1)
    gen = _generator(m, params, mnt=4)
    pc = gen.build_prefix_cache([int(t) for t in np.asarray(pre[0])], b)
    ft = gen.generate_with_lengths(
        {"tokens": jnp.concatenate([pre, suf], 1)}, max_new_tokens=4,
        seed=seed)
    pt = gen.generate_with_lengths({"tokens": suf}, max_new_tokens=4,
                                   seed=seed, prefix_cache=pc)
    for a, c in zip(ft, pt):
        np.testing.assert_array_equal(a, c)


# ------------------------------------------- explicit arch fallback
def test_unsupported_archs_report_and_raise():
    """Recurrent / windowed / naive-softmax models must say NO (and raise
    rather than silently degrade) — callers fall back to full prefill."""
    cases = [
        get_config("mamba2-130m", smoke=True),                  # SSM
        _flash_cfg(attention_impl="naive"),                     # reassociates
        _flash_cfg(attention_impl="auto"),                      # -> naive
        _flash_cfg(sliding_window=8),                           # windowed
    ]
    for cfg in cases:
        m = build_model(cfg)
        assert not m.supports_prefix_prefill, cfg.name
        with pytest.raises(NotImplementedError):
            m.prefill_prefix(None, jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError):
            m.prefill_with_prefix(None, {"tokens": jnp.zeros((1, 4),
                                                             jnp.int32)},
                                  16, None)


def test_supported_arch_reports_yes(lm):
    m, _ = lm
    assert m.supports_prefix_prefill
    gen = _generator(m, None)
    assert gen.supports_prefix_prefill


# ------------------------------------------- engine integration
VOCAB_E = 4096


def _engine_stack(small_cfg=None, **router_kw):
    from repro.models.embedder import init_embedder, tiny_embedder_config
    tok = HashWordTokenizer(VOCAB_E)
    ecfg = tiny_embedder_config(VOCAB_E)
    ep = init_embedder(jax.random.PRNGKey(0), ecfg)
    lm_cfg = _flash_cfg(vocab_size=VOCAB_E, max_seq_len=512)
    gc = GenerateConfig(max_new_tokens=6,
                        sampler=SamplerConfig(vocab_size=VOCAB_E))
    big_m = build_model(lm_cfg)
    small_m = build_model(small_cfg or lm_cfg.replace(num_layers=1))
    big = Generator(big_m, big_m.init(jax.random.PRNGKey(1)), gc)
    small = Generator(small_m, small_m.init(jax.random.PRNGKey(2)), gc)
    eng = TweakLLMEngine(
        tokenizer=tok, embedder_params=ep, embedder_cfg=ecfg,
        big=big, small=small,
        cache_cfg=CacheConfig(capacity=64, dim=ecfg.d_model, topk=4),
        router_cfg=RouterConfig(**router_kw))
    return eng


def _seed_tweak_traffic(eng, n=3):
    eng.populate([f"seeded question number {i} about topic {i}"
                  for i in range(n)],
                 [f"cached answer {i} " + "filler word " * (3 * i)
                  for i in range(n)])
    return eng.handle_batch_result(
        ["a fresh question about something else",
         "yet another question on a new theme",
         "a third distinct question arrives"], max_new_tokens=4)


def test_engine_tweak_uses_prefix_cache_and_buckets():
    eng = _engine_stack(tweak_threshold=-1.0)   # everything routes TWEAK
    assert eng._prefix_path_available()
    res = _seed_tweak_traffic(eng)
    assert eng.stats.tweak == 3
    assert eng._prefix_caches                    # prefix KV was built
    pc = next(iter(eng._prefix_caches.values()))
    assert pc.token_ids == eng._tweak_prefix_ids()
    assert all(isinstance(r, str) and r for r in res.responses)
    # prompt accounting: every tweak row billed prefix + real suffix
    p = len(eng._tweak_prefix_ids())
    assert res.small_prompt_tokens >= 3 * (p + 1)
    assert eng.stats.small_prompt_tokens == res.small_prompt_tokens


def test_engine_prefix_toggle_serves_both_paths():
    """use_prefix_cache=False forces the full-prompt fallback; both paths
    must serve the same traffic and bill identical PROMPT token totals
    (same real prompt content, different prefill strategy)."""
    a = _engine_stack(tweak_threshold=-1.0)
    b = _engine_stack(tweak_threshold=-1.0)
    b.use_prefix_cache = False
    ra = _seed_tweak_traffic(a)
    rb = _seed_tweak_traffic(b)
    assert a.stats.tweak == b.stats.tweak == 3
    assert a._prefix_caches and not b._prefix_caches
    assert ra.small_prompt_tokens == rb.small_prompt_tokens
    assert [len(r) > 0 for r in ra.responses] == \
        [len(r) > 0 for r in rb.responses]


def test_engine_fallback_arch_serves_tweak_without_prefix():
    """A mamba2 small model can't do prefix prefill: the engine must fall
    back explicitly (no prefix caches) and still serve the TWEAK path."""
    cfg = get_config("mamba2-130m", smoke=True)
    eng = _engine_stack(small_cfg=cfg.replace(vocab_size=VOCAB_E,
                                              max_seq_len=512),
                        tweak_threshold=-1.0)
    assert not eng._prefix_path_available()
    res = _seed_tweak_traffic(eng)
    assert eng.stats.tweak == 3
    assert not eng._prefix_caches
    assert all(isinstance(r, str) and r for r in res.responses)
    assert eng.stats.small_prompt_tokens > 0


def test_stale_prefix_cache_rebuilt_on_generator_swap():
    """Swapping the small generator (new model/sampler config) must
    invalidate the cached prefix KV — a stale prefix would corrupt every
    subsequent tweak response silently."""
    eng = _engine_stack(tweak_threshold=-1.0)
    _seed_tweak_traffic(eng)
    old = dict(eng._prefix_caches)
    old_sig = eng._prefix_sig
    assert old
    # same arch, different params + different sampler config
    lm_cfg = _flash_cfg(vocab_size=VOCAB_E, max_seq_len=512, num_layers=1,
                        rope_theta=20_000.0)
    m2 = build_model(lm_cfg)
    eng.small = Generator(m2, m2.init(jax.random.PRNGKey(9)),
                          GenerateConfig(max_new_tokens=6,
                                         sampler=SamplerConfig(
                                             temperature=0.5,
                                             vocab_size=VOCAB_E)))
    eng.handle_batch(["a question that routes to tweak again"],
                     max_new_tokens=4)
    assert eng._prefix_sig != old_sig
    for bucket, pc in old.items():
        assert eng._prefix_caches.get(bucket) is not pc


# ------------------------------------------- truncation bugfix
def test_overlong_cached_response_keeps_adapted_cue():
    """Regression: encode_batch tail-truncation used to cut the trailing
    'adapted response :' cue off over-long tweak prompts.  Truncation must
    come out of the cached-response field instead."""
    tok = HashWordTokenizer(VOCAB_E)
    long_resp = " ".join(f"filler{i}" for i in range(500))
    toks, mask = tweak_lib.build_tweak_batch(
        tok, ["the new question"], ["the old question"], [long_resp], 128)
    row = toks[0][mask[0] > 0].tolist()
    assert len(row) == 128                       # budget filled exactly
    cue = tok.encode(". adapted response :", add_bos=False)
    assert row[-len(cue):] == cue                # cue survives at the end
    nq = tok.encode("the new question", add_bos=False)
    as_str = ",".join(map(str, row))
    assert ",".join(map(str, nq)) in as_str      # new query survives whole
    # suffix variant preserves the cue too
    stoks, smask = tweak_lib.build_tweak_suffix_batch(
        tok, ["the new question"], ["the old question"], [long_resp], 64)
    srow = stoks[0][smask[0] > 0].tolist()
    assert srow[-len(cue):] == cue


def test_truncation_never_drops_statics_raises_when_impossible():
    tok = HashWordTokenizer(VOCAB_E)
    with pytest.raises(ValueError, match="static"):
        tweak_lib.build_tweak_batch(tok, ["q"], ["cq"], ["cr"], 8)


def test_static_overflow_rejected_before_any_state_mutation():
    """A budget that passes the bucket math but can't fit the static
    segments must fail the up-front handle_batch validation — NOT raise
    out of truncation mid-serve, after lookup touched recency and stats
    were partially billed."""
    eng = _engine_stack(tweak_threshold=-1.0)
    eng.populate(["a seeded question about pottery"], ["a cached answer"])
    msl = eng.small.model.cfg.max_seq_len          # 512 in this stack
    statics = eng._tweak_static_tokens()
    assert statics > 16
    # budget 16 fits the bucket check (16 + 495 + 1 <= 512) but not the
    # static segments
    before = (eng.stats.total, eng.stats.exact,
              eng.stats.baseline_prompt_tokens)
    with pytest.raises(ValueError, match="static"):
        eng.handle_batch(["anything routes to tweak"],
                         max_new_tokens=msl - 17)
    assert (eng.stats.total, eng.stats.exact,
            eng.stats.baseline_prompt_tokens) == before


def test_stale_prefix_cache_rebuilt_on_checkpoint_swap_same_config():
    """Swapping the small generator for one with IDENTICAL configs but
    different weights (checkpoint reload) must still invalidate the
    prefix KV — config equality alone cannot see the new params."""
    eng = _engine_stack(tweak_threshold=-1.0)
    _seed_tweak_traffic(eng)
    old_sig = eng._prefix_sig
    old = dict(eng._prefix_caches)
    assert old
    m2 = build_model(eng.small.model.cfg)          # same config
    eng.small = Generator(m2, m2.init(jax.random.PRNGKey(33)),
                          eng.small.cfg)           # same generate config
    eng.handle_batch(["a further question that routes to tweak"],
                     max_new_tokens=4)
    assert eng._prefix_sig != old_sig
    for bucket, pc in old.items():
        assert eng._prefix_caches.get(bucket) is not pc


# ------------------------------------------- prompt-token accounting
def test_prompt_token_accounting_miss_and_exact():
    eng = _engine_stack()          # default router: fresh queries MISS
    res = eng.handle_batch_result(["a totally novel question about chess"],
                                  max_new_tokens=4)
    s = eng.stats
    assert s.big_prompt_tokens > 0                   # real, unpadded
    assert s.big_prompt_tokens <= eng.max_query_len
    assert res.big_prompt_tokens == s.big_prompt_tokens
    assert s.baseline_prompt_tokens == s.big_prompt_tokens
    base = s.baseline_prompt_tokens
    # EXACT repeat: no LLM prompt billed, but the all-Big baseline would
    # still have ingested the query
    eng.handle_batch(["a totally novel question about chess"],
                     max_new_tokens=4)
    assert s.big_prompt_tokens == res.big_prompt_tokens
    assert s.baseline_prompt_tokens > base
    assert s.cost < s.baseline_cost
