"""Compile-only checks: every Pallas kernel, and the XLA cache lookup,
compiled for one TPU v5e chip.

Each kernel is lowered with ``interpret=False`` at the widths the served
path uses (the cosine lookups over a 262,144-row bank of the 64-wide
embedder, the attention kernels at Qwen2.5-3B's 16 query heads, 2 KV
heads and head_dim 128 in bf16) and compiled by the TPU compiler for a
chip that is described, not attached.  A block shape the chip's tiling
rules refuse, or a primitive Mosaic cannot lower, fails here without a
chip.  Nothing runs, so results are not checked: ``chip_smoke.py`` does
that on the chip.  The XLA lookup is compiled at the served bank's
size to check what it lowers to.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cosine_topk.kernel import (cosine_topk_gather_pallas,
                                              cosine_topk_pallas)
from repro.kernels.cosine_topk.ops import cosine_topk
from repro.kernels.decode_attention.kernel import (
    decode_attention_block_pallas, decode_attention_pallas)
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.paged_attention.kernel import (
    paged_decode_attention_block_pallas, paged_decode_attention_pallas)

BANK, DIM, CAND = 262_144, 64, 2048          # lookups
SERVED_BANK, MAX_BATCH = 1_048_576, 32        # the benchmark's flat bank
B, H, HK, DH = 8, 16, 2, 128                 # Qwen2.5-3B attention
T, PAGE, SEQ, SPEC_K = 2048, 16, 512, 4
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
POOL = B * (T // PAGE) + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


KERNELS = {
    "cosine_topk": (
        lambda q, db, v: cosine_topk_pallas(q, db, 4, v, block_n=1024,
                                            interpret=False),
        [((B, DIM), F32), ((BANK, DIM), F32), ((BANK,), jnp.bool_)]),
    "cosine_topk_gather": (
        lambda q, c, i, v: cosine_topk_gather_pallas(
            q, c, i, v, 4, block_m=256, interpret=False),
        [((B, DIM), F32), ((B, CAND, DIM), F32), ((B, CAND), I32),
         ((B, CAND), jnp.bool_)]),
    "decode_attention": (
        lambda q, k, v, n: decode_attention_pallas(q, k, v, n, block_t=1024,
                                                   interpret=False),
        [((B, H, DH), BF16), ((B, T, HK, DH), BF16), ((B, T, HK, DH), BF16),
         ((B,), I32)]),
    "decode_attention_block": (
        lambda q, k, v, n: decode_attention_block_pallas(
            q, k, v, n, block_t=1024, interpret=False),
        [((B, SPEC_K, H, DH), BF16), ((B, T, HK, DH), BF16),
         ((B, T, HK, DH), BF16), ((B,), I32)]),
    "paged_attention": (
        lambda q, kp, vp, t, sp: paged_decode_attention_pallas(
            q, kp, vp, t, sp, interpret=False),
        [((B, H, DH), BF16), ((POOL, PAGE, HK, DH), BF16),
         ((POOL, PAGE, HK, DH), BF16), ((B, T // PAGE), I32), ((B, T), I32)]),
    "paged_attention_block": (
        lambda q, kp, vp, t, sp, qp: paged_decode_attention_block_pallas(
            q, kp, vp, t, sp, qp, interpret=False),
        [((B, SPEC_K, H, DH), BF16), ((POOL, PAGE, HK, DH), BF16),
         ((POOL, PAGE, HK, DH), BF16), ((B, T // PAGE), I32), ((B, T), I32),
         ((B,), I32)]),
    "flash_attention": (
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               interpret=False),
        [((B, SEQ, H, DH), BF16), ((B, SEQ, HK, DH), BF16),
         ((B, SEQ, HK, DH), BF16)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_lookup_sorts_no_bank_wide_row(one_chip, no_persistent_cache):
    """The XLA lookup over the served 1,048,576-row bank selects its top-4
    by chunks: no sort (nor TopK) runs over a whole row of scores."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in
            [((MAX_BATCH, DIM), F32), ((SERVED_BANK, DIM), F32),
             ((SERVED_BANK,), jnp.bool_)]]
    hlo = jax.jit(lambda q, db, v: cosine_topk(q, db, v, k=4, impl="xla")
                  ).lower(*args).compile().as_text()
    shape = dict(re.findall(r"(%[\w.-]+) = \(?\w+\[([\d,]*)\]", hlo))
    selects = [m for m in re.finditer(
        r" (sort|custom-call)\(([^)]*)\)(.*)", hlo)
        if m[1] == "sort" or 'custom_call_target="TopK"' in m[3]]
    assert selects
    for m in selects:
        for operand in m[2].split(", "):
            dims = shape[operand].split(",")
            assert str(SERVED_BANK) not in dims, (operand, dims)
