"""Layer-2 analyzer self-tests: the contract checks pass on the real hot
paths, and each checker demonstrably CATCHES a seeded violation — a
dropped donation, an f64 promotion, a host callback, a shape leak past
the bucket set, and an unrolled decode loop (DESIGN.md §10).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts
from repro.analysis.contracts import (
    callback_eqns, check_recompiles, check_traced, has_donation, run_all,
    wide_dtype_vars, while_count,
)
from repro.core import cache as cache_lib


# ------------------------------------------------- the real paths pass --

def test_all_contracts_clean_on_head():
    failures = run_all()
    assert failures == [], "\n".join(failures)


def test_contract_names_cover_the_registered_hot_paths():
    names = [n for n, _ in contracts.CONTRACTS]
    assert names == ["lookup_and_touch", "insert_batch", "ivf_lookup",
                     "fused_decode", "prefix_suffix_prefill"]


# -------------------------------------------- seeded violations caught --

def _insert_args(cfg, b=2):
    return (cache_lib.init_cache(cfg), contracts._unit_rows(b),
            jnp.zeros((b, cfg.max_query_tokens), jnp.int32),
            jnp.ones((b, cfg.max_query_tokens), jnp.float32),
            jnp.zeros((b, cfg.max_response_tokens), jnp.int32),
            jnp.ones((b, cfg.max_response_tokens), jnp.float32),
            jnp.asarray(2, jnp.int32))


def test_dropped_donation_is_caught():
    cfg = contracts._cache_cfg()
    no_donate = cache_lib.make_insert_batch(cfg, donate=False)
    tr = no_donate.trace(*_insert_args(cfg))
    assert not has_donation(tr.lower().as_text())
    failures = check_traced("insert_batch", tr, expect_donation=True)
    assert len(failures) == 1 and "donation was dropped" in failures[0]
    # ... and the donating build keeps the aliasing the registry declares
    donating = cache_lib.make_insert_batch(cfg)
    assert has_donation(donating.trace(*_insert_args(cfg)).lower().as_text())


def test_unexpected_donation_is_caught():
    # a read-only path that suddenly aliases its input is just as wrong
    jitted = jax.jit(lambda s: s * 2.0, donate_argnums=(0,))
    tr = jitted.trace(jnp.ones((4, 4)))
    failures = check_traced("ro_path", tr, expect_donation=False)
    assert len(failures) == 1 and "unexpected" in failures[0]


def test_f64_promotion_is_caught():
    with jax.enable_x64(True):  # lowering must also happen inside the x64 scope
        tr = jax.jit(lambda x: x.astype(jnp.float64) * 2.0).trace(
            jnp.ones((4,), jnp.float32))
        failures = check_traced("widened", tr)
    assert any("float64" in w for w in wide_dtype_vars(tr.jaxpr))
    assert len(failures) == 1 and "64-bit" in failures[0]


def test_host_callback_is_caught():
    def host_fn(x):
        return np.asarray(x)

    def f(x):
        return jax.pure_callback(
            host_fn, jax.ShapeDtypeStruct((4,), jnp.float32), x)

    tr = jax.jit(f).trace(jnp.ones((4,), jnp.float32))
    assert callback_eqns(tr.jaxpr) == ["pure_callback"]
    failures = check_traced("cb_path", tr)
    assert len(failures) == 1 and "callback" in failures[0]


def test_callback_found_inside_scan_body():
    # iter_eqns must recurse into sub-jaxprs: a callback hidden in a
    # lax.scan body is still a per-iteration host round-trip
    def f(x):
        def body(c, _):
            y = jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct((), jnp.float32),
                c)
            return y, y
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    tr = jax.jit(f).trace(jnp.float32(1.0))
    assert "pure_callback" in callback_eqns(tr.jaxpr)


def test_shape_leak_fails_the_recompile_gate():
    jitted = jax.jit(lambda x: x * 2.0)
    for b in (1, 2, 4):          # pretend the bucket set is (1, 2) ...
        jax.block_until_ready(jitted(jnp.ones((b, 4))))
    failures = check_recompiles("leaky", jitted, calls=2)
    assert len(failures) == 1 and "shape/dtype leak" in failures[0]


def test_under_exercised_bucket_set_also_fails():
    jitted = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(jitted(jnp.ones((2, 4))))
    failures = check_recompiles("partial", jitted, calls=3)
    assert len(failures) == 1 and "under-exercised" in failures[0]


def test_unrolled_decode_loop_is_caught():
    # no while primitive in the jaxpr -> the fused-decode contract fails
    tr = jax.jit(lambda x: x * 2.0).trace(jnp.ones((4,)))
    assert while_count(tr.jaxpr) == 0
    failures = check_traced("decode", tr, expect_while=True)
    assert len(failures) == 1 and "while_loop" in failures[0]
    with_loop = jax.jit(lambda x: jax.lax.while_loop(
        lambda c: c[0] < 3, lambda c: (c[0] + 1, c[1] * 2.0), (0, x)))
    tr2 = with_loop.trace(jnp.ones((4,)))
    assert while_count(tr2.jaxpr) == 1
    assert check_traced("decode", tr2, expect_while=True) == []


def test_cli_reports_clean(capsys):
    assert contracts.main([]) == 0
    assert "hot paths clean" in capsys.readouterr().out
