"""The model family's reference module, chosen by a configuration's
``family``, gives the language-model check, the configuration check and
the weight rules; a family without one stops at set-up.  For the dense
family each gives what the harness gave before it was chosen so: the same
keys, fields and draws, to the bit."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_cell
from bench import check, harness, weights

# the model keys the check passed to the dense reference before it was
# chosen by family
DENSE_KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
              "rope_theta", "vocab_size", "tie_word_embeddings")
ROUTER_KEYS = ("tweak_threshold", "exact_threshold", "default_cost",
               "cal_span", "band", "probe_temp", "w_agree", "w_rerank",
               "commit_at")


def _dense_weights():
    from repro.models.model import build_model
    return weights.make(build_model(tiny_cell.tiny_model_config()).init,
                        tiny_cell.SEED)


@pytest.mark.parametrize("control", [False, True])
def test_dense_gap_through_the_family_loader_is_the_direct_one(control):
    from bench.reference import dense
    w = _dense_weights()
    rng = np.random.default_rng(5)
    rows = [{"kind": "miss", "prompt": rng.integers(5, 512, n).tolist(),
             "served": rng.integers(5, 512, 6).tolist()} for n in (7, 19)]
    got = check.logit_gaps(w, rows, tiny_cell.TINY_MODEL, "dense",
                           control=control)
    m = {k: tiny_cell.TINY_MODEL[k] for k in DENSE_KEYS}
    seqs = [(r["prompt"], r["served"]) for r in rows]
    targets = None
    if control:
        targets = [a for _, a in dense.served_gaps(w, seqs, m,
                                                   precision="fp8")]
    want = float(max(g.max() for g, _ in dense.served_gaps(
        w, seqs, m, targets=targets)))
    assert got["gen_logit_gap"] == want
    assert got["gen_logit_gap"] > 0.01       # random tokens are not greedy
    assert got["tokens"] == 12


def test_family_without_a_module_stops_at_set_up(monkeypatch, capsys):
    import repro.launch.serve as serve
    from bench import run
    drawn, built = [], []
    monkeypatch.setattr(weights, "make", lambda *a, **k: drawn.append(a))
    tiny_cell.patch_program(monkeypatch)
    monkeypatch.setattr(serve, "build_stack",
                        lambda **k: built.append(k))
    cell = tiny_cell.cell()
    cell.config["family"] = "sparse-latent"
    with pytest.raises(FileNotFoundError,
                       match=r"bench/reference/sparse-latent\.py"):
        run.main(["--workload", tiny_cell.STAND_IN, "--seed",
                  str(tiny_cell.SEED), "--seconds", "1", "--trace", "0"],
                 cell=cell, require_tpu=False)
    assert drawn == [] and built == []
    assert capsys.readouterr().out == ""


def _draw_as_before(init_fn, seed, stream="weights"):
    """The draw before family rules existed: one fixed table of names."""
    fan_in = {"w_q": (-3,), "w_k": (-3,), "w_v": (-3,), "w_o": (-3, -2),
              "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
              "score_head": (-2,)}
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for path, sd in leaves:
        name = weights._leaf_name(path)
        if name in ("scale", "bias"):
            plan.append((name, 0.0))
        else:
            std = (0.1 if name.startswith("b_") else 0.02 if name == "embed"
                   else int(np.prod([sd.shape[a] for a in fan_in[name]]))
                   ** -0.5)
            plan.append(("normal", std))

    def draw(key):
        out = []
        for i, ((_, sd), (kind, std)) in enumerate(zip(leaves, plan)):
            if kind != "normal":
                fill = jnp.ones if kind == "scale" else jnp.zeros
                out.append(fill(sd.shape, sd.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((std * jax.random.normal(k, sd.shape, jnp.float32)
                            ).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(weights.jax_key(seed, stream))


def _trees():
    from repro.models.embedder import init_embedder, tiny_embedder_config
    from repro.models.model import build_model
    from repro.models.reranker import init_reranker, tiny_reranker_config
    ecfg, rcfg = tiny_embedder_config(512), tiny_reranker_config(512)
    return {"dense": (build_model(tiny_cell.tiny_model_config()).init,
                      "weights"),
            "embedder": (lambda k: init_embedder(k, ecfg), "embedder"),
            "reranker": (lambda k: init_reranker(k, rcfg), "reranker")}


@pytest.mark.parametrize("tree", ["dense", "embedder", "reranker"])
def test_trees_draw_as_before_with_the_family_rules(tree):
    dense = harness.family_module("dense")
    init_fn, stream = _trees()[tree]
    seed = 2**31 + 12345
    got = weights.make(init_fn, seed, stream, rules=dense.FAN_IN)
    want = _draw_as_before(init_fn, seed, stream)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_rules_draw_a_leaf_the_shared_table_refuses():
    def init(key):
        return {"router": jnp.zeros((2, 16, 8), jnp.bfloat16),
                "w_up": jnp.zeros((16, 32), jnp.float32)}

    with pytest.raises(ValueError, match="'router'"):
        weights.make(init, tiny_cell.SEED)
    w = weights.make(init, tiny_cell.SEED, rules={"router": (-2,)})
    assert w["router"].dtype == jnp.bfloat16 and w["router"].shape == (2, 16,
                                                                       8)
    r = np.asarray(w["router"], np.float32)
    assert 0.5 * 16 ** -0.5 < r.std() < 1.5 * 16 ** -0.5
    # the shared table's leaves draw as they do without the rule
    plain = weights.make(lambda k: {"router": jnp.zeros((2, 16, 8)),
                                    "w_up": jnp.zeros((16, 32))},
                         tiny_cell.SEED, rules={"router": (-2,)})
    assert np.array_equal(np.asarray(w["w_up"]), np.asarray(plain["w_up"]))


def _router(cfg):
    return types.SimpleNamespace(**{k: cfg["router"][k] for k in ROUTER_KEYS})


def test_check_config_takes_its_fields_from_the_family_module():
    dense = harness.family_module("dense")
    cfg = tiny_cell.cell().config
    mc, rc = tiny_cell.tiny_model_config(), _router(cfg)
    harness.check_config(cfg, mc, rc, dense)
    assert dense.program_fields(cfg["model"])["resolved_head_dim"] == 32
    bad = dict(cfg, model=dict(cfg["model"], intermediate_size=512))
    with pytest.raises(ValueError, match="d_ff"):
        harness.check_config(bad, mc, rc, dense)
    stated = dict(cfg, model=dict(cfg["model"], head_dim=64))
    with pytest.raises(ValueError, match="resolved_head_dim"):
        harness.check_config(stated, mc, rc, dense)
    # only what the family module names is compared
    narrow = types.SimpleNamespace(
        program_fields=lambda m: {"d_model": m["hidden_size"]})
    harness.check_config(bad, mc, rc, narrow)
    experts = types.SimpleNamespace(program_fields=lambda m: {
        "num_experts": 64, "experts_per_token": 6})
    with pytest.raises(ValueError, match="num_experts"):
        harness.check_config(cfg, mc, rc, experts)
    with pytest.raises(ValueError, match="router.band"):
        harness.check_config(cfg, mc, _router(dict(
            cfg, router=dict(cfg["router"], band=0.1))), dense)
