"""Seeded model weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights it serves (the
language model, the embedder and the reranker), so the references read
the same arrays and nothing the program computed.  The layout (tree,
shapes, dtypes) is read from the program's own ``init`` by
``jax.eval_shape``, without running it; each leaf is then drawn from the
run's seed by its name: norm scales 1, norm biases 0, QKV biases
N(0, 0.1), the token embedding N(0, 0.02), and every matrix N(0, 1/fan_in)
by the fan-in table below, which the language model, the embedder and the
reranker share, plus the rules a model family's reference module adds
(its ``FAN_IN``).  A leaf of another name is an error: the reference
would not know how to read it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> axes (counted from the end) whose product is the fan-in
_FAN_IN = {"w_q": (-3,), "w_k": (-3,), "w_v": (-3,), "w_o": (-3, -2),
           "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
           "score_head": (-2,)}


def jax_key(seed: int, stream: str):
    """A JAX key for one named stream of a run seed of any size."""
    words = np.random.SeedSequence(
        [seed, int.from_bytes(stream.encode(), "little")]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def make(init_fn, seed: int, stream: str = "weights", rules=None):
    """Weights shaped as ``init_fn(key)`` would make them, drawn from one
    named stream of ``seed``; ``init_fn`` is only traced for its output
    shapes.  ``rules`` adds fan-in rules, in the form of ``_FAN_IN``."""
    fan_in = {**_FAN_IN, **(rules or {})}
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for path, sd in leaves:
        name = _leaf_name(path)
        if name == "scale":
            plan.append(("ones", 0.0))
        elif name == "bias":
            plan.append(("zeros", 0.0))
        elif name.startswith("b_"):
            plan.append(("normal", 0.1))
        elif name == "embed":
            plan.append(("normal", 0.02))
        elif name in fan_in:
            fan = int(np.prod([sd.shape[a] for a in fan_in[name]]))
            plan.append(("normal", fan ** -0.5))
        else:
            raise ValueError(f"no rule to draw weight leaf {name!r} "
                             f"({jax.tree_util.keystr(path)})")

    def draw(key):
        out = []
        for i, ((_, sd), (kind, std)) in enumerate(zip(leaves, plan)):
            if kind in ("ones", "zeros"):
                fill = jnp.ones if kind == "ones" else jnp.zeros
                out.append(fill(sd.shape, sd.dtype))
            else:
                k = jax.random.fold_in(key, i)
                # drawn in float32: a bfloat16 draw can round its uniform
                # to the ends of the interval, where the inverse error
                # function is infinite
                out.append((std * jax.random.normal(k, sd.shape, jnp.float32)
                            ).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(jax_key(seed, stream))
