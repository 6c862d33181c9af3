"""``constrain`` is the identity off-mesh and a real constraint on one."""
import jax
import jax.numpy as jnp

from repro.models.sharding_utils import BATCH, constrain


def test_constrain_off_mesh_returns_x():
    x = jnp.ones((4, 8))
    assert constrain(x, BATCH, None) is x


def test_constrain_under_mesh_applies_the_spec():
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with jax.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(lambda x: constrain(x, BATCH, None))(
            jnp.ones((4, 8)))
    eqns = [e for e in jaxpr.eqns if e.primitive.name == "sharding_constraint"]
    assert len(eqns) == 1
    assert eqns[0].params["sharding"].spec == jax.sharding.PartitionSpec(
        "data", None)
