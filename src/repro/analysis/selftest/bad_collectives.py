"""Seeded violations: collective-axis, collective-budget,
collective-fp32. Fixture only — never imported or executed."""
import jax
import jax.numpy as jnp


def local(x):
    a = jax.lax.psum(x.astype(jnp.float32), "model")
    b = jax.lax.psum(a, "data")     # wrong axis, bf16, 2nd psum on path
    return b


def build(mesh):
    return jax.shard_map(local, mesh=mesh, in_specs=("model",),
                         out_specs=("model",), axis_names={"model"})


def stray(x):
    return jax.lax.all_gather(x, "model")   # outside any shard_map body
