"""Seeded ENTRY_POINTS for the collectives checker: three kernels, each
violating one rule of the family. Loaded via --collectives-entry-module
(or the `collectives_entry_module` option); the checker builds each on
its virtual mesh and traces abstractly — nothing executes."""
import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from cylon_tpu.analysis.collectives import EntryPoint, _sds


def _bad_axis_fn(mesh):
    """psum over an axis name the mesh does not declare — fails at
    trace time (collectives/trace-error)."""
    spec = P(mesh.axis_names[0])

    def kernel(x):
        return jax.lax.psum(x, "not_an_axis")

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,),
                             out_specs=P()))


def _bad_a2a_fn(mesh):
    """all_to_all with split_axis != concat_axis — traces fine but
    transposes received blocks (collectives/all-to-all-axes)."""
    axis = mesh.axis_names[0]
    spec = P(axis)

    def kernel(x):
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=1,
                                  tiled=False)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def _f64_fn(mesh):
    """A stray np.float64 scalar silently promotes the whole lane
    (collectives/f64-promotion)."""
    spec = P(mesh.axis_names[0])

    def kernel(x):
        return x * np.float64(2.0)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def _clean_fn(mesh):
    """Control: a correct psum must produce no finding."""
    axis = mesh.axis_names[0]
    spec = P(axis)

    def kernel(x):
        return jax.lax.psum(x, axis)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(spec,),
                             out_specs=P()))


ENTRY_POINTS = [
    EntryPoint("bad_axis", "fixtures/collectives_bad.py",
               _bad_axis_fn,
               lambda m: (_sds((64,), jnp.float32),)),
    EntryPoint("bad_all_to_all", "fixtures/collectives_bad.py",
               _bad_a2a_fn,
               lambda m: (_sds((16, 4, 8), jnp.float32),)),
    EntryPoint("f64_promotion", "fixtures/collectives_bad.py",
               _f64_fn,
               lambda m: (_sds((64,), jnp.float32),)),
    EntryPoint("clean", "fixtures/collectives_bad.py",
               _clean_fn,
               lambda m: (_sds((64,), jnp.float32),)),
]
