"""Microbatched pipeline parallelism over one mesh axis.

``pipeline_apply`` runs a GPipe-style schedule under ``shard_map``: stage
parameters are sharded over ``axis`` (leading dim = number of stages), the
input batch is split into microbatches, and activations flow stage-to-stage
through ``lax.ppermute`` ring shifts.  The schedule is unrolled at trace time
(n_microbatches + n_stages - 1 ticks), so the compiled program is a straight
line of compute/permute pairs XLA can overlap.

The stage function must be shape-preserving: ``stage_fn(stage_params, x) ->
y`` with ``y.shape == x.shape`` (the residual-stream contract every model in
the zoo satisfies).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn, params, x, mesh, axis: str,
                   n_microbatches: int):
    """Apply ``n_stages`` chained stages to ``x`` with pipeline parallelism.

    Args:
      stage_fn: ``(stage_params, microbatch) -> microbatch`` (shape-preserving).
      params: pytree whose leaves all have leading dim ``mesh.shape[axis]``;
        leaf ``[s]`` holds stage ``s``'s parameters.
      x: batched input; ``x.shape[0]`` must divide by ``n_microbatches``.
      mesh: the device mesh; ``axis``: the pipeline axis name.
    Returns:
      The sequential composition ``stage_{S-1}(... stage_0(x))``, replicated.
    """
    if axis not in mesh.shape:
        raise KeyError(f"mesh has no axis {axis!r}; axes: {mesh.axis_names}")
    n_stages = int(mesh.shape[axis])
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} "
                         "microbatches")
    for leaf in jax.tree.leaves(params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"param leading dim {leaf.shape[0]} != n_stages {n_stages}")
    mb_shape = (n_microbatches, B // n_microbatches) + x.shape[1:]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def run(p, xr):
        p = jax.tree.map(lambda a: a[0], p)   # drop the sharded stage dim
        idx = jax.lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == n_stages - 1
        mbs = xr.reshape(mb_shape)
        out_buf = jnp.zeros(mb_shape, xr.dtype)
        carry = jnp.zeros(mb_shape[1:], xr.dtype)
        for t in range(n_microbatches + n_stages - 1):
            feed = mbs[min(t, n_microbatches - 1)]
            inp = jnp.where(is_first, feed, carry)
            out = stage_fn(p, inp).astype(xr.dtype)
            o = t - (n_stages - 1)
            if o >= 0:  # drain: the last stage owns microbatch ``o`` now
                out_buf = jnp.where(is_last, out_buf.at[o].set(out), out_buf)
            carry = jax.lax.ppermute(out, axis, perm)
        # only the last stage holds real outputs; mask + psum replicates them
        res = jnp.where(is_last, out_buf, jnp.zeros_like(out_buf))
        return jax.lax.psum(res, axis)

    fn = jax.shard_map(run, mesh=mesh, in_specs=(PS(axis), PS()),
                       out_specs=PS(), check_vma=False)
    return fn(params, x).reshape(x.shape)
