"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

shard_map + collective_permute: each device owns one stage's parameters
(stacked leaf layout, leading stage dim sharded over 'pipe').  The
schedule runs M + P - 1 ticks; on each tick every device applies its stage
to the microbatch it holds and permutes activations one stage forward —
the classic GPipe fill/drain bubble with P-1 idle slots.

This is the optional large-depth axis (DESIGN.md §6): the graded meshes
use (data, model); 'pipe' composes on top for 1000+-node layouts, e.g.
(pipe=4, data=16, model=8).  Forward-only here covers the serving and
bubble-analysis use cases; training composes this with jax.grad through
shard_map."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_params: Any, x_micro: jax.Array, *,
                     stage_fn: Callable[[Any, jax.Array], jax.Array],
                     mesh, axis: str = "pipe") -> jax.Array:
    """stage_params: tree with leading dim = n_stages (sharded over axis);
    x_micro: (M, mb, ...) microbatches (replicated).  Returns (M, mb, ...)
    outputs of the final stage."""
    n_stages = dict(mesh.shape)[axis]
    M = x_micro.shape[0]

    def per_device(params_local, xs):
        # params_local: leading dim 1 (this device's stage)
        params1 = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        ticks = M + n_stages - 1
        # mark carries as device-varying over the pipe axis (shard_map vma)
        vary = lambda v: jax.lax.pcast(v, (axis,), to="varying")  # noqa: E731
        buf = vary(jnp.zeros_like(xs[0]))
        outs = vary(jnp.zeros_like(xs))

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_idx = jnp.clip(t, 0, M - 1)
            incoming = jnp.where(stage == 0,
                                 jnp.where(t < M, 1, 0), 0)
            inp = jnp.where(incoming, xs[mb_idx], buf)
            y = stage_fn(params1, inp)
            # last stage records its finished microbatch (t - (P-1))
            done_idx = t - (n_stages - 1)
            record = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
            ci = jnp.clip(done_idx, 0, M - 1)
            outs = outs.at[ci].set(jnp.where(record, y, outs[ci]))
            # shift activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # deliver final outputs from the last stage to everyone
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(per_device, mesh=mesh,
                     in_specs=(pspec, P()), out_specs=P())(
        stage_params, x_micro)


def reference_forward(stage_params: Any, x_micro: jax.Array, *,
                      stage_fn: Callable[[Any, jax.Array], jax.Array]
                      ) -> jax.Array:
    """Sequential oracle: apply all stages to every microbatch."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def run_one(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x

    return jax.vmap(run_one)(x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (P-1)/(M+P-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


__all__ = ["pipeline_forward", "reference_forward", "bubble_fraction"]
